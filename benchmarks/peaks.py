"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.
Copied from ``bench.py`` ``DEVICE_PEAKS`` (PERF.md, Open questions: the
original is for a later PR to delete). A device that is not in the table is
an error, not a default."""

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device kind {device_kind!r}; "
            "add it to benchmarks/peaks.py with its source"
        ) from None
