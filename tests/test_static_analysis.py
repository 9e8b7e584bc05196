"""mstcheck: the self-scan CI gate plus checker unit coverage.

``test_repo_self_scan`` IS the static-analysis gate: it runs every rule
family over ``mlx_sharding_tpu/`` and fails on any finding that is neither
inline-suppressed (``# mst: allow(<rule>): <reason>``) nor recorded in
``mlx_sharding_tpu/analysis/baseline.json`` — no external runner needed.
The fixture corpus in ``tests/analysis_fixtures/`` pins each rule to a
minimal known-bad snippet: exactly one finding, with the expected span.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.analysis.core import (
    DEFAULT_BASELINE,
    analyze_paths,
    load_baseline,
    main,
    write_baseline,
)
from mlx_sharding_tpu.analysis.runtime import LockOrderRecorder

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "mlx_sharding_tpu"
FIXTURES = REPO / "tests" / "analysis_fixtures"

# fixture file -> (rule, line, col) of the single expected finding
EXPECTED = {
    "mst001_bad_suppression.py": ("MST001", 6, 0),
    "mst101_host_effect.py": ("MST101", 8, 15),
    "mst102_sync_hot_path.py": ("MST102", 7, 11),
    "mst102_block_until_ready.py": ("MST102", 6, 4),
    "mst103_recompile_hazard.py": ("MST103", 9, 16),
    "mst104_double_harvest.py": ("MST104", 8, 11),
    "mst105_dense_dequant.py": ("MST105", 10, 4),
    "mst106_sync_spill.py": ("MST106", 11, 11),
    "mst107_wall_clock_deadline.py": ("MST107", 7, 22),
    "mst107_monotonic_bypass.py": ("MST107", 12, 15),
    "mst108_block_migration.py": ("MST108", 8, 10),
    "mst109_demand_import.py": ("MST109", 10, 13),
    "mst110_spawn_upload.py": ("MST110", 10, 15),
    "mst111_prefix_import.py": ("MST111", 10, 13),
    "mst201_unlocked_attr.py": ("MST201", 15, 0),
    "mst202_check_then_act.py": ("MST202", 14, 0),
    "mst203_lock_cycle.py": ("MST203", 17, 0),
    "mst301_generator_leak.py": ("MST301", 7, 8),
    "mst302_alloc_leak.py": ("MST302", 11, 12),
    "mst303_unknown_fault_site.py": ("MST303", 6, 4),
    "mst304/scheduler.py": ("MST304", 1, 0),
    "mst112_trace_hot_path.py": ("MST112", 11, 4),
    "mst113_control_plane_in_tick.py": ("MST113", 10, 21),
    "mst114_spec_policy_sync.py": ("MST114", 6, 15),
    "mst115_prefix_federation_in_tick.py": ("MST115", 10, 7),
    "mst116_latent_reconstruct_in_tick.py": ("MST116", 10, 12),
    "mst002_dead_suppression.py": ("MST002", 5, 0),
    "mst401_exception_leak.py": ("MST401", 6, 0),
    "mst402_double_release.py": ("MST402", 8, 4),
    "mst403_release_escaped.py": ("MST403", 7, 4),
    "mst404_early_return_leak.py": ("MST404", 7, 0),
    "mst501_cross_role_write.py": ("MST501", 17, 0),
    "mst502_split_lockset.py": ("MST502", 20, 0),
    "mst503_bare_container.py": ("MST503", 17, 0),
    "mst504_blocking_under_tick_lock.py": ("MST504", 21, 0),
}


# ----------------------------------------------------------- the CI gate
def test_repo_self_scan_is_clean():
    baseline = load_baseline(DEFAULT_BASELINE) if DEFAULT_BASELINE.exists() else None
    report = analyze_paths([str(PACKAGE)], baseline=baseline)
    rendered = "\n".join(f.render() for f in report.findings)
    assert not report.findings, (
        f"mstcheck found new violations in mlx_sharding_tpu/:\n{rendered}\n"
        "Fix them, add an inline '# mst: allow(<rule>): <reason>', or (for "
        "grandfathered findings only) regenerate the baseline with "
        "`python -m mlx_sharding_tpu.analysis mlx_sharding_tpu/ "
        "--write-baseline`."
    )
    assert report.files_scanned > 40  # the scan actually covered the tree


def test_one_benchmark_program_and_one_account_of_speed():
    """``benchmarks/run.py`` is the one benchmark and ``PERF.md`` the one
    account of speed (PR 30). No source file or user-facing document names
    the deleted second instrument, the records it wrote or the switch only
    its A/B set. The history (``CHANGES.md``, ``PERF.md``, ``ROADMAP.md``,
    ``SURVEY.md``, ``ISSUE.md``) may; ``benchmarks/`` has its own tests."""
    gone = ("bench" + ".py", "BENCH" + "_DETAIL", "MULTICHIP" + "_r",
            "MST_FLASH" + "_DECODE")
    skip = {"benchmarks", "chiprun_out", "__pycache__"}
    named = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip
                   and (not d.startswith(".") or d == ".claude")]
        for name in files:
            if name.endswith(".py") or name in ("README.md", "PARITY.md",
                                                "SKILL.md"):
                path = Path(root, name)
                text = path.read_text(errors="replace")
                named += [f"{path.relative_to(REPO)}: {g}"
                          for g in gone if g in text]
    assert not named, named
    for f in ("bench" + ".py", "BENCH" + "_DETAIL.json", "ADVICE.md"):
        assert not (REPO / f).exists(), f


def test_which_kernel_runs_is_decided_in_ops_and_by_nobody_else():
    """A kernel is chosen by one predicate per op, inside ``ops/``, from the
    backend, the operands' shapes and an ``interpret`` argument (PR 47): no
    file of ``ops/`` reads the environment, no file of the package names one
    of the six removed switches, and the 4-bit GEMV that no cell dispatched
    to is gone with its start-up sweep."""
    switches = re.compile(
        r"MST_(QMM|QMM_GEMV|QMM_AUTOTUNE|FUSE_PROJ|PAGED_KERNEL|FLASH)\b")
    named = []
    for path in sorted(PACKAGE.rglob("*.py")):
        text = path.read_text()
        rel = path.relative_to(REPO)
        named += [f"{rel}: {m.group(0)}" for m in switches.finditer(text)]
        if path.parent.name == "ops" and "os.environ" in text:
            named.append(f"{rel}: os.environ")
    assert not named, named
    from mlx_sharding_tpu.ops import quant_matmul

    for name in ("quant_gemv_pipelined", "autotune_gemv"):
        assert not hasattr(quant_matmul, name), name


def test_static_lock_graph_is_acyclic_with_expected_edges():
    report = analyze_paths([str(PACKAGE)], baseline=None)
    edges = {(e.src, e.dst) for e in report.lock_edges}
    # metrics render() holds its lock while reading the engine's locked
    # accessors: the one cross-class ordering the stack relies on
    assert ("ServingMetrics.lock",
            "ContinuousBatcher._admission_lock") in edges
    assert ("ReplicaSet._serial_locks[*]",
            "ContinuousBatcher._admission_lock") in edges
    cycle = LockOrderRecorder().find_cycle(extra_edges=edges)
    assert cycle is None, f"static lock-order cycle: {' -> '.join(cycle)}"


def test_cli_module_exit_codes():
    # the acceptance contract, verbatim, via the real entry point; the
    # non-zero-on-findings side runs in-process (main() == 1) per fixture
    clean = subprocess.run(
        [sys.executable, "-m", "mlx_sharding_tpu.analysis",
         "mlx_sharding_tpu/"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 finding(s)" in clean.stdout


# ------------------------------------------------------- fixture corpus
@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_fixture_fires_exactly_once_with_span(name):
    rule, line, col = EXPECTED[name]
    report = analyze_paths([str(FIXTURES / name)], baseline=None)
    assert len(report.findings) == 1, [f.render() for f in report.findings]
    f = report.findings[0]
    assert (f.rule, f.line, f.col) == (rule, line, col), f.render()
    # and the CLI exits non-zero on it (no baseline applies to tests/)
    assert main([str(FIXTURES / name)]) == 1


def test_every_fixture_is_covered():
    on_disk = {
        p.relative_to(FIXTURES).as_posix()
        for p in FIXTURES.rglob("*.py")
    }
    assert on_disk == set(EXPECTED)


# ------------------------------------------------- suppression workflow
def test_suppression_with_reason_is_honored(tmp_path):
    bad = tmp_path / "counter.py"
    bad.write_text(
        "import threading\n\n\n"
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._count = 0\n\n"
        "    def incr(self):\n"
        "        with self._lock:\n"
        "            self._count += 1\n\n"
        "    def snapshot(self):\n"
        "        # mst: allow(MST201): racy read is fine for a gauge\n"
        "        return self._count\n"
    )
    report = analyze_paths([str(bad)], baseline=None)
    assert report.findings == []


def test_suppression_without_reason_is_mst001(tmp_path):
    bad = tmp_path / "counter.py"
    bad.write_text(
        "import threading\n\n\n"
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._count = 0\n\n"
        "    def incr(self):\n"
        "        with self._lock:\n"
        "            self._count += 1\n\n"
        "    def snapshot(self):\n"
        "        # mst: allow(MST201)\n"
        "        return self._count\n"
    )
    report = analyze_paths([str(bad)], baseline=None)
    rules = sorted(f.rule for f in report.findings)
    # the reasonless allow does NOT silence the finding and adds MST001
    assert rules == ["MST001", "MST201"]


def test_unparseable_file_is_mst000(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def broken(:\n    pass\n")
    report = analyze_paths([str(bad)], baseline=None)
    assert [f.rule for f in report.findings] == ["MST000"]


# --------------------------------------------------- baseline workflow
def test_baseline_grandfathers_findings(tmp_path):
    src = (FIXTURES / "mst201_unlocked_attr.py").read_text()
    bad = tmp_path / "counter.py"
    bad.write_text(src)

    first = analyze_paths([str(bad)], baseline=None)
    assert len(first.findings) == 1

    baseline_path = tmp_path / "baseline.json"
    write_baseline(baseline_path, first.findings)
    again = analyze_paths([str(bad)], baseline=load_baseline(baseline_path))
    assert again.findings == []
    assert [f.rule for f in again.baselined] == ["MST201"]

    # the key is line-number-free: shifting the file must not invalidate it
    bad.write_text("# a new leading comment\n" + src)
    shifted = analyze_paths([str(bad)], baseline=load_baseline(baseline_path))
    assert shifted.findings == []
    assert [f.rule for f in shifted.baselined] == ["MST201"]


def test_write_baseline_cli_roundtrip(tmp_path):
    bad = tmp_path / "counter.py"
    bad.write_text((FIXTURES / "mst201_unlocked_attr.py").read_text())
    baseline_path = tmp_path / "baseline.json"

    assert main([str(bad), "--baseline", str(baseline_path),
                 "--write-baseline"]) == 0
    data = json.loads(baseline_path.read_text())
    assert data["version"] == 1 and len(data["findings"]) == 1
    assert main([str(bad), "--baseline", str(baseline_path)]) == 0
    assert main([str(bad), "--baseline", str(baseline_path),
                 "--no-baseline"]) == 1


def test_stale_baseline_entry_is_mst003_hard_error(tmp_path):
    """Fixing the grandfathered bug must surface the baseline entry as a
    hard error with the regeneration hint — never silent rot."""
    bad = tmp_path / "counter.py"
    bad.write_text((FIXTURES / "mst201_unlocked_attr.py").read_text())
    baseline_path = tmp_path / "baseline.json"
    write_baseline(baseline_path, analyze_paths([str(bad)],
                                                baseline=None).findings)
    bad.write_text("x = 1\n")  # the bug is gone; the entry goes stale
    report = analyze_paths([str(bad)], baseline=load_baseline(baseline_path),
                           baseline_path=baseline_path)
    assert [f.rule for f in report.findings] == ["MST003"]
    f = report.findings[0]
    assert f.path == str(baseline_path)
    assert "--write-baseline" in f.message and "MST201" in f.message
    assert main([str(bad), "--baseline", str(baseline_path)]) == 1


# ------------------------------------------------- incremental cache
def test_incremental_cache_reuses_and_invalidates(tmp_path):
    src = tmp_path / "m.py"
    src.write_text((FIXTURES / "mst201_unlocked_attr.py").read_text())
    cache = tmp_path / "cache.json"

    cold = analyze_paths([str(src)], baseline=None, cache_path=cache)
    assert (cold.cache_hits, cold.cache_misses) == (0, 1)
    warm = analyze_paths([str(src)], baseline=None, cache_path=cache)
    assert (warm.cache_hits, warm.cache_misses) == (1, 0)
    # cached facts reproduce the finding exactly
    assert [(f.rule, f.line, f.col) for f in warm.findings] == \
        [(f.rule, f.line, f.col) for f in cold.findings]

    src.write_text("x = 1\n")  # content hash changes -> full recheck
    fixed = analyze_paths([str(src)], baseline=None, cache_path=cache)
    assert (fixed.cache_hits, fixed.cache_misses) == (0, 1)
    assert fixed.findings == []


def test_cache_preserves_suppressions(tmp_path):
    src = tmp_path / "counter.py"
    src.write_text(
        "import threading\n\n\n"
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._count = 0\n\n"
        "    def incr(self):\n"
        "        with self._lock:\n"
        "            self._count += 1\n\n"
        "    def snapshot(self):\n"
        "        # mst: allow(MST201): racy read is fine for a gauge\n"
        "        return self._count\n"
    )
    cache = tmp_path / "cache.json"
    assert analyze_paths([str(src)], baseline=None,
                         cache_path=cache).findings == []
    warm = analyze_paths([str(src)], baseline=None, cache_path=cache)
    assert warm.cache_hits == 1 and warm.findings == []


def test_cli_json_format_reports_cache_and_registry(tmp_path, capsys):
    fixture = FIXTURES / "mst402_double_release.py"
    cache = tmp_path / "cache.json"
    assert main([str(fixture), "--format", "json",
                 "--cache", str(cache)]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["files_scanned"] == 1 and out["cache_misses"] == 1
    assert [f["rule"] for f in out["findings"]] == ["MST402"]
    kinds = {r["kind"] for r in out["resource_registry"]}
    assert {"prefix.lease", "weights.lease", "replica.probe",
            "scheduler.page"} <= kinds
    # warm run serves the same findings from the cache
    assert main([str(fixture), "--format", "json",
                 "--cache", str(cache)]) == 1
    out2 = json.loads(capsys.readouterr().out)
    assert out2["cache_hits"] == 1
    assert out2["findings"] == out["findings"]


# --------------------------------------------- MST40x path sensitivity
def test_mst40x_clean_idioms_stay_clean(tmp_path):
    """The verifier must be quiet on the repo's own disciplined shapes:
    try/finally, None-refined early return, release delegated to a helper
    (interprocedural summary), and ownership transfer via return."""
    good = tmp_path / "clean.py"
    good.write_text(
        "def protected(store, owner, digests, pages):\n"
        "    lease = store.register(owner, digests, pages, digests, 64)\n"
        "    try:\n"
        "        broadcast(pages)\n"
        "    finally:\n"
        "        lease.release()\n"
        "\n\n"
        "def optional(store, owner, digests, pages):\n"
        "    lease = store.register(owner, digests, pages, digests, 64)\n"
        "    if lease is None:\n"
        "        return None\n"
        "    try:\n"
        "        broadcast(pages)\n"
        "    finally:\n"
        "        lease.release()\n"
        "    return True\n"
        "\n\n"
        "def delegated(store, owner, digests, pages):\n"
        "    lease = store.register(owner, digests, pages, digests, 64)\n"
        "    _finish(lease)\n"
        "\n\n"
        "def _finish(lease):\n"
        "    lease.release()\n"
        "\n\n"
        "def spawn(store, owner, digests, pages, make_engine):\n"
        "    lease = store.register(owner, digests, pages, digests, 64)\n"
        "    try:\n"
        "        return make_engine(lease)\n"
        "    except BaseException:\n"
        "        lease.release()\n"
        "        raise\n"
        "\n\n"
        "def broadcast(pages):\n"
        "    raise RuntimeError\n"
    )
    report = analyze_paths([str(good)], baseline=None)
    assert report.findings == [], [f.render() for f in report.findings]
