"""Percentile, due-time and histogram arithmetic of the yardstick."""
import math

import pytest

from benchmarks import run, stats


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert math.isnan(stats.percentile([], 50))


def test_failed_requests_rank_at_infinity():
    xs = [10.0] * 18 + [math.inf] * 2  # 10 % failed
    assert stats.percentile(xs, 50) == 10.0
    assert stats.percentile(xs, 90) == 10.0
    assert math.isinf(stats.percentile(xs, 95))
    assert stats.finite(stats.percentile(xs, 95)) == stats.DID_NOT_COMPLETE


def _rec(due, first, last, got, done, ok=True, cut=False):
    # tokens arrive evenly between the first and the last chunk
    chunks = [] if first is None else [
        (first + (last - first) * i / max(got - 1, 1), 1) for i in range(got)]
    return {"due": due, "sent": due + 0.001, "first": first, "last": last,
            "got": got, "done": done, "ok": ok, "cut": cut, "chunks": chunks,
            "asked": got, "prompt_tokens": 10,
            "error": None if ok or cut else "x"}


def read(kind, name, ctx):
    return run.load_reader(kind, name)(ctx)


def test_end_to_end_times_from_due_and_counts_the_windows_tokens():
    in_window = [
        _rec(100.0, 100.2, 101.2, 11, 101.3),   # ttft 200 ms, tpot 100 ms
        _rec(105.0, 105.4, 105.9, 6, 106.0),    # ttft 400 ms, tpot 100 ms
        _rec(109.5, 109.85, 111.85, 21, 111.9),  # ttft 350 ms; 2 of its 21 tokens inside
        _rec(109.9, None, None, 0, None, ok=False),
        _rec(109.95, None, None, 0, None, ok=False, cut=True),  # no failure, no time
    ]
    lead = [_rec(99.0, 99.5, 100.5, 5, 100.6)]  # due before; 3 of 5 inside
    ctx = {"records": in_window, "all_records": in_window + lead, "w0": 100.0,
           "w1": 110.0, "setup_s": 12.5}
    assert read("end_to_end", "setup_s", ctx) == 12.5
    assert read("end_to_end", "ttft_ms.p50", ctx) == pytest.approx(350.0)
    # 4 requests: the 95th percentile is the failed one
    assert read("end_to_end", "ttft_ms.p95", ctx) == stats.DID_NOT_COMPLETE
    assert read("end_to_end", "tpot_ms.p95", ctx) == stats.DID_NOT_COMPLETE
    # tokens that arrived inside [w0, w1), whichever request: 11 + 6 + 2 + 3
    assert read("layer_metrics", "out_tok_s.arrived", ctx) == pytest.approx(2.2)
    # spread over the time since the stream's previous chunk: the token that
    # arrived at 110.05 was made from 109.95 on, half of it inside (+0.5), and
    # the one that arrived at 100.0 was made before the window opened (-1)
    assert read("end_to_end", "out_tok_s", ctx) == pytest.approx(2.15)
    assert read("layer_metrics", "ttft_ms.p50.sat", ctx) == pytest.approx(350.0)
    assert read("layer_metrics", "tpot_ms.p50.sat", ctx) == pytest.approx(100.0)


def test_tokens_of_a_burst_are_spread_over_the_time_they_took():
    """Blocks of 8 tokens every 1.5 s: counted at arrival the window holds 2
    or 3 blocks with where its edge falls, spread it holds 4 s of a steady
    16/3 tokens a second (less the first chunk's lead, which has no span)."""
    def stream(phase):
        return [{"chunks": [(phase + 1.5 * i, 8) for i in range(12)]}]
    arrived = {stats.tokens_in_window(stream(p), 6.0, 10.0, False) for p in (0.1, 0.7, 1.3)}
    spread = [stats.tokens_in_window(stream(p), 6.0, 10.0, True) for p in (0.1, 0.7, 1.3)]
    assert arrived == {16, 24}
    assert spread == pytest.approx([4 * 8 / 1.5] * 3)


def test_histogram_window_delta_and_quantile():
    before = stats.parse_prometheus(
        '# TYPE h histogram\nh_bucket{le="0.1"} 5\nh_bucket{le="0.2"} 5\n'
        'h_bucket{le="+Inf"} 5\nh_count 5\n')
    after = stats.parse_prometheus(
        'h_bucket{le="0.1"} 5\nh_bucket{le="0.2"} 15\nh_bucket{le="+Inf"} 15\nh_count 15\n')
    buckets = stats.histogram_delta(before, after, "h")
    assert buckets == [(0.1, 0.0), (0.2, 10.0), (math.inf, 0.0)]
    assert stats.histogram_quantile(buckets, 0.5) == pytest.approx(0.15)
    assert stats.histogram_quantile([(0.1, 0.0)], 0.5) is None


def test_scalar_sums_label_sets():
    m = stats.parse_prometheus('a_total{x="1"} 2\na_total{x="2"} 3\nb 7\n')
    assert stats.scalar(m, "a_total") == 5
    assert stats.scalar(m, "b") == 7
    assert stats.scalar(m, "c", 0) == 0
