"""The traffic generator: seeded, and the same work for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks import traffic

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_multiset_in_another_order(path):
    mix = json.loads(path.read_text())
    load = {"rate": 2.0, "clients": 5}
    a, lead = traffic.plan(mix, load, 20.0, 1, 1000, 4096)
    b, _ = traffic.plan(mix, load, 20.0, 2**31 + 5, 1000, 4096)
    assert sorted(r.prompt_tokens for r in a) == sorted(r.prompt_tokens for r in b)
    assert sorted(r.output_tokens for r in a) == sorted(r.output_tokens for r in b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    assert all(len(r.prompt.split()) == r.prompt_tokens for r in a)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= r.prompt_tokens <= hi for r in a)
    if mix["arrivals"] == "closed":  # an order, no due times
        assert len(a) == 5 * mix["requests_per_client"]
        assert [r.due for r in a] == list(range(len(a)))
    else:
        assert [r.due for r in a] != [r.due for r in b]
        in_window = [r for r in a if r.due >= lead]
        assert len(in_window) == 40
        assert all(lead <= r.due < lead + 20.0 for r in in_window)


def test_same_seed_same_plan():
    mix = json.loads(MIXES[0].read_text())
    assert traffic.plan(mix, {"rate": 3.0, "clients": 2}, 10.0, 7, 500, 4096) == traffic.plan(
        mix, {"rate": 3.0, "clients": 2}, 10.0, 7, 500, 4096)


def test_poisson_gaps_have_unit_mean_and_a_tail():
    g = traffic.arrival_gaps("poisson", 200)
    assert g.mean() == pytest.approx(1.0)
    assert g.max() > 4.0 and g.min() < 0.01
    assert np.all(traffic.arrival_gaps("uniform", 5) == 1.0)


def test_a_request_longer_than_the_context_is_refused():
    mix = {"prompt_tokens": {"dist": "fixed", "value": 100},
           "output_tokens": {"dist": "fixed", "value": 100}}
    with pytest.raises(ValueError):
        traffic.plan(mix, {"rate": 1.0}, 5.0, 0, 100, 128)


def test_blocked_order_keeps_the_multiset_and_spreads_it():
    rng = np.random.default_rng(3)
    values = np.arange(40)
    out = traffic.blocked_order(values, rng)
    assert sorted(out) == list(values)
    # every block of 8 holds one value from each fifth of the range
    for b in range(5):
        block = out[8 * b: 8 * b + 8]
        assert sorted(v // 5 for v in block) == list(range(8))
    assert list(out) != list(traffic.blocked_order(values, np.random.default_rng(4)))
