import re

import jax
import pytest

pytestmark = pytest.mark.quick
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.sample import (
    apply_logit_bias,
    apply_repetition_penalty,
    init_recent_tokens,
    make_sampler_params,
    nucleus_logits_batched,
    sample_token,
    sample_token_batched,
    stack_sampler_params,
    top_p_filter,
    transform_logits_batched,
    update_recent_tokens,
)


def test_greedy_at_zero_temperature():
    logits = jnp.asarray([[0.1, 5.0, -1.0, 2.0]])
    sp = make_sampler_params(temperature=0.0)
    tok, logprobs = sample_token(jax.random.PRNGKey(0), logits, sp)
    assert int(tok[0]) == 1
    np.testing.assert_allclose(
        np.asarray(logprobs), np.asarray(jax.nn.log_softmax(logits)), rtol=1e-5
    )


def test_categorical_respects_distribution():
    logits = jnp.asarray([[0.0, 10.0, 0.0, 0.0]])
    sp = make_sampler_params(temperature=1.0)
    toks = [
        int(sample_token(jax.random.PRNGKey(i), logits, sp)[0][0]) for i in range(20)
    ]
    assert toks.count(1) >= 18  # overwhelming mass on token 1


def test_top_p_filter_masks_tail():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    filtered = top_p_filter(logits, jnp.asarray(0.7))
    # 0.5 kept (0 mass before); 0.3 kept (0.5 < 0.7); 0.15 dropped (0.8 >= 0.7)
    f = np.asarray(filtered[0])
    assert np.isfinite(f[0]) and np.isfinite(f[1])
    assert np.isinf(f[2]) and np.isinf(f[3])


def test_top_p_one_keeps_all():
    logits = jnp.asarray([[1.0, 2.0, 3.0]])
    filtered = top_p_filter(logits, jnp.asarray(1.0))
    assert np.isfinite(np.asarray(filtered)).all()


def test_logit_bias():
    logits = jnp.zeros((1, 8))
    sp = make_sampler_params(temperature=0.0, logit_bias={5: 100.0})
    tok, _ = sample_token(jax.random.PRNGKey(0), logits, sp)
    assert int(tok[0]) == 5


def test_logit_bias_padding_is_noop():
    logits = jnp.asarray([[3.0, 1.0, 2.0]])
    biased = apply_logit_bias(
        logits, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.float32)
    )
    np.testing.assert_array_equal(np.asarray(biased), np.asarray(logits))


def test_repetition_penalty_matches_reference_rule():
    logits = jnp.asarray([[2.0, -2.0, 1.0, 0.5]])
    recent = jnp.asarray([[0, 1, -1, -1]])  # tokens 0 and 1 seen; -1 = empty
    out = np.asarray(apply_repetition_penalty(logits, recent, jnp.asarray(2.0)))[0]
    np.testing.assert_allclose(out, [1.0, -4.0, 1.0, 0.5])  # pos/2, neg*2, rest same


def test_repetition_penalty_via_sampler_changes_choice():
    logits = jnp.asarray([[5.0, 4.9, 0.0]])
    sp = make_sampler_params(temperature=0.0, repetition_penalty=2.0)
    recent = update_recent_tokens(init_recent_tokens(1, 4), jnp.asarray([0]))
    tok, _ = sample_token(jax.random.PRNGKey(0), logits, sp, recent)
    assert int(tok[0]) == 1  # token 0 penalized 5.0 -> 2.5


def test_recent_tokens_window_slides():
    r = init_recent_tokens(1, 3)
    for t in [7, 8, 9, 10]:
        r = update_recent_tokens(r, jnp.asarray([t]))
    np.testing.assert_array_equal(np.asarray(r), [[8, 9, 10]])


# --- the batched sampler: what a step runs is decided per batch -----------

V_BATCHED, W_BATCHED = 640, 8


def _parent_tokens(keys, logits, sp, recent):
    """The composition sample_token_batched had before it decided per batch
    (every row sorted and drawn, the choice made afterwards): the plain
    reference its tokens must equal bit for bit."""
    lo = transform_logits_batched(logits, recent, sp)
    greedy = jnp.argmax(lo, axis=-1)
    filtered = nucleus_logits_batched(lo, sp)  # vmap(top_p_filter): all rows sort
    sampled = jax.vmap(lambda k, l: jax.random.categorical(k, l))(keys, filtered)
    return jnp.where(sp.temperature > 0, sampled, greedy).astype(jnp.int32)


def _rows(*rows):
    """(temperature, top_p, active[, penalty[, bias]]) per row."""
    full = [(r + (None, None))[:5] for r in rows]
    sp = stack_sampler_params([
        make_sampler_params(t, p, pen, bias) for t, p, _, pen, bias in full
    ])
    return sp, jnp.asarray([a for _, _, a, _, _ in full])


_G, _D, _N = (0.0, 1.0, True), (0.9, 1.0, True), (1.3, 0.6, True)
BATCHED_CASES = {
    "all_greedy": [_G] * 6,
    "all_sampled_top_p_1": [_D, (0.5, 1.0, True), (2.0, 1.0, True)] * 2,
    "all_sampled_top_p_below_1": [_N, (0.7, 0.9, True), (1.0, 0.2, True)] * 2,
    "mixed_rows": [_G, _N, _D, (0.0, 0.5, True), _G, (0.8, 0.95, True)],
    # a greedy row may carry top_p < 1: it asks for no sort
    "draw_beside_greedy_top_p_below_1": [_D, (0.0, 0.4, True), _G] * 2,
    "inactive_nucleus_row_beside_greedy": [_G, (1.5, 0.5, False), _G, _G,
                                           (2.0, 1.0, False), _G],
    "inactive_nucleus_row_beside_draw": [_D, (1.5, 0.5, False), _G] * 2,
    "penalty_and_bias_on_greedy_rows": [
        (0.0, 1.0, True, 1.8, None), (0.0, 1.0, True, None, {7: 40.0, 9: -30.0}),
        (0.0, 1.0, True, 1.3, {3: 25.0}), _N, _G, (0.0, 1.0, False, 2.0, {1: 50.0}),
    ],
}


@pytest.mark.parametrize("case", sorted(BATCHED_CASES))
def test_batched_sampler_gives_the_parents_tokens(case):
    sp, active = _rows(*BATCHED_CASES[case])
    M = int(active.shape[0])
    got_fn = jax.jit(sample_token_batched)
    want_fn = jax.jit(_parent_tokens)
    for seed in range(4):
        k_logits, k_recent, k_keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        logits = 3.0 * jax.random.normal(k_logits, (M, V_BATCHED), jnp.float32)
        recent = jax.random.randint(k_recent, (M, W_BATCHED), -1, V_BATCHED)
        keys = jax.random.split(k_keys, M)
        got, logprobs = got_fn(keys, logits, sp, recent, active)
        want = np.asarray(want_fn(keys, logits, sp, recent))
        got, act = np.asarray(got), np.asarray(active)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got[act], want[act])
        lo = transform_logits_batched(logits, recent, sp)
        np.testing.assert_array_equal(
            np.asarray(logprobs), np.asarray(jax.nn.log_softmax(lo, axis=-1))
        )
        if case == "inactive_nucleus_row_beside_greedy":
            # no active row draws, so the step took the branch without a
            # draw: the rows nobody reads come back greedy, where the parent
            # had drawn them a token (at these temperatures another one)
            greedy = np.asarray(jnp.argmax(lo, axis=-1))
            np.testing.assert_array_equal(got, greedy)
            assert (want[~act] != greedy[~act]).any()


def _computations(hlo_text):
    """{name: [lines]} of the computations of an optimized HLO module."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return comps


def test_every_sort_of_the_batched_sampler_lies_behind_a_conditional():
    """The compiled text: a ``sort`` only in computations that a
    ``conditional`` calls (directly, or through a fusion or a call of a
    branch), none in the entry computation or anything else it reaches."""
    sp, active = _rows(*BATCHED_CASES["mixed_rows"])
    M = int(active.shape[0])
    text = jax.jit(sample_token_batched).lower(
        jax.random.split(jax.random.PRNGKey(0), M),
        jnp.zeros((M, V_BATCHED), jnp.float32), sp,
        jnp.full((M, W_BATCHED), -1, jnp.int32), active,
    ).compile().as_text()
    comps = _computations(text)

    def named(lines):  # the computations a piece of text names
        return set(re.findall(r"%([\w.\-]+)", "\n".join(lines))) & set(comps)

    behind = named(
        line for body in comps.values() for line in body
        if " conditional(" in line
    )
    while True:
        more = named(line for c in behind for line in comps[c]) - behind
        if not more:
            break
        behind |= more
    sorting = {
        name for name, body in comps.items()
        if any(re.search(r" sort\(", line) for line in body)
    }
    assert sorting, "the sampled top_p < 1 branch sorts: where did it go?"
    assert sorting <= behind, sorting - behind
    entry = re.search(r"^ENTRY %([\w.\-]+)", text, re.M).group(1)
    assert entry not in behind
