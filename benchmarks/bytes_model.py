"""Bytes of the model's matrices as the served path holds them, and how many
distinct experts a step's tokens select: what a family's ``decode_step_bytes``
(``benchmarks/reference/<model_type>.py``, the numerator of
``decode_hbm_share``) is built from. Kept here, where a PR that claims a gain
cannot change it."""

from __future__ import annotations

from benchmarks.config import GROUP_SIZE, Unit, is_packed


def unit_bytes(u: Unit, fmt: str) -> int:
    """Bytes of ONE matrix of the unit (one expert, for an expert unit)."""
    if u.kind == "norm":
        return 2 * u.out
    if is_packed(u, fmt):
        return u.out * (u.inn // 2 + 2 * 4 * (u.inn // GROUP_SIZE))
    return 2 * u.out * u.inn


def expected_distinct_experts(n_experts: int, k: int, tokens: float) -> float:
    """Expected number of distinct experts ``tokens`` tokens select when
    each picks ``k`` of ``n_experts`` uniformly (random weights route
    uniformly)."""
    return n_experts * (1.0 - (1.0 - k / n_experts) ** tokens)
