"""Bytes the power-retention decode step requires, computed from shapes
(``flops.py``'s rule: what the algorithm needs, not what a kernel moves). The
step of one slot, layer and key-value head reads its state once and writes it
once: ``S`` [D, value_width] and the normaliser ``z`` [D], float32. Beside
that go the token's own q (per query head), k, v and the output o."""

from __future__ import annotations


def retention_step_bytes(slots: float, layers: int, kv_heads: int, q_heads: int,
                         d: int, value_width: int, itemsize: int = 4) -> float:
    """One decode step over ``slots`` active slots (a mean may be fractional)."""
    state = kv_heads * (d * value_width + d) * itemsize * 2
    token = (2 * q_heads + 2 * kv_heads) * value_width * itemsize
    return slots * layers * (state + token)


def retention_step_flops(slots: float, layers: int, kv_heads: int, q_heads: int,
                         d: int, value_width: int) -> float:
    """Decay and update of ``S`` and ``z`` (two operations an entry) and the
    query heads' contraction with both (a multiply-add an entry and head)."""
    entries = d * value_width + d
    return slots * layers * (kv_heads * 2.0 * entries + q_heads * 2.0 * entries)
