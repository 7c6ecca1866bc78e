"""Bytes and operations the ``ssm_step`` calls of one decode step require,
computed from shapes (``flops.py``'s rule: what the algorithm needs, not what
a kernel moves), and the bytes of the whole step they are a share of.
``calls`` is the driver's counter ``ssm_calls`` (``families/hybrid_ssm_lm.py``).

One call, for one active slot of one Mamba-2 layer: the state ``H`` [heads x
head size, state size] float32 in once and out once; in, the token's ``x`` and
``z`` (a number a channel), ``dt`` (a number a head), ``B`` and ``C`` (a number
a state entry); out, ``y`` (a number a channel). An idle slot requires
nothing."""

from __future__ import annotations


def state_bytes(calls: dict, itemsize: int = 4) -> int:
    """Bytes of one slot's state in one layer."""
    return calls["heads"] * calls["head_dim"] * calls["state"] * itemsize


def ssm_step_bytes(calls: dict, slots: float, itemsize: int = 4) -> float:
    """Every layer's call of one decode step over ``slots`` active slots (a
    mean may be fractional)."""
    channels = calls["heads"] * calls["head_dim"]
    token = (3 * channels + calls["heads"] + 2 * calls["state"]) * itemsize
    return slots * calls["layers"] * (2 * state_bytes(calls, itemsize) + token)


def ssm_step_flops(calls: dict, slots: float) -> float:
    """Decay and update of every state entry (a multiply and a multiply-add)
    and its contraction with ``C`` (a multiply-add): 5 operations an entry."""
    return slots * calls["layers"] * 5.0 * calls["heads"] * calls["head_dim"] * calls["state"]


def step_bytes(calls: dict, slots: float, live_rows: float) -> float:
    """What a whole decode step over ``slots`` active slots has to move: the
    weights once, the SSM calls' bytes, the convolution tails of the active
    slots in and out, and in every attention layer the K and the V row of
    each of the ``live_rows`` positions attended plus the new rows."""
    tails = slots * calls["layers"] * 2 * (calls["conv"] - 1) * calls["conv_channels"] * 4
    rows = calls["attention_layers"] * 2 * calls["kv_row_bytes"] * (live_rows + slots)
    return calls["weight_bytes"] + ssm_step_bytes(calls, slots) + tails + rows
