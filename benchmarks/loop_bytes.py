"""Bytes and operations one call of a looped decoder's serving program
requires, computed from shapes and from what the call's span says it did
(``flops.py``'s rule: what the algorithm needs, not what a program moves).
``calls`` is the driver's counter ``loop_calls`` (``families/looped_lm.py``):
passes, layers, a layer's weight bytes and parameters, the head's, the bytes
of an embedding row and of one cached K or V row.

A call streams the layer weights once a pass (they do not stay on the chip
between passes), the head once and an embedding row a token; in every plane
(pass, layer) it reads the K and the V row of each position its queries
attend, once, and writes the new tokens' rows. Only live rows count, not the
positions a slot's table spans."""

from __future__ import annotations


def call_bytes(calls: dict, new_rows: float, live_rows: float) -> float:
    """``new_rows`` tokens computed (a step's decoding slots, a chunk's real
    positions), ``live_rows`` cache positions attended, summed over the
    call's sequences."""
    planes = calls["passes"] * calls["layers"]
    return (planes * calls["layer_bytes"] + calls["head_bytes"]
            + new_rows * calls["embed_row_bytes"]
            + planes * 2 * calls["row_bytes"] * (live_rows + new_rows))


def call_flops(calls: dict, new_rows: float, head_rows: float, scored: float) -> float:
    """A multiply-add per weight and token in every pass, the head on
    ``head_rows`` tokens, and in every plane the scores and the weighted
    values of ``scored`` query-key pairs."""
    planes = calls["passes"] * calls["layers"]
    return (2.0 * new_rows * planes * calls["layer_params"]
            + 2.0 * head_rows * calls["head_params"]
            + planes * 4.0 * calls["q_width"] * scored)


def least_seconds(byts: float, ops: float, peaks: dict) -> float:
    return max(byts / peaks["hbm_bytes_per_s"], ops / peaks["bf16_flops"])


def step_least_seconds(calls: dict, active: float, live_rows: float, peaks: dict) -> float:
    """A decode step of ``active`` slots attending ``live_rows`` positions."""
    return least_seconds(call_bytes(calls, active, live_rows),
                         call_flops(calls, active, active, live_rows), peaks)


def chunk_least_seconds(calls: dict, pos0: float, live_rows: float, peaks: dict) -> float:
    """A prefill chunk whose real queries stand at ``[pos0, live_rows)``:
    query ``j`` attends ``j + 1`` positions; one token reaches the head."""
    new = live_rows - pos0
    scored = new * pos0 + new * (new + 1) / 2.0
    return least_seconds(call_bytes(calls, new, live_rows),
                         call_flops(calls, new, 1.0, scored), peaks)
