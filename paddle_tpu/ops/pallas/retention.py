"""Power-retention decode step as one Mosaic kernel, ``retention_step``.

A retention layer (``models/retention_lm.py``) keeps, per sequence and
key-value head, a fixed recurrent state instead of a growing KV cache. One
decode step has to decay every active sequence's state, add the new token's
``phi(k) v^T`` to it and contract it with the query heads' ``phi(q)``. The
state is far larger than everything else the step touches (4.7 MB per slot,
head and layer at head size 128), so the step is bound by how often the state
crosses HBM: XLA's unfused form passes over it three times (update, write,
read again for the contraction). This kernel brings each tile in once and
writes it out once, in place, and does its arithmetic in float32 on the
vector unit.

Layout. A slot's state for one key-value head is ``[R, D]`` float32 with the
*feature* axis ``D`` minor-most: rows ``0..dv-1`` are ``S^T`` (one row per
value channel), row ``dv`` is the normaliser ``z`` (the value "1" carried
through the same recurrence), the remaining rows pad ``R`` to a multiple of
8 and stay zero. A token's update is then ``state = g * state + v_aug[:, None]
* phi_k[None, :]`` with ``v_aug = [v, 1, 0...]``, a broadcast along lanes and
sublanes with no transpose, and contracting ``phi_q`` with every row gives
numerator and denominator alike.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.core.enforce import enforce

__all__ = ["retention_step", "retention_step_xla", "state_block"]

# f32 rows of D this many wide keep a tile (in and out, double-buffered)
# near 5 MB of VMEM at R = 136
MAX_BLOCK_D = 2304
LANES = 128


def state_block(d: int) -> int:
    """Tile of the feature axis: all of it when small, else the largest
    divisor of ``d`` that is a multiple of 128 and at most MAX_BLOCK_D."""
    if d <= MAX_BLOCK_D:
        return d
    for blk in range(MAX_BLOCK_D, 0, -128):
        if d % blk == 0:
            return blk
    raise ValueError(f"feature size {d} has no tile that is a multiple of 128")


def _step_kernel(g_ref, pq_ref, pk_ref, v_ref, s_ref, acc_ref, s_out_ref):
    """One (slot, key-value head, feature tile): decay, update, contract, a
    lane tile at a time so that the state passes through the vector unit
    once. The contraction with ``phi(q)`` is a multiply-add on the vector
    unit in float32, not a matmul: ``phi(q) . phi(k)`` is a square built from
    thousands of signed terms that cancel, and the MXU's bfloat16 passes lose
    it (a third of a sigma of the logits, measured). ``g_ref`` [S, H_kv]
    lives in SMEM; ``acc_ref`` [G, R, W] stays resident over the feature
    tiles; its lanes are summed outside."""
    t = pl.program_id(2)
    g = g_ref[pl.program_id(0), pl.program_id(1)]
    heads, _, width = acc_ref.shape
    v = v_ref[...]  # [R, 1]
    parts = [None] * heads
    for c in range(s_ref.shape[1] // width):
        at = slice(c * width, (c + 1) * width)
        new = g * s_ref[:, at] + v * pk_ref[:, at]
        s_out_ref[:, at] = new
        for h in range(heads):
            term = new * pq_ref[h:h + 1, at]
            parts[h] = term if parts[h] is None else parts[h] + term

    @pl.when(t == 0)
    def _():
        for h in range(heads):
            acc_ref[h] = parts[h]

    @pl.when(t > 0)
    def _():
        for h in range(heads):
            acc_ref[h] += parts[h]


def retention_step(state, phi_q, phi_k, v_aug, g, *, layer: int,
                   interpret: Optional[bool] = None):
    """Update layer ``layer`` of ``state`` in place and read it.

    ``state`` [L, S, H_kv, R, D] float32 (donate it: the output aliases it),
    ``phi_q`` [S, H_kv, G, D], ``phi_k`` [S, H_kv, 1, D], ``v_aug``
    [S, H_kv, R, 1], ``g`` [S, H_kv], all float32. A slot that must not
    change passes ``g`` 1 and ``phi_k`` 0. Returns ``(acc [S, H_kv, G, R],
    state)`` where ``acc[..., :dv]`` is the numerator and ``acc[..., dv]``
    the normaliser of each query head."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    L, S, H, R, D = state.shape
    G = phi_q.shape[2]
    enforce(phi_q.shape == (S, H, G, D) and phi_k.shape == (S, H, 1, D)
            and v_aug.shape == (S, H, R, 1) and g.shape == (S, H),
            f"retention_step: operands do not match state {state.shape}: "
            f"{phi_q.shape} {phi_k.shape} {v_aug.shape} {g.shape}")
    blk = state_block(D)
    W = LANES if blk % LANES == 0 else blk  # lanes the contraction is folded to
    per_head = lambda shape: pl.BlockSpec(
        (None, None) + shape, lambda s, h, t: (s, h, 0, t))
    acc, state = pl.pallas_call(
        _step_kernel,
        name="retention_step",
        grid=(S, H, D // blk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            per_head((G, blk)),
            per_head((1, blk)),
            pl.BlockSpec((None, None, R, 1), lambda s, h, t: (s, h, 0, 0)),
            pl.BlockSpec((None, None, None, R, blk),
                         lambda s, h, t: (layer, s, h, 0, t)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, G, R, W), lambda s, h, t: (s, h, 0, 0, 0)),
            pl.BlockSpec((None, None, None, R, blk),
                         lambda s, h, t: (layer, s, h, 0, t)),
        ],
        out_shape=[jax.ShapeDtypeStruct((S, H, G, R, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(g, phi_q, phi_k, v_aug, state)
    return jnp.sum(acc, -1), state


@functools.partial(jax.jit, static_argnames=("layer",))
def retention_step_xla(state, phi_q, phi_k, v_aug, g, *, layer: int):
    """The same step as plain einsums (three passes over the state): what
    the kernel is tested against."""
    new = (g[:, :, None, None] * state[layer]
           + v_aug * phi_k)  # [S, H, R, 1] * [S, H, 1, D]
    acc = jnp.einsum("shgd,shrd->shgr", phi_q, new,
                     precision=jax.lax.Precision.HIGHEST)
    return acc, state.at[layer].set(new)
