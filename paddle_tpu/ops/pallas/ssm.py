"""The decode step of a Mamba-2 layer's state update as one Mosaic kernel,
``ssm_step``.

A Mamba-2 layer (``models/hybrid_ssm_lm.py``) keeps per sequence a state
``H`` of ``d_state x d_ssm`` float32 numbers (2 MB a slot and layer at the
Granite 4.0-H widths, 4 MB at Nemotron-H's). One decode step has to decay
it, add the new token's outer product and contract it with ``C``::

    H = a * H + B (x) (dt * x)          y = C . H

``B`` and ``C`` come in ``G`` groups: the channels are ``G`` equal runs of
heads and a channel reads its run's ``B`` and ``C`` (``G`` 1: all heads share
one).

The state is far larger than everything else the step touches, so the step
is bound by how often it crosses HBM. XLA's einsum form crosses it three
times (update, write, read again for the contraction), and writes the whole
layer back, idle slots included. This kernel brings each **active** slot's
state in once and writes it out once, in place; the grid walks a
scalar-prefetched list of the active slots, so an idle slot's state is not
read, not written and not moved.

Layout. A slot's state is ``[d_state, d_ssm]`` with the channel axis
``d_ssm`` (heads side by side, a head's channels contiguous) along the lanes:
a head's decay and ``dt * x`` are then lane vectors that broadcast along
sublanes, ``B`` and ``C`` are columns that broadcast along lanes, and the
contraction with ``C`` sums over sublanes: elementwise adds of whole
registers. The body walks the channels in chunks of 512 lanes; a group is a
whole number of chunks, so a chunk reads one group's columns. Everything is
float32 on the vector unit; no product goes through the matrix unit, so
nothing is rounded to bfloat16 on the way.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.core.enforce import enforce

__all__ = ["active_list", "ssm_step", "ssm_step_xla"]

LANES = 128
LANE_CHUNK = 512  # channels the kernel's body handles at a time
VMEM_LIMIT = 48 * 2**20  # a state tile in and out, double-buffered: 8 MB at 128 x 4096


def active_list(active):
    """``active`` [S] (non-zero = the slot decodes) -> ``(ids [S] int32, n)``:
    the active slots' numbers first, in order, the rest of the list repeating
    the last active one (a grid step that names the block of the step before
    moves nothing); ``n`` is their count, at least 1: with no active slot the
    list names slot 0, whose operands the caller has made a no-op."""
    on = active != 0
    S = on.shape[0]
    order = jnp.argsort(jnp.logical_not(on), stable=True).astype(jnp.int32)
    n = jnp.maximum(jnp.sum(on.astype(jnp.int32)), 1)
    return jnp.where(jnp.arange(S) < n, order, order[n - 1]), n


def _masked(active, xdt, decay, b, c):
    """Operands under which an idle slot's update is the identity, and finite
    whatever the slot's row held."""
    on = (active != 0)[:, None]
    return (jnp.where(on, xdt, 0.0), jnp.where(on, decay, 1.0),
            jnp.where(on[..., None], b, 0.0), jnp.where(on[..., None], c, 0.0))


def _step_kernel(ids_ref, meta_ref, u_ref, bc_ref, h_ref, y_ref, h_out_ref):
    """One entry of the active list: the slot's whole state ``[N, D]``.
    ``u_ref`` [2, D] holds ``dt * x`` and the decay a channel, ``bc_ref``
    [2 G, N] holds the groups' ``B`` rows, then their ``C`` rows. Entries past
    the active count name the last active slot's blocks again and compute
    nothing: the pipeline keeps the blocks it has and writes them back once."""
    del ids_ref

    @pl.when(pl.program_id(0) < meta_ref[0])
    def _():
        N, D = h_ref.shape
        G = bc_ref.shape[0] // 2
        # B and C as columns: the diagonal of a broadcast row, summed along
        # lanes (one term a row is not zero, so the sum is exact)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))
        col = lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)
        W = LANE_CHUNK if (D // G) % LANE_CHUNK == 0 else D // G
        for g in range(G):
            b_col, c_col = col(bc_ref[g:g + 1, :]), col(bc_ref[G + g:G + g + 1, :])
            for k in range(g * (D // G) // W, (g + 1) * (D // G) // W):
                at = slice(k * W, (k + 1) * W)
                new = u_ref[1:2, at] * h_ref[:, at] + b_col * u_ref[0:1, at]
                h_out_ref[:, at] = new
                y_ref[0:1, at] = jnp.sum(new * c_col, axis=0, keepdims=True)


def ssm_step(state, xdt, decay, b, c, active, *, layer,
             interpret: Optional[bool] = None):
    """Update plane ``layer`` of ``state`` in place and read it.

    ``state`` [L, S, N, D] float32 (donate it: the output aliases it);
    ``xdt`` [S, D] the new token's ``dt * x`` a channel, ``decay`` [S, D]
    its ``exp(dt * A)`` a channel, ``b`` and ``c`` [S, G, N] (channel ``d``
    reads group ``d // (D / G)``), all float32; ``active`` [S], non-zero
    where the slot decodes; ``layer`` an int,
    traced or not. Returns ``(y [S, D], state)``: ``y[s] = C_s . H_s`` after
    the update for an active slot and 0 for an idle one, whose state is
    left where it lies."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _ssm_step(state, xdt, decay, b, c, active, jnp.asarray(layer, jnp.int32),
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step(state, xdt, decay, b, c, active, layer, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    L, S, N, D = state.shape
    G = b.shape[1] if b.ndim == 3 else 0
    enforce(state.dtype == jnp.float32 and xdt.shape == decay.shape == (S, D)
            and b.shape == c.shape == (S, G, N) and active.shape == (S,)
            and G >= 1 and D % G == 0,
            f"ssm_step: operands do not match state {state.shape} {state.dtype}: "
            f"{xdt.shape} {decay.shape} {b.shape} {c.shape} {active.shape}")
    enforce(interpret or ((D // G) % LANES == 0 and N % 8 == 0),
            f"ssm_step: a state tile [{N}, {D}] in {G} groups does not lie in whole tiles")
    xdt, decay, b, c = _masked(active, xdt, decay, b, c)
    ids, n = active_list(active)
    u = jnp.stack([xdt, decay], axis=1)  # [S, 2, D]
    bc = jnp.concatenate([b, c], axis=1)  # [S, 2 G, N]
    by_slot = lambda rows, width: pl.BlockSpec(
        (None, rows, width), lambda j, ids, meta: (ids[j], 0, 0))
    tile = pl.BlockSpec((None, None, N, D), lambda j, ids, meta: (meta[1], ids[j], 0, 0))
    y, state = pl.pallas_call(
        _step_kernel,
        name="ssm_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[by_slot(2, D), by_slot(2 * G, N), tile],
            out_specs=[by_slot(1, D), tile]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count from the scalars: ids, meta, u, bc, state
        input_output_aliases={4: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(ids, jnp.stack([n, layer]).astype(jnp.int32), u, bc, state)
    # an idle slot's row of y was never written
    return jnp.where((active != 0)[:, None], y[:, 0], 0.0), state


@functools.partial(jax.jit, static_argnames=("layer",))
def ssm_step_xla(state, xdt, decay, b, c, active, *, layer: int):
    """The same step as plain einsums over the whole plane (three passes over
    the state, idle slots moved with the rest): what the kernel is tested
    against, and what a program lowered for anything but a TPU runs."""
    xdt, decay, b, c = _masked(active, xdt, decay, b, c)
    S, G, N = b.shape
    by_group = lambda v: v.reshape(S, 1, G, -1)  # [S, D] -> [S, 1, G, D / G]
    old = state[layer].reshape(S, N, G, -1)
    new = by_group(decay) * old + jnp.swapaxes(b, 1, 2)[..., None] * by_group(xdt)
    y = jnp.einsum("sgn,sngd->sgd", c, new, precision=jax.lax.Precision.HIGHEST)
    return y.reshape(S, -1), state.at[layer].set(new.reshape(state.shape[1:]))
