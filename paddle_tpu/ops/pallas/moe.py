"""Grouped matmul of an expert layer as one Mosaic kernel, ``moe_gmm``.

``ops/moe.py`` sorts the token-expert pairs that land on the experts held
here by expert and lays each expert's rows out from a row that is a multiple
of the row tile, so every row tile belongs to one expert and the kernel needs
no mask: tile ``t`` of ``x`` times the whole ``[K, tn]`` column block of
expert ``tile_expert[t]``'s weights, float32 accumulation, one store. The
contraction axis is not tiled, and on the chip the whole matrix as one block
was fastest at the published widths (``tn = N``: 16 MB of bfloat16 at
4096 x 2048, rows of the block contiguous in HBM), so consecutive row tiles
of one expert find the weights where the last left them and an expert's
matrix crosses HBM once.

The row count is static and sized for the worst routing (every pair lands
here); ``used[0]`` says how many tiles hold rows. Tiles past it are skipped:
their block indices repeat the last used tile's, so they start no transfer,
and their rows of the output are never read. With two rows an expert (a
decode step) or thirty-two (a prefill chunk) the call is bound by reading
each hit expert's weights once.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import enforce

__all__ = ["moe_gmm", "moe_gmm_xla", "resolve_tiles", "take_resolved"]

# Chip-measured tiles (TPU v5e), keyed by what a call can observe:
# (rows a tile, K, N, operand itemsize) -> column block ``tn``. Anything
# else takes :func:`rule_tn`. The row tile is the caller's: it is the
# granule the rows were laid out in.
_TUNED_BLOCKS: dict[tuple[int, int, int, int], int] = {
    # 32 experts held, each token 8 of 128; milliseconds a call, the winner
    # then the runner-up, then XLA's ragged dot (PERF.md, PR 31)
    # a step, 32 tokens (68 pairs on 27 experts): 0.655, 1024 0.697; 0.756
    (16, 4096, 2048, 2): 2048,
    # 0.656, 2048 0.667; 0.736
    (16, 2048, 4096, 2): 4096,
    # a chunk, 512 tokens (989 pairs on 32 experts) in tiles of 32: 0.865,
    # 1024 0.935; 1.213
    (32, 4096, 2048, 2): 2048,
    # 0.878, 2048 0.910; 1.176
    (32, 2048, 4096, 2): 4096,
    # the same in tiles of 64, which the model takes: 0.806, 1024 0.882; 1.898
    (64, 4096, 2048, 2): 2048,
    # 0.828, 2048 0.855; 1.906
    (64, 2048, 4096, 2): 4096,
    # 128 experts held in a latent of 1024, each token 22 of 512 (PERF.md,
    # PR 45): a step, 64 tokens (358 pairs on 120 experts): 0.956, 896 1.006;
    # 3.543 (least by bytes 0.812)
    (16, 1024, 2688, 2): 2688,
    # 0.940, 512 0.981; 2.671
    (16, 2688, 1024, 2): 1024,
    # a chunk, 512 tokens (2734 pairs on 128 experts) in tiles of 64, which
    # the model takes: 1.155, 896 1.269; 3.289 (in tiles of 32: 1.095)
    (64, 1024, 2688, 2): 2688,
    # 1.104, 512 1.192; 2.537 (in tiles of 32: 1.064)
    (64, 2688, 1024, 2): 1024,
}

_BLOCK_BYTES = 16 * 1024 * 1024  # one weight block; two are in flight


def rule_tn(k: int, n: int, itemsize: int) -> int:
    """Largest column block that is a multiple of 128, divides ``n`` and
    keeps a ``[k, tn]`` weight block under :data:`_BLOCK_BYTES`; all of
    ``n`` when it is small or has no such divisor."""
    for tn in range(min(n, max(_BLOCK_BYTES // (k * itemsize), 128)) // 128 * 128, 0, -128):
        if n % tn == 0:
            return tn
    return n


def resolve_tiles(tm: int, k: int, n: int, itemsize: int) -> tuple[int, str]:
    """``(tn, source)``: the table's row, else the rule."""
    tn = _TUNED_BLOCKS.get((tm, k, n, itemsize))
    return (tn, "table") if tn is not None else (rule_tn(k, n, itemsize), "rule")


# what the calls traced since the last take_resolved() ran with
_resolved: dict[str, str] = {}


def take_resolved() -> dict[str, str]:
    """``{"moe_gmm_<tm>x<K>x<N>": "<tn> <source>"}`` of the calls traced
    since the last call, and forget them (the ``executor.compile`` span
    carries it beside the flash kernels')."""
    out = dict(_resolved)
    _resolved.clear()
    return out


def _kernel(tile_expert_ref, used_ref, x_ref, w_ref, o_ref):
    del tile_expert_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32).astype(o_ref.dtype)


def moe_gmm(x, w, tile_expert, used, *, tm: int, tn: Optional[int] = None,
            out_dtype=jnp.float32, interpret: Optional[bool] = None):
    """``out[t * tm:(t + 1) * tm] = x[t * tm:(t + 1) * tm] @ w[tile_expert[t]]``
    for the row tiles ``t < used[0]``; later rows of ``out`` hold nothing.

    ``x`` [M, K] and ``w`` [E, K, N] in the operand type (bfloat16 on the
    chip), ``tile_expert`` [M / tm] int32 non-decreasing, ``used`` [1]
    int32. ``tm`` a multiple of 16 for bfloat16 rows, 8 for float32."""
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = x.shape
    e, k2, n = w.shape
    enforce(k == k2 and m % tm == 0 and tile_expert.shape == (m // tm,)
            and used.shape == (1,),
            f"moe_gmm: x {x.shape}, w {w.shape}, tile_expert {tile_expert.shape}, "
            f"used {used.shape} do not fit row tiles of {tm}")
    source = "caller"
    if tn is None:
        tn, source = resolve_tiles(tm, k, n, w.dtype.itemsize)
    enforce(n % tn == 0, f"moe_gmm: column block {tn} does not divide N {n}")
    prof.inc_counter(f"moe_gmm.blocks.{source}")
    _resolved[f"moe_gmm_{tm}x{k}x{n}"] = f"{tn} {source}"
    last = lambda used: jnp.maximum(used[0] - 1, 0)
    row = lambda t, used: jnp.minimum(t, last(used))
    return pl.pallas_call(
        _kernel,
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, t, te, used: (row(t, used), 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, t, te, used: (te[row(t, used)], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, te, used: (row(t, used), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(e * k * n + m * k) * w.dtype.itemsize
            + m * n * jnp.dtype(out_dtype).itemsize),
        interpret=interpret,
    )(tile_expert, used, x, w)


def moe_gmm_xla(x, w, group_sizes, *, out_dtype=jnp.float32):
    """The same product as XLA's ragged dot over the same row layout
    (``group_sizes`` [E]: each expert's rows, padding included): the form
    the CPU and training run, and what the kernel is tested against. Rows
    past the last group come out zero."""
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32).astype(out_dtype)
