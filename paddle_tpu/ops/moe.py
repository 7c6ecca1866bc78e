"""Sparse expert layer that drops nothing: a top-k router and the share of an
expert layer that one chip computes.

``parallel/moe.py`` is the capacity-bounded dense-dispatch layer (Switch,
GShard): a token past an expert's capacity is dropped. Here an expert takes
every token routed to it. The layer is told which experts it holds
(``held``: first index and count, of the router's full width): it computes
the selected experts that are held here and leaves the others' terms out,
which is one chip's part of an expert-parallel layer (the exchange that sums
the parts across chips is not here). With every expert held it is the whole
layer.

The token-expert pairs that land on held experts are sorted by expert, each
expert's rows starting at a multiple of the row tile, and the three SwiGLU
projections run as grouped matmuls over that layout: the ``moe_gmm`` kernel
on the chip (``ops/pallas/moe.py``), XLA's ragged dot on the CPU and under
differentiation.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.enforce import enforce

__all__ = ["Route", "topk_route", "share_layout", "expert_share_ffn", "row_tile_for"]


def row_tile_for(rows_an_expert: float) -> int:
    """The kernel's row tile for an expected load: the smallest of 16, 32,
    64, 128 that holds twice it (on the chip, at 31 rows an expert, a call
    took 1.03, 0.86 and 0.81 ms in tiles of 16, 32 and 64: loads are
    uneven, and a second tile of an expert costs more than padding; a
    step's 2 rows want the smallest, and bfloat16 rows come in sublane
    pairs of 8, so that is 16)."""
    return next((tm for tm in (16, 32, 64) if 2 * rows_an_expert <= tm), 128)


class Route(NamedTuple):
    experts: jax.Array  # [N, k] int32, indices into the router's full width
    weights: jax.Array  # [N, k] float32


def topk_route(scores, bias, k: int, scaling: float = 1.0) -> Route:
    """The ``k`` experts a token with the largest ``scores + bias``, weighted
    by their own scores normalised to sum 1 and times ``scaling``. ``scores``
    [N, E] float32 (sigmoid or softmax of the router's logits), ``bias`` [E]
    or None: it enters the selection only and takes no gradient. Nothing is
    dropped: every token gets its ``k``."""
    scores = scores.astype(jnp.float32)
    pick = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(pick, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / jnp.sum(chosen, -1, keepdims=True) * scaling
    return Route(experts.astype(jnp.int32), weights)


class ShareLayout(NamedTuple):
    """Where the pairs that land here sit among the sorted rows."""
    dest: jax.Array         # [N, k] int32 row of each pair; ``rows`` where not held
    here: jax.Array         # [N, k] bool: the pair's expert is held
    src: jax.Array          # [rows] int32 token of each row (0 on padding rows)
    load: jax.Array         # [count] int32 tokens each held expert took
    padded: jax.Array       # [count] int32 the same, rounded up to the row tile
    tile_expert: jax.Array  # [rows / tm] int32 expert of each row tile
    used: jax.Array         # [1] int32 row tiles that hold rows


def share_rows(n_pairs: int, count: int, tm: int) -> int:
    """Static row count that holds any routing of ``n_pairs`` pairs onto
    ``count`` experts in tiles of ``tm``."""
    return -(-(n_pairs + count * (tm - 1)) // tm) * tm


def share_layout(experts, held: Tuple[int, int], tm: int) -> ShareLayout:
    """Sort the pairs of ``experts`` [N, k] that fall in ``held`` by expert,
    stably, each expert's rows starting at a multiple of ``tm``."""
    first, count = held
    n, k = experts.shape
    rows = share_rows(n * k, count, tm)
    local = experts.reshape(-1) - first
    here = (local >= 0) & (local < count)
    onehot = (jnp.where(here, local, count)[:, None] == jnp.arange(count)[None, :])
    rank = jnp.cumsum(onehot.astype(jnp.int32), 0) - 1  # a pair's place in its expert
    load = jnp.sum(onehot.astype(jnp.int32), 0)
    padded = -(-load // tm) * tm
    ends = jnp.cumsum(padded)
    start = ends - padded
    mine = jnp.clip(local, 0, count - 1)
    dest = jnp.where(here, start[mine] + jnp.take_along_axis(
        rank, mine[:, None], axis=1)[:, 0], rows)
    token = jnp.arange(n * k, dtype=jnp.int32) // k
    src = jnp.zeros((rows,), jnp.int32).at[dest].set(token, mode="drop")
    used = ends[-1] // tm
    tile = jnp.minimum(jnp.arange(rows // tm), jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile * tm, side="right"), count - 1).astype(jnp.int32)
    return ShareLayout(dest.reshape(n, k), here.reshape(n, k), src, load, padded,
                       tile_expert, used.reshape(1).astype(jnp.int32))


def expert_share_ffn(x, route: Route, experts: dict, held: Tuple[int, int], *,
                     compute_dtype=jnp.bfloat16, kernel: Optional[bool] = None,
                     row_tile: Optional[int] = None, rows_an_expert: float = 0.0):
    """``sum_{e selected and held} w_e E_e(x)`` per token, ``E_e`` a SwiGLU:
    ``x`` [N, d]; ``experts`` holds ``gate``, ``fc1`` [count, d, f] and
    ``fc2`` [count, f, d], the held experts' weights stacked in their order.
    Matmul operands are cast to ``compute_dtype``, sums are float32.
    ``kernel``: the ``moe_gmm`` Mosaic kernel (default: on a TPU backend),
    else XLA's ragged dot, which is also differentiable. ``row_tile``: rows
    an expert's group is padded to (default: :func:`row_tile_for` the
    expected ``rows_an_expert`` with the kernel, 1 without). Returns ``(y [N, d] float32, load
    [count] int32)``; ``load`` is the tokens each held expert took."""
    from paddle_tpu.ops.pallas import moe as pmoe

    count = experts["gate"].shape[0]
    enforce(held[1] == count, f"expert_share_ffn: {count} experts' weights "
            f"for a share of {held[1]}")
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    tm = row_tile or (row_tile_for(rows_an_expert) if kernel else 1)
    lay = share_layout(route.experts, held, tm)
    cdt = jnp.dtype(compute_dtype)
    if kernel:
        gmm = lambda a, w: pmoe.moe_gmm(a.astype(cdt), w.astype(cdt), lay.tile_expert,
                                        lay.used, tm=tm)
    else:
        gmm = lambda a, w: pmoe.moe_gmm_xla(a.astype(cdt), w.astype(cdt), lay.padded)
    with jax.named_scope("moe_experts"):
        rows = jnp.take(x, lay.src, axis=0)
        h = jax.nn.silu(gmm(rows, experts["gate"])) * gmm(rows, experts["fc1"])
        out = gmm(h, experts["fc2"])  # [rows, d]
        picked = jnp.take(out, jnp.minimum(lay.dest, out.shape[0] - 1), axis=0)  # [N, k, d]
        # where, not times zero: rows no tile wrote hold anything
        y = jnp.sum(jnp.where(lay.here[..., None], route.weights[..., None] * picked, 0.0), 1)
    return y, lay.load
