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
expert's rows starting at a multiple of the row tile, and the expert's
projections (:data:`BODIES`: a SwiGLU's three, or the two of an up-relu^2-down
expert) run as grouped matmuls over that layout: the ``moe_gmm`` kernel on
the chip (``ops/pallas/moe.py``), XLA's ragged dot on the CPU and under
differentiation. The layer works in whatever width it is handed: a model
whose experts live in a latent space projects down before and up after.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.core.enforce import enforce

__all__ = ["BODIES", "Route", "topk_route", "sigmoid_route", "share_layout",
           "expert_share_ffn", "row_tile_for", "stack_experts"]


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


def sigmoid_route(flat, router_w, router_b, k: int, scaling: float, routed=None) -> Route:
    """The router of the DeepSeek-V3 kind: ``s = sigmoid(W_r n)`` in float32
    over the router's full width (float32 operands, ``highest`` precision:
    a rounded score flips a near tie), then :func:`topk_route`. ``routed``
    [N] bool: the tokens whose pairs the expert layer computes (None: all);
    the others' are given an index past the router's width, held nowhere."""
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(jnp.matmul(
            flat, router_w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
        route = topk_route(scores, router_b, k, scaling)
        if routed is not None:
            route = route._replace(experts=jnp.where(
                routed[:, None], route.experts, router_w.shape[-1]))
    return route


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


def _swiglu(gmm, rows, w):
    return gmm(jax.nn.silu(gmm(rows, w["gate"])) * gmm(rows, w["fc1"]), w["fc2"])


def _relu2(gmm, rows, w):
    return gmm(jnp.square(jax.nn.relu(gmm(rows, w["fc1"]))), w["fc2"])


# an expert's body over the sorted rows: ``gmm(rows, stacked weights)`` is the
# grouped matmul, the activation between is float32
BODIES = {"swiglu": _swiglu, "relu2": _relu2}


def expert_share_ffn(x, route: Route, experts: dict, held: Tuple[int, int], *,
                     compute_dtype=jnp.bfloat16, kernel: Optional[bool] = None,
                     row_tile: Optional[int] = None, rows_an_expert: float = 0.0,
                     body: str = "swiglu"):
    """``sum_{e selected and held} w_e E_e(x)`` per token: ``x`` [N, d];
    ``E_e`` is ``body`` of :data:`BODIES`, a SwiGLU (``experts`` holds
    ``gate``, ``fc1`` [count, d, f] and ``fc2`` [count, f, d], the held
    experts' weights stacked in their order) or ``W2 relu(W1 x)^2`` (``fc1``
    and ``fc2`` alone); routing, layout and scatter are the same for both.
    Matmul operands are cast to ``compute_dtype``, sums are float32.
    ``kernel``: the ``moe_gmm`` Mosaic kernel (default: on a TPU backend),
    else XLA's ragged dot, which is also differentiable. ``row_tile``: rows
    an expert's group is padded to (default: :func:`row_tile_for` the
    expected ``rows_an_expert`` with the kernel, 1 without). Returns ``(y [N, d] float32, load
    [count] int32)``; ``load`` is the tokens each held expert took."""
    from paddle_tpu.ops.pallas import moe as pmoe

    count = experts["fc1"].shape[0]
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
        out = BODIES[body](gmm, jnp.take(x, lay.src, axis=0), experts)  # [rows, d]
        picked = jnp.take(out, jnp.minimum(lay.dest, out.shape[0] - 1), axis=0)  # [N, k, d]
        # where, not times zero: rows no tile wrote hold anything
        y = jnp.sum(jnp.where(lay.here[..., None], route.weights[..., None] * picked, 0.0), 1)
    return y, lay.load


def stack_experts(params: dict, held: Tuple[int, int]) -> dict:
    """The stacked leaves ``<m>/experts/<which>/w`` [count, a, b] from a
    checkpoint that holds a matrix an expert, ``<m>/experts/<e>/<which>/w``
    with ``e`` the expert's index in the router's width: the held experts'
    are stacked in their order, every other leaf is passed on. ``params`` is
    emptied as it is read, so that the per-expert arrays go as their stacks
    come (at the published sizes they do not fit beside each other twice)."""
    import re

    first, count = held
    one = re.compile(rf"(.+/experts)/{first}/(\w+)/w")
    out = {}
    for name in [n for n in params if one.fullmatch(n)]:
        m, which = one.fullmatch(name).groups()
        out[f"{m}/{which}/w"] = jnp.stack(
            [params.pop(f"{m}/{first + j}/{which}/w") for j in range(count)])
    out.update(params)
    params.clear()
    return out
