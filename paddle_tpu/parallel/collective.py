"""Collective communication primitives.

Reference: the op-handle collectives —
``details/all_reduce_op_handle.cc:48`` (grouped ncclAllReduce),
``details/reduce_op_handle.cc`` (reduce-to-one-device),
``details/broadcast_op_handle.cc`` (ncclBcast),
``operators/nccl/nccl_op.cc`` raw collective ops.

TPU-native: thin, named wrappers over lax collectives. These only have
meaning inside shard_map/pmap-style per-device code; under plain pjit with
NamedSharding annotations XLA inserts the equivalent collectives itself —
prefer that. Provided for explicit SPMD kernels (ring attention, custom
reductions) and API parity.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import lax


def all_reduce(x, axis_name: str, op: str = "sum"):
    """AllReduceOpHandle parity."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op!r}")


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, scatter_dimension: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def broadcast(x, axis_name: str, root: int = 0):
    """BroadcastOpHandle parity: every member takes root's value."""
    idx = lax.axis_index(axis_name)
    masked = jax.numpy.where(idx == root, x, jax.numpy.zeros_like(x))
    return lax.psum(masked, axis_name)


def ppermute(x, axis_name: str, perm):
    """Ring/shift primitive (basis for ring attention / pipeline bubbles)."""
    return lax.ppermute(x, axis_name, perm)


def ring_shift(x, axis_name: str, shift: int = 1):
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)


# -- KV-page transfer primitives (serving.disagg) ---------------------------
#
# Disaggregated prefill/decode handoff moves one request's KV pages from a
# prefill worker's page arrays into a decode worker's. When both workers
# live in one process these run jitted on-device (a gather/scatter per
# page — no host round-trip); across processes the gathered pages are
# serialized with per-page CRCs (serving.disagg.HandoffPayload). Page
# arrays are ``[L, num_pages, page_size, H_kv * dh]``; one page is the
# fixed-shape ``[L, page_size, H_kv * dh]`` slice, so both ops compile
# exactly once per engine geometry. Neither spells the axes after the
# page's: ``pages[:, id]`` holds for any form.

def gather_kv_page(pages, page_id):
    """Extract one physical page from a paged KV array (device-side)."""
    return pages[:, page_id]


def scatter_kv_page(pages, page_id, page):
    """Implant one page payload at ``page_id`` in a paged KV array
    (device-side; the engine jits this with ``pages`` donated, so the
    update lands in its running page array in place)."""
    return pages.at[:, page_id].set(page)
