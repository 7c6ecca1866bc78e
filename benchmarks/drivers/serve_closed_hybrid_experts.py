"""Driver ``serve_closed_hybrid_experts``: ``serve_closed``'s closed loop,
window and accounting as they are, for a model with Mamba-2, attention and
expert layers and an untied head. It is ``serve_closed_experts`` (the weights
seeded a matrix an expert, the reference walked by layer with one layer's
float32 weights made from the seed at a time, ``head/w`` read at the end,
``served_far_share`` beside ``served_gap_sigmas``: ``serve_closed_hybrid``
reads the head from the embedding and holds every layer's weights at once, so
it cannot walk this model) with what a model that keeps states adds:

* the leak check covers pages **and** slots and says so in its name;
* the counters the SSM readers take their shapes from beside the expert
  readers', and a line that says what share of the window's iterations
  carried a prefill chunk;
* the seeded weights are made a leaf at a time (:func:`leaf_at_a_time`).
  ``weights.make_weights`` compiles one program that makes every leaf it is
  asked for; with a matrix an expert this model has 1 368 leaves (1 280 of
  them expert matrices of two shapes), and that one program took the TPU's
  compiler 714 s for a described v5e and the cell's first run on the chip
  over 500 s of set-up (my chip run, PR 45). The values are the same ones:
  ``weights._leaf`` under the key ``weights.make_weights`` gives the name."""

from __future__ import annotations

import contextlib
import functools
import importlib
import zlib

import jax
import jax.numpy as jnp

from benchmarks import weights
from benchmarks.drivers import serve_closed_experts


@functools.lru_cache(maxsize=None)
def _maker(leaf: str, shape, dtype):
    """One compiled maker for every leaf of this kind, shape and type: the
    leaf's name enters as a number, so 640 matrices compile once."""
    return jax.jit(lambda key, name_crc: weights._leaf(
        jax.random.fold_in(key, name_crc), leaf, shape, dtype))


def make_weights(shapes: dict, seed: int) -> dict:
    """``weights.make_weights``, a call a leaf: the same rule under the same
    key (the seed's, folded with the crc of the leaf's name)."""
    key = weights.seed_key(seed)
    return {n: _maker(n.rsplit("/", 1)[-1], tuple(s.shape), jnp.dtype(s.dtype).name)(
        key, zlib.crc32(n.encode()) & 0x7FFFFFFF) for n, s in sorted(shapes.items())}


@contextlib.contextmanager
def leaf_at_a_time():
    """While this is open every caller of ``weights.make_weights`` (the
    program's weights in ``serve_closed.serve``, a layer's in the reference
    walk) finds :func:`make_weights`. No file that is there is edited."""
    whole = weights.make_weights
    weights.make_weights = make_weights
    try:
        yield
    finally:
        weights.make_weights = whole


def run(ctx) -> dict:
    family = importlib.import_module(f"benchmarks.families.{ctx.config['family']}")
    with leaf_at_a_time():
        result = serve_closed_experts.run(ctx)
    for c in result["checks"]:
        if c["name"] == "leaked_pages":  # the one manager's pages and slots, both
            c["name"] = "leaked_pages_or_slots"
    c, engine = result["counters"], ctx.mix["engine"]
    c.update(ssm_calls=family.ssm_calls(ctx.config), page_size=engine["page_size"],
             prefill_chunk=engine["prefill_chunk"])
    steps, chunks = len(c["step_seconds"]), len(c["chunk_seconds"])
    print(f"iterations in the window: {steps} steps, {chunks} prefill chunks "
          f"({100.0 * chunks / max(steps, 1):.2f} % of the iterations carry a chunk)", flush=True)
    return result
