"""Bytes and operations the ``moe_gmm`` calls of a latent expert layer
require: ``moe_bytes.py``'s count (the weights of every expert that took a
token cross HBM once, every pair's row is read and its result written once, a
pair costs a multiply-add per weight) for the **two** calls of an
up-relu^2-down expert in the latent width, ``latent -> f`` and ``f ->
latent``. ``moe_bytes.layer_least_seconds`` reckons a SwiGLU's three calls and
would read this layer half again too high."""

from __future__ import annotations

from benchmarks import moe_bytes


def layer_least_seconds(experts_hit: float, pairs: float, latent: int, f: int, itemsize: int,
                        peaks: dict) -> float:
    """Least time of one expert layer's two calls: each the larger of its
    bytes over the memory's rate and its operations over the matrix unit's."""
    return sum(max(moe_bytes.gmm_bytes(experts_hit, pairs, k, n, itemsize)
                   / peaks["hbm_bytes_per_s"],
                   moe_bytes.gmm_flops(pairs, k, n) / peaks["bf16_flops"])
               for k, n in ((latent, f), (f, latent)))
