"""Bytes and operations a grouped matmul of an expert layer requires,
computed from shapes and from what the routing did (``flops.py``'s rule: what
the algorithm needs, not what a kernel moves): the weights of every expert
that took a token cross HBM once, every token-expert pair's row is read and
its result written once, and a pair costs a multiply-add per weight."""

from __future__ import annotations


def gmm_bytes(experts_hit: float, pairs: float, k: int, n: int, itemsize: int = 2,
              out_itemsize: int = 4) -> float:
    """One call ``[pairs, k] x [experts, k, n]``."""
    return experts_hit * k * n * itemsize + pairs * (k * itemsize + n * out_itemsize)


def gmm_flops(pairs: float, k: int, n: int) -> float:
    return 2.0 * pairs * k * n


def layer_least_seconds(experts_hit: float, pairs: float, d: int, f: int, itemsize: int,
                        peaks: dict) -> float:
    """Least time of one expert layer's three calls (gate and up ``d -> f``,
    down ``f -> d``): each the larger of its bytes over the memory's rate and
    its operations over the matrix unit's."""
    total = 0.0
    for k, n, calls in ((d, f, 2), (f, d, 1)):
        total += calls * max(gmm_bytes(experts_hit, pairs, k, n, itemsize)
                             / peaks["hbm_bytes_per_s"],
                             gmm_flops(pairs, k, n) / peaks["bf16_flops"])
    return total
