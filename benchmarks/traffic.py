"""The general traffic generator. A mix is a data file under ``traffic/``;
everything made from it is a function of that file, the configuration and
``--seed`` alone. Every seed gets the same multiset of sizes, in another
order, so that a seed changes the inputs and not the amount of work. The
batches of a training mix are made beside their family (``families/``), from
the helpers here: what a row holds belongs to the model."""

from __future__ import annotations

from statistics import NormalDist
import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def token_ids(rng, vocab: int, shape) -> np.ndarray:
    return rng.integers(1, vocab, size=shape, dtype=np.int32)  # 0 is kept for padding


def even_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """n whole lengths spread evenly over [lo, hi]: the fixed multiset a
    uniform draw would approach."""
    return np.floor(lo + (np.arange(n) + 0.5) * (hi - lo + 1) / n).astype(np.int64)


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """n whole lengths at the mid-quantiles (i + 0.5) / n of a clipped
    log-normal: the fixed multiset that n draws from it approach."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


# -- serving requests ------------------------------------------------------

def closed_loop_requests(mix: dict, vocab: int, seed: int):
    """Per client, the endless list of (prompt tokens, output budget) it will
    send: ``rounds`` rounds of one request per client. Every round holds the
    same prompt lengths and the same output lengths (the mid-quantiles of the
    mix's two log-normals, one per client); the seed deals each round's
    prompts and outputs to the clients, apart, and fills in the tokens.

    A mix with a ``deal_seed`` is dealt by that number instead, the same for
    every ``--seed``, which then fills in the tokens alone. It is for a cell
    whose step reads the live contexts, so that a gap between tokens follows
    their sum: which lengths meet in the slots at one time is then part of the
    work, and the 95th percentile of the gaps over a window of some twenty
    request lives moved by 1.6 % with the dealing alone (PERF.md, PR 41)."""
    clients, rounds = mix["clients"], mix["rounds"]
    p, o = mix["prompt_len"], mix["output_len"]
    p_len = lognormal_quantiles(clients, p["median"], p["sigma"], p["lo"], p["hi"])
    o_len = lognormal_quantiles(clients, o["median"], o["sigma"], o["lo"], o["hi"])
    rng = rng_of(seed, 3)
    deal = rng_of(mix["deal_seed"], 4) if "deal_seed" in mix else rng
    per_client = [[] for _ in range(clients)]
    for _ in range(rounds):
        for c, (n_p, n_o) in enumerate(zip(deal.permutation(p_len), deal.permutation(o_len))):
            per_client[c].append((token_ids(rng, vocab, (int(n_p),)), int(n_o)))
    return per_client
