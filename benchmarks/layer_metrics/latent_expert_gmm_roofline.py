"""Least time the chip could take for the window's ``moe_gmm`` calls of a
latent expert layer over the device time of the operations named
``moe_gmm*``: ``moe_gmm_roofline``'s reckoning with two calls a layer in the
latent width (``benchmarks/latent_expert_bytes.py``), at the counts the
program's spans carry (every decode step and every prefill chunk of the window
says how many token-expert pairs it computed here and how many held experts
took a token, summed over its expert layers; a layer is taken at the call's
mean). None where the cell runs no latent expert layer, the spans carry no
counts or the trace names no such kernel."""

import re

from benchmarks import latent_expert_bytes
from benchmarks.moe_spans import window_calls

# a Mosaic kernel's ``name=`` heads its device-op name
GMM_OP = re.compile(r"^moe_gmm")


def read(view):
    t, calls = view["trace"], view["counters"].get("moe_calls")
    if not t or not calls or "latent" not in calls or not view["peaks"]:
        return None
    sec = sum(s for name, s in t["ops"].items() if GMM_OP.search(name))
    found = window_calls(view)
    if not sec or not found:
        return None
    layers = calls["layers"]
    least = sum(layers * latent_expert_bytes.layer_least_seconds(
        c["moe_experts_hit"] / layers, c["moe_pairs"] / layers, calls["latent"], calls["f"],
        calls["itemsize"], view["peaks"]) for c in found["steps"] + found["chunks"])
    print(f"latent expert moe_gmm roofline: {least * 1e3:.1f} ms least over "
          f"{len(found['steps'])} steps and {len(found['chunks'])} chunks; kernels "
          f"{sec * 1e3:.1f} ms", flush=True)
    return 100.0 * least / sec
