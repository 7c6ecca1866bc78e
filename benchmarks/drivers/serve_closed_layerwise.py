"""Driver ``serve_closed_layerwise``: ``serve_closed``'s closed loop, window
and accounting as they are, for a model whose float32 reference does not fit
the chip at once. It differs in how the reference is walked: one layer's
weights are made from the seed at a time (a leaf's values depend on the seed
and its name alone), every sampled request goes through that layer, the
weights are freed; logits are computed at the served positions only.

The reference is handed the program's own weights: the values ``weights.py``
gives the program in the type it holds them in (bfloat16 here), widened to
float32. They are the model's weights; rounding them is not the program's
error."""

from __future__ import annotations

import contextlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, weights
from benchmarks.drivers import serve_closed
from benchmarks.references import common as refc

PAD_TO = 512  # sequences are padded to a multiple: one compile for all of them


def _widened(shapes: dict, names, seed: int) -> dict:
    made = weights.make_weights({n: shapes[n] for n in names}, seed)
    return {n: w.astype(jnp.float32) for n, w in made.items()}


def reference_rows(ctx, family, shapes, sample, mm):
    """Per sampled request, the reference's logits [served tokens, vocab] at
    the positions that produced the served tokens: one teacher-forced pass
    over prompt + served tokens, by layer."""
    embed, layer, logits_at = family.reference(ctx.config, mm)
    layer = jax.jit(layer)
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    ids = np.zeros((len(sample), -(-longest // PAD_TO) * PAD_TO), np.int32)
    for row, r in zip(ids, sample):
        row[:len(r["prompt"]) + len(r["tokens"])] = np.concatenate([r["prompt"], r["tokens"]])
    emb = _widened(shapes, ["emb/word_emb"], ctx.seed)["emb/word_emb"]
    xs = [jax.jit(embed)(emb, jnp.asarray(row)) for row in ids]
    del emb
    n_layers = 1 + max(int(n.split("/")[0][len("layer_"):]) for n in shapes
                       if n.startswith("layer_"))
    for i in range(n_layers):
        head = f"layer_{i}/"
        lp = _widened(shapes, [n for n in shapes if n.startswith(head)], ctx.seed)
        lp = {n[len(head):]: w for n, w in lp.items()}
        xs = [layer(x, lp) for x in xs]
        jax.block_until_ready(xs)
        del lp
    top = _widened(shapes, ["final_norm/scale", "head/w"], ctx.seed)
    out = []
    for x, r in zip(xs, sample):
        at = len(r["prompt"]) - 1 + np.arange(len(r["tokens"]))
        out.append(np.asarray(jax.jit(logits_at)(x[at], top["final_norm/scale"], top["head/w"])))
    return out


def served_gaps(ctx, family, shapes, sample, mm_names=("f32",)):
    """``serve_closed.served_gaps`` with the reference walked by layer."""
    out = {m: [] for m in mm_names}
    if not sample:
        return out
    rows = reference_rows(ctx, family, shapes, sample, refc.MATMULS["f32"])
    for r, row in zip(sample, rows):
        out["f32"].extend(check.gap_sigmas(row, r["tokens"]).tolist())
    for m in mm_names:
        if m != "f32":
            low = reference_rows(ctx, family, shapes, sample, refc.MATMULS[m])
            for row, lo in zip(rows, low):
                out[m].extend(check.gap_sigmas(row, lo.argmax(-1)).tolist())
    return out


@contextlib.contextmanager
def layerwise_reference():
    """``serve_closed.run`` looks its reference walk up by name at the call;
    while this is open it finds the walk above. The one seam between the two
    drivers: no file that is there is edited, no accounting is copied."""
    whole = serve_closed.served_gaps
    serve_closed.served_gaps = served_gaps
    try:
        yield
    finally:
        serve_closed.served_gaps = whole


def run(ctx) -> dict:
    family = importlib.import_module(f"benchmarks.families.{ctx.config['family']}")
    with layerwise_reference():
        result = serve_closed.run(ctx)
    for c in result["checks"]:
        if c["name"] == "leaked_pages":  # this family holds states, not pages
            c["name"] = "leaked_slots_or_states"
    result["counters"].update(retention_calls=family.retention_calls(ctx.config),
                              max_slots=ctx.mix["engine"]["max_slots"])
    return result
