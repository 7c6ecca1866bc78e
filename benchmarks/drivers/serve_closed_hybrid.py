"""Driver ``serve_closed_hybrid``: ``serve_closed``'s closed loop, window and
accounting as they are, for a model with Mamba-2 layers beside attention
layers and a tied head. Three things are its own:

* the reference walk (:func:`served_gaps`, found by ``serve_closed.run`` the
  way ``serve_closed_experts.swapped`` makes it found). The model in float32
  does not sit on the chip beside anything, so the walk holds the program's
  own weights as the program holds them (``weights.py``'s bfloat16 values:
  they are the model's weights, rounding them is not the program's error) and
  widens one layer at a time to float32; logits are computed at the served
  positions only, through the embedding (the head is tied: there is no
  ``head/w`` for ``serve_closed_layerwise`` to read);
* the leak check covers pages **and** slots (``PagedKVCache.assert_no_leaks``
  is the one manager's: a slot still held is a state still held) and says so
  in its name;
* the counters the SSM readers take their shapes from, and a line that says
  what share of the window's iterations carried a prefill chunk."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, weights
from benchmarks.drivers import serve_closed
from benchmarks.drivers.serve_closed_experts import swapped
from benchmarks.references import common as refc

PAD_TO = 512  # sequences are padded to a multiple: one compile for all of them


def reference_rows(ctx, family, shapes, sample, mm):
    """Per sampled request, the reference's logits [served tokens, vocab] at
    the positions that produced the served tokens: one teacher-forced pass
    over prompt + served tokens, layer by layer."""
    embed, layer, logits_at = family.reference(ctx.config, mm)
    layer = jax.jit(jax.vmap(layer, (0, None)))
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    ids = np.zeros((len(sample), -(-longest // PAD_TO) * PAD_TO), np.int32)
    for row, r in zip(ids, sample):
        row[:len(r["prompt"]) + len(r["tokens"])] = np.concatenate([r["prompt"], r["tokens"]])
    held = weights.make_weights(shapes, ctx.seed)  # as the program holds them
    wide = lambda names: {n: held[n].astype(jnp.float32) for n in names}
    n_layers = 1 + max(int(n.split("/")[0][len("layer_"):]) for n in shapes
                       if n.startswith("layer_"))
    emb = wide(["emb/word_emb"])["emb/word_emb"]
    x = jax.jit(jax.vmap(embed, (None, 0)))(emb, jnp.asarray(ids))
    for i in range(n_layers):
        head = f"layer_{i}/"
        x = layer(x, {n[len(head):]: w for n, w in
                      wide([n for n in shapes if n.startswith(head)]).items()})
    final = wide(["final_norm/scale"])["final_norm/scale"]
    del held
    out = []
    for row, r in zip(x, sample):
        at = len(r["prompt"]) - 1 + np.arange(len(r["tokens"]))
        out.append(np.asarray(jax.jit(logits_at)(row[at], final, emb)))
    return out


def served_gaps(ctx, family, shapes, sample, mm_names=("f32",)):
    """``serve_closed.served_gaps`` with the reference walked layer by layer."""
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


def run(ctx) -> dict:
    family = importlib.import_module(f"benchmarks.families.{ctx.config['family']}")
    with swapped(served_gaps=served_gaps):
        result = serve_closed.run(ctx)
    for c in result["checks"]:
        if c["name"] == "leaked_pages":  # the one manager's pages and slots, both
            c["name"] = "leaked_pages_or_slots"
    c, engine = result["counters"], ctx.mix["engine"]
    c.update(ssm_calls=family.ssm_calls(ctx.config), max_slots=engine["max_slots"],
             page_size=engine["page_size"], prefill_chunk=engine["prefill_chunk"])
    steps, chunks = len(c["step_seconds"]), len(c["chunk_seconds"])
    print(f"iterations in the window: {steps} steps, {chunks} prefill chunks "
          f"({100.0 * chunks / max(steps, 1):.2f} % of the iterations carry a chunk)", flush=True)
    return result
