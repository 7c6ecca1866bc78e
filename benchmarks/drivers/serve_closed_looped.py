"""Driver ``serve_closed_looped``: ``serve_closed``'s closed loop, window and
accounting as they are, for a decoder whose layer stack runs several passes
over the same weights. Four things are its own:

* the weights are seeded in the form a published checkpoint holds them, a
  leaf a layer (``family.checkpoint_shapes``, as ``serve_closed_experts``
  seeds a matrix an expert): the reference reads them so, the program's
  loader stacks them (``family.make_engine``);
* the reference walk (:func:`served_gaps`, found by ``serve_closed.run`` the
  way ``serve_closed_experts.swapped`` makes it found). The model in float32
  does not fit the chip at once, and every layer is needed once a pass, so
  the walk holds the program's own weights as the program holds them
  (``weights.py``'s bfloat16 values: they are the model's weights, rounding
  them is not the program's error) and widens one layer at a time to
  float32, every pass spelled as a Python loop over
  ``references/looped_lm.py``'s layer;
* one more number decides ``correct``, ``served_far_share``
  (``serve_closed_experts.far_share``: the share of the served tokens more
  than 0.1 sigma below the reference's best). The seeded stack amplifies
  rounding from pass to pass, so the largest gap of a sound run is a tail
  that still grows with the seeds; the share is a mean over a thousand
  tokens, and a fault of the mechanism moves most of them;
* the counters the loop's reader takes its shapes from, and a line that
  says what share of the window's iterations carried a prefill chunk."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, weights
from benchmarks.drivers import serve_closed
from benchmarks.drivers.serve_closed_experts import (
    FAR_SIGMAS, checkpoint_weights, far_share, swapped,
)
from benchmarks.references import common as refc

PAD_TO = 128  # sequences are padded to a multiple: one compile for all of them


def reference_rows(ctx, family, shapes, sample, mm):
    """Per sampled request, the reference's logits [served tokens, vocab] at
    the positions that produced the served tokens: one teacher-forced pass
    over prompt + served tokens, every pass of the loop, layer by layer."""
    embed, layer, close_pass, logits_at = family.reference(ctx.config, mm)
    layer, close_pass = jax.jit(jax.vmap(layer, (0, None))), jax.jit(
        jax.vmap(close_pass, (0, None, None, None)))
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    ids = np.zeros((len(sample), -(-longest // PAD_TO) * PAD_TO), np.int32)
    for row, r in zip(ids, sample):
        row[:len(r["prompt"]) + len(r["tokens"])] = np.concatenate([r["prompt"], r["tokens"]])
    held = weights.make_weights(shapes, ctx.seed)  # as the program holds them
    wide = lambda names: {n: held[n].astype(jnp.float32) for n in names}
    n_layers = 1 + max(int(n.split("/")[0][len("layer_"):]) for n in shapes
                       if n.startswith("layer_"))
    by_layer = [[n for n in shapes if n.startswith(f"layer_{i}/")] for i in range(n_layers)]
    x = jax.jit(jax.vmap(embed, (None, 0)))(wide(["emb/word_emb"])["emb/word_emb"],
                                             jnp.asarray(ids))
    top = wide(["final_norm/scale", "exit_gate/w", "exit_gate/b"])
    for _ in range(ctx.config["total_ut_steps"]):
        for i, names in enumerate(by_layer):
            head = f"layer_{i}/"
            x = layer(x, {n[len(head):]: w for n, w in wide(names).items()})
        x, _ = close_pass(x, top["final_norm/scale"], top["exit_gate/w"], top["exit_gate/b"])
    head_w = wide(["head/w"])["head/w"]
    out = []
    for row, r in zip(x, sample):
        at = len(r["prompt"]) - 1 + np.arange(len(r["tokens"]))
        out.append(np.asarray(jax.jit(logits_at)(row[at], head_w)))
    return out


def served_gaps(ctx, family, shapes, sample, mm_names=("f32",)):
    """``serve_closed.served_gaps`` with the reference walked pass by pass
    and layer by layer."""
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
    seen = []

    def recorded(*args, **kwargs):
        seen.append(served_gaps(*args, **kwargs))
        return seen[-1]

    with checkpoint_weights(), swapped(served_gaps=recorded):
        result = serve_closed.run(ctx)
    gaps = seen[-1]["f32"]
    result["checks"].append(check.compared(
        "served_far_share", far_share(gaps), ctx.limits["served_far_share"],
        f"of {len(gaps)} served tokens, over {FAR_SIGMAS} sigmas"))
    c = result["counters"]
    c.update(loop_calls=family.loop_calls(ctx.config),
             max_slots=ctx.mix["engine"]["max_slots"],
             prefill_chunk=ctx.mix["engine"]["prefill_chunk"])
    steps, chunks = len(c["step_seconds"]), len(c["chunk_seconds"])
    print(f"iterations in the window: {steps} steps, {chunks} prefill chunks "
          f"({100.0 * chunks / max(steps, 1):.2f} % of the iterations carry a chunk)", flush=True)
    return result
