"""Continuous-batching autoregressive serving: mixed-length generation
requests share a paged KV cache, with iteration-level admission — a
finished request's slot refills on the very next decode step instead of
idling until the slowest member of a static batch drains. A draft model
speculates `spec_tokens` tokens per iteration (verified token-exactly in
one target pass), and the radix prefix cache lets requests sharing a
system prompt skip its prefill entirely.

Run: python examples/serve_decode.py [--cpu]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import numpy as np

from paddle_tpu import models
from paddle_tpu.serving import DecodeConfig, DecodeEngine

# a tiny LM stands in for a trained checkpoint; the draft would normally
# be a distilled/smaller checkpoint sharing the target's tokenizer —
# here the target drafts for itself (acceptance stays high, and the
# output is token-exact no matter how good or bad the draft is)
spec = models.get_model("transformer_lm", seq_len=128, vocab=256,
                        d_model=64, d_inner=128, num_heads=4, n_layers=2)
cfg = spec.extra["cfg"]
rng = np.random.RandomState(0)
variables = spec.model.init(0, *spec.synth_batch(2, rng))

engine = DecodeEngine(
    variables, cfg,
    decode=DecodeConfig(
        max_slots=4,         # concurrent sequences per decode step
        page_size=16,        # tokens per KV page (HBM granularity)
        max_context=128,     # prompt + generation budget per sequence
        prefill_chunk=16,    # prompts absorbed in fixed-shape chunks
        spec_tokens=4,       # drafted tokens per verify iteration
        prefix_cache=True,   # radix tree over already-prefilled pages
    ),
    draft_variables=variables,  # swap in a smaller LM (same vocab)
    draft_cfg=cfg,
)

# submit a mixed-length burst sharing a 32-token "system prompt": after
# the first request prefills it, every later request adopts those KV
# pages from the radix tree instead of recomputing them
system_prompt = rng.randint(1, 256, size=(32,))
handles = []
for i in range(8):
    tail = rng.randint(1, 256, size=(int(rng.randint(4, 24)),))
    prompt = np.concatenate([system_prompt, tail])
    max_new = int(rng.randint(8, 48))
    handles.append((i, max_new, engine.submit(prompt, max_new)))

for i, max_new, h in handles:
    out = h.result(timeout=300)
    print(f"req {i}: asked {max_new:2d} tokens -> got {len(out.tokens):2d} "
          f"({out.finish_reason}, {out.n_preemptions} preemptions)")

snap = engine.metrics.snapshot()
print(f"steps={snap['steps_total']} tokens={snap['tokens_total']} "
      f"mean tokens/step={snap['mean_step_occupancy']:.2f} "
      f"(of {4} slots)")
print(f"speculation: {snap['verify_steps_total']} verify steps, "
      f"accept rate {snap['spec_accept_rate']:.2f}, "
      f"{engine.metrics.accepted_tokens_per_verify_step():.2f} "
      "accepted tokens/verify step")
print(f"prefix cache: {snap['prefix_hit_tokens_total']} prompt tokens "
      f"served from the tree "
      f"({engine.metrics.prefix_saved_frac():.0%} of all prompt tokens), "
      f"{snap['cow_copies_total']} copy-on-write page copies")
print(f"decode step executables: {engine.decode_step_cache_size()} "
      f"verify: {engine.verify_step_cache_size()} "
      "(compiled once; admission never recompiles)")
engine.close()
