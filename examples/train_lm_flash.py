"""Train the decoder-only LM with the TPU-native fast path: bf16 MXU
compute + Pallas flash attention (fused backward, causal block skipping),
gradient accumulation, AdamW with warmup-cosine schedule, remat — then
decode with the cached generate().

Run: python examples/train_lm_flash.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in __import__("sys").argv:
    jax.config.update("jax_platforms", "cpu")
import numpy as np

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models import transformer_lm

pt.core.config.set_flags(use_bf16_compute=True, use_flash_attention=True)

spec = models.get_model(
    "transformer_lm", seq_len=256, vocab=8000, d_model=256, d_inner=1024,
    num_heads=8, n_layers=4, remat=True,
)
rng = np.random.RandomState(0)
batch = spec.synth_batch(16, rng)
variables = spec.model.init(0, *batch)
sched = pt.lr_scheduler.LinearWarmup(
    pt.lr_scheduler.CosineDecay(3e-4, decay_steps=1000), warmup_steps=50)
opt = pt.optimizer.AdamW(learning_rate=sched, weight_decay=0.01)
opt_state = opt.create_state(variables.params)
step = jax.jit(opt.minimize(spec.model, accum_steps=4), donate_argnums=(0, 1))

for i in range(20):
    out = step(variables, opt_state, *batch, rng=jax.random.PRNGKey(i))
    variables, opt_state = out.variables, out.opt_state
    if i % 5 == 0:
        print(f"step {i}: loss={float(out.loss):.4f}")

prompt = np.random.RandomState(1).randint(1, 8000, (2, 16)).astype(np.int32)
tokens = transformer_lm.generate(
    variables, jax.numpy.asarray(prompt), max_new_tokens=32, cfg=spec.extra["cfg"])
print("generated:", np.asarray(tokens)[0].tolist())
