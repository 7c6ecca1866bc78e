"""Train the modern decoder stack: RoPE + grouped-query attention +
SwiGLU FFN + sliding-window attention, with ZeRO-1 optimizer-state
sharding and prefetched input on a data-parallel mesh.

Run: python examples/train_lm_modern.py            (single chip / CPU)
     XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         JAX_PLATFORMS=cpu python examples/train_lm_modern.py   (8-dev mesh)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in __import__("sys").argv:
    jax.config.update("jax_platforms", "cpu")
import numpy as np

from paddle_tpu import models
from paddle_tpu.parallel import DataParallel, make_mesh

spec = models.get_model(
    "transformer_lm",
    seq_len=256,
    vocab=2048,
    d_model=256,
    d_inner=512,
    num_heads=8,
    num_kv_heads=2,          # GQA: 4 query heads share each kv head
    pos_encoding="rope",     # rotary embeddings at the attention rotation
    ffn_activation="swiglu",
    attention_window=128,    # sliding window: O(T*W) attention
    n_layers=2,
)

dp = DataParallel(
    spec.model, spec.optimizer(),
    mesh=make_mesh(data=-1),
    zero_shard_optimizer=True,  # Adam moments sharded over the data axis
)
rng = np.random.RandomState(0)
batch = spec.synth_batch(8 * dp.num_devices, rng)
variables, opt_state = dp.init(0, *batch)

for step in range(10):
    out = dp.step(variables, opt_state, *batch, rng=jax.random.PRNGKey(step))
    variables, opt_state = out.variables, out.opt_state
    print(f"step {step}: loss {float(out.loss):.4f}")
