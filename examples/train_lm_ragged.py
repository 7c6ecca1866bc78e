"""Ragged long-context LM training: seq_lens + sliding window through the
flash ring, sequence-parallel over a seq mesh axis.

The reference's variable-length story was LoD tensors threaded through every
op (``paddle/fluid/framework/lod_tensor.h:60-110``); here ragged batches
travel as a [B] ``seq_lens`` vector — attention masks padded keys
STRUCTURALLY inside the fused flash kernels (global-position kv_len bounds,
so fully-padded tail blocks are skipped, not computed-and-masked), and the
loss averages real targets only. This composes with ring sequence
parallelism and sliding-window attention; run it on the 8-device CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_lm_ragged.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

# default to the virtual CPU mesh. Set PT_EXAMPLE_TPU=1 to run on the chip.
if not os.environ.get("PT_EXAMPLE_TPU"):
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.parallel.mesh import make_mesh


def main():
    # the fused kernels only pay off on real hardware; the CPU mesh runs the
    # (numerically identical) composed ring so the demo stays quick
    pt.core.config.set_flags(
        use_flash_attention=jax.devices()[0].platform == "tpu"
    )
    mesh = make_mesh(seq=4, data=2)
    spec = models.get_model(
        "transformer_lm", ring_mesh=mesh, seq_len=256, vocab=512,
        d_model=64, d_inner=128, num_heads=4, n_layers=2,
        attention_window=64,
    )
    rng = np.random.RandomState(0)
    bs, T = 8, 256
    ids = rng.randint(1, 512, size=(bs, T)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    seq_lens = rng.randint(T // 4, T + 1, size=(bs,)).astype(np.int32)
    for b in range(bs):  # zero the pad tail like a real tokenizer batch
        ids[b, seq_lens[b]:] = 0
        labels[b, seq_lens[b]:] = 0

    variables = spec.model.init(0, ids, labels, seq_lens)
    opt = spec.optimizer()
    opt_state = opt.create_state(variables.params)
    step = jax.jit(opt.minimize(spec.model))
    for s in range(20):
        out = step(variables, opt_state, ids, labels, seq_lens,
                   rng=jax.random.PRNGKey(s))
        variables, opt_state = out.variables, out.opt_state
        if s % 5 == 0 or s == 19:
            print(f"step {s:3d}  masked loss {float(out.loss):.4f}")


if __name__ == "__main__":
    main()
