"""Train a mixture-of-experts LM with expert parallelism.

The FFN in every block is an expert-parallel MoE (Switch router by
default): expert weights shard over the ``expert`` mesh axis, tokens
all-to-all to their experts and back, and the router's load-balance aux
loss joins the training loss. Composes with data parallelism (and, on a
joint mesh, with ring-attention sequence parallelism — see
tests/test_lm_moe.py).

Run on the 8-device virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_lm_moe.py
"""
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

if not os.environ.get("PT_EXAMPLE_TPU"):
    # APPEND to any existing XLA_FLAGS — setdefault would silently skip the
    # device-count flag and make_mesh would then fail on 1 CPU device
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax

if not os.environ.get("PT_EXAMPLE_TPU"):
    # default to the virtual CPU mesh; PT_EXAMPLE_TPU=1 runs on the real
    # backend instead
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from paddle_tpu import models  # noqa: E402
from paddle_tpu.parallel import DataParallel  # noqa: E402
from paddle_tpu.parallel.mesh import make_mesh  # noqa: E402


def main():
    mesh = make_mesh(expert=4, data=2)
    spec = models.get_model(
        "transformer_lm", seq_len=64, vocab=512, d_model=64, d_inner=128,
        num_heads=4, n_layers=2, max_len=64,
        moe_experts=4, moe_router="top1", moe_aux_weight=0.01,
        scan_layers=True,
    )
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 512, size=(8, 64)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)  # memorize next-token on a fixed batch

    trainer = DataParallel(
        spec.model, spec.optimizer(), mesh=mesh,
        batch_specs=[P("data"), P("data")], donate=False,
    )
    v, o = trainer.init(0, ids, labels)
    n_expert_params = sum(
        np.prod(p.shape) for k, p in v.params.items() if "moe_ffn" in k
    )
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}, "
          f"{n_expert_params:,} expert params")
    for step in range(1, 201):
        out = trainer.step(v, o, *trainer.put_batch(ids, labels))
        v, o = out.variables, out.opt_state
        if step % 40 == 0 or step == 1:
            print(f"step {step}: loss {float(out.loss):.4f}")
    assert float(out.loss) < 2.0, float(out.loss)
    print("memorization OK (loss includes the router aux term)")


if __name__ == "__main__":
    main()
