"""Exact test-set evaluation with uneven final batches — every sample
counts exactly once on a device mesh.

Static TPU shapes forbid ragged shards, so `DataParallel.pad_batch` pads
the final partial batch to the shard multiple (repeating the last real
row) and `Trainer.evaluate` threads the validity mask into a per-sample
metric: the reported accuracy is over EXACTLY N test samples, matching the
reference's data_balance guarantee (data_balance_op_handle.cc:154).

Data: REAL bundled UCI handwritten digits (dataset/digits.py — zero
egress), 359 test samples: with the default 8 virtual devices that is
2 x 128 + a ragged 103-row final batch (the mesh size follows
len(jax.devices()) — a preset XLA_FLAGS overrides the 8-device default).

Run: python examples/evaluate_exact_testset.py          # default backend
     python examples/evaluate_exact_testset.py --cpu    # force CPU (~10s)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as pt
from paddle_tpu import nets, reader
from paddle_tpu.dataset import digits
from paddle_tpu.parallel import make_mesh
from paddle_tpu.trainer import Trainer


def net(img, label):
    img = img.reshape(img.shape[0], 28, 28, 1)
    conv = nets.simple_img_conv_pool(
        img, num_filters=16, filter_size=5, pool_size=2, pool_stride=2, act="relu")
    logits = pt.layers.fc(conv.reshape(img.shape[0], -1), size=10, name="clf")
    loss = pt.layers.softmax_with_cross_entropy(logits, label).mean()
    return loss, logits


def batches(split_reader, bs, drop_last):
    r = reader.stack_batch(
        lambda: ((im, np.int64(lb)) for im, lb in split_reader()), bs,
        drop_last=drop_last,
    )
    return lambda: ((x.astype(np.float32), y.reshape(-1, 1)) for x, y in r())


def main():
    n_dev = len(jax.devices())
    tr = Trainer(
        lambda: pt.build(net, name="digits_net"),
        lambda: pt.optimizer.Adam(learning_rate=1e-3),
        parallel=True,
        parallel_kwargs=dict(mesh=make_mesh(data=n_dev)),
    )
    # train batches must divide the mesh; eval batches may be ragged
    tr.train(num_epochs=4, reader=batches(digits.train_as_mnist(), 64, True))

    test_n = sum(1 for _ in digits.test_as_mnist()())
    acc = tr.evaluate(
        batches(digits.test_as_mnist(), 128, False),  # final batch is ragged
        lambda out, x, y: (np.asarray(jax.numpy.argmax(out[1], -1))
                           == np.asarray(y)[:, 0]),
    )
    print(f"test accuracy over exactly {test_n} samples "
          f"({n_dev}-device mesh, ragged final batch): {acc:.4f}")


if __name__ == "__main__":
    main()
