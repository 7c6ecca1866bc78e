"""Train an MNIST classifier end-to-end — the minimal paddle_tpu workflow:
build -> init -> minimize -> Executor-style loop -> save for serving.

Run: python examples/train_mnist.py  (CPU or TPU; ~30s on CPU)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in __import__("sys").argv:
    jax.config.update("jax_platforms", "cpu")
import numpy as np

import paddle_tpu as pt
from paddle_tpu import dataset, nets, reader


def net(img, label):
    img = img.reshape(img.shape[0], 28, 28, 1)
    conv = nets.simple_img_conv_pool(
        img, num_filters=16, filter_size=3, pool_size=2, pool_stride=2, act="relu")
    logits = pt.layers.fc(conv.reshape(img.shape[0], -1), size=10)
    loss = pt.layers.softmax_with_cross_entropy(logits, label).mean()
    acc = pt.layers.accuracy(logits, label)
    return loss, acc


def main():
    model = pt.build(net)
    batches = reader.stack_batch(dataset.mnist.train(), 64)
    first = next(iter(batches()))
    variables = model.init(0, *first)
    opt = pt.optimizer.Adam(learning_rate=1e-3)
    opt_state = opt.create_state(variables.params)
    step = jax.jit(opt.minimize(model), donate_argnums=(0, 1))

    for epoch in range(2):
        for i, batch in enumerate(batches()):
            out = step(variables, opt_state, *[np.asarray(b) for b in batch])
            variables, opt_state = out.variables, out.opt_state
            if i % 20 == 0:
                print(f"epoch {epoch} step {i}: loss={float(out.loss):.4f}")

    # export for serving (StableHLO; native=True adds the C++ predictor artifact)
    def infer(img):
        img = img.reshape(img.shape[0], 28, 28, 1)
        conv = nets.simple_img_conv_pool(
            img, num_filters=16, filter_size=3, pool_size=2, pool_stride=2, act="relu")
        return pt.layers.fc(conv.reshape(img.shape[0], -1), size=10)

    infer_model = pt.build(infer)
    pt.io.save_inference_model("/tmp/mnist_model", infer_model, variables, [first[0]], native=True)
    print("saved inference model to /tmp/mnist_model")


if __name__ == "__main__":
    main()
