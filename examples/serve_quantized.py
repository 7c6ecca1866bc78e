"""Serve a trained model from pure C++ via the native predictor, with
int8 weight-only quantization (~4x smaller artifact).

This path fits fixed-shape (single forward pass) inference. For
autoregressive generation, use the continuous-batching decode engine
instead — see examples/serve_decode.py (paged KV cache, iteration-level
admission, no per-shape recompiles).

Run: python examples/serve_quantized.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if "--cpu" in __import__("sys").argv:
    jax.config.update("jax_platforms", "cpu")
import numpy as np

import paddle_tpu as pt
from paddle_tpu.native import NativePredictor
from paddle_tpu.native.export import save_native_model

def train_net(x, y):
    h = pt.layers.fc(x, size=64, act="relu")
    logits = pt.layers.fc(h, size=4)
    return pt.layers.softmax_with_cross_entropy(logits, y).mean()

def serve_net(x):  # same layer order => same parameter names
    h = pt.layers.fc(x, size=64, act="relu")
    return pt.layers.fc(h, size=4)

model = pt.build(train_net)
rng = np.random.RandomState(0)
x = rng.randn(128, 16).astype(np.float32)
y = rng.randint(0, 4, (128, 1))
variables = model.init(0, x, y)
opt = pt.optimizer.Adam(learning_rate=1e-2)
opt_state = opt.create_state(variables.params)
step = jax.jit(opt.minimize(model))
for _ in range(50):
    out = step(variables, opt_state, x, y)
    variables, opt_state = out.variables, out.opt_state

serve_model = pt.build(serve_net)
save_native_model(serve_model, variables, [x], "/tmp/quant_model", quantize_int8=True)
pred = NativePredictor("/tmp/quant_model")   # pure C++ from here on
(logits,) = pred.run(x)
print("C++ int8 predictions:", logits.argmax(1)[:16].tolist())
pred.close()
