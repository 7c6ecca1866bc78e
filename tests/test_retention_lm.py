"""The power-retention LM (``models/retention_lm.py``) at a small size on the
CPU: the three forms of the layer's core agree, the ``retention_step`` kernel
(interpret mode) agrees with its einsum form, and ``pt.Trainer`` trains the
model with the loss and gradients of the plain reference."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models import retention_lm as R
from paddle_tpu.ops.pallas import retention as kernel

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import retention_lm as ref  # noqa: E402

SMALL = dict(vocab=97, d_model=64, d_inner=128, num_heads=4, num_kv_heads=2, head_dim=16,
             n_layers=2, ret_tile=8, train_chunk=8, param_dtype="float32",
             compute_dtype="float32")


def small_model(seq_len=24, **over):
    return models.get_model("retention_lm", seq_len=seq_len, **dict(SMALL, **over))


# -- (a) one layer's core: attention form = chunked form = recurrent form ----

@pytest.mark.parametrize("gates", ["near_0", "near_1", "mixed"])
def test_attention_chunked_and_recurrent_forms_agree(gates):
    G, T, C, dh, tile = 2, 50, 16, 16, 8  # 3 chunks and a last one of 2, padded to 16
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(G, T, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, dh)), jnp.float32)
    log_g = {"near_0": rng.uniform(-6.0, -3.0, T), "near_1": rng.uniform(-2e-3, -1e-4, T),
             "mixed": np.log(rng.uniform(0.05, 0.999, T))}[gates]
    log_g = jnp.asarray(log_g, jnp.float32)
    ones = jnp.ones((T,), jnp.float32)
    eps = 1e-6

    attention, _ = R.retention_chunk(q, k, R._augment(v, ones), log_g, None, tile=tile,
                                     cdt=jnp.float32)

    state = jnp.zeros((dh + R.PAD_ROWS, 3 * tile * tile), jnp.float32)
    pad = -T % C
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    kp, vp = (jnp.pad(x, ((0, pad), (0, 0))) for x in (k, v))
    valid = jnp.pad(ones, (0, pad))
    gp = jnp.pad(log_g, (0, pad), constant_values=-1.0) * valid  # a padded gate is ignored
    chunks = []
    for c in range(0, T + pad, C):
        out, state = R.retention_chunk(
            qp[:, c:c + C], kp[c:c + C], R._augment(vp[c:c + C], valid[c:c + C]),
            gp[c:c + C], state, tile=tile, cdt=jnp.float32)
        chunks.append(out)
    chunked = jnp.concatenate(chunks, 1)[:, :T]

    token_state = jnp.zeros_like(state)[None, None, None]  # one layer, slot and head
    tokens = []
    for t in range(T):
        out, token_state = kernel.retention_step_xla(
            token_state, R.phi(q[:, t], tile)[None, None], R.phi(k[t], tile, key_side=True)[None, None, None],
            R._augment(v[t], jnp.float32(1.0))[None, None, :, None], jnp.exp(log_g[t])[None, None], layer=0)
        tokens.append(out[0, 0])
    recurrent, token_state = jnp.stack(tokens, 1), token_state[0, 0, 0]

    # numerators and normalisers to 1e-5 of their scale; the quotient wherever
    # the normaliser is not itself rounding noise (gates near 0 leave a token
    # little but its own squared score)
    scale = float(jnp.max(jnp.abs(attention)))
    sound = np.asarray(attention[..., dh] > 1e-2)
    assert sound.mean() > 0.5
    for name, got in (("chunked", chunked), ("recurrent", recurrent)):
        np.testing.assert_allclose(got, attention, rtol=1e-5, atol=1e-5 * scale, err_msg=name)
        np.testing.assert_allclose(R._normalise(got, dh, eps)[sound],
                                   R._normalise(attention, dh, eps)[sound],
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    plain = ref.retention(q, k, v, log_g, eps, refc.mm_f32)
    np.testing.assert_allclose(plain[sound], R._normalise(attention, dh, eps)[sound],
                               rtol=1e-4, atol=1e-5, err_msg="reference")
    # the padded tail added nothing to the state and decayed nothing
    np.testing.assert_allclose(state, token_state, rtol=1e-5, atol=1e-6)


def test_the_tiled_power_map_is_the_squared_scaled_product():
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(rng.normal(size=(5, 32)), jnp.float32) for _ in range(2))
    got = jnp.sum(R.phi(x, 8) * R.phi(y, 8, key_side=True), -1)
    np.testing.assert_allclose(got, jnp.sum(x * y, -1) ** 2 / 32, rtol=1e-5, atol=1e-6)
    assert R.phi(x, 8).shape == (5, 10 * 64)
    assert R.state_dim({"head_dim": 128, "ret_tile": 16}) == 9216


# -- (f) the kernel, interpret mode, against the einsum form ------------------

def test_the_retention_step_kernel_is_the_einsum_form(monkeypatch):
    monkeypatch.setattr(kernel, "MAX_BLOCK_D", 256)  # D 768: three feature tiles
    L, S, H, Gp, dh, tile = 3, 4, 2, 5, 32, 16
    D, Rr = 3 * tile * tile, dh + R.PAD_ROWS
    rng = np.random.default_rng(3)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    state, phi_q, phi_k, v_aug = arr(L, S, H, Rr, D), arr(S, H, Gp, D), arr(S, H, 1, D), arr(S, H, Rr, 1)
    g = jnp.asarray(rng.uniform(0.1, 1.0, (S, H)), jnp.float32)
    idle = jnp.asarray([1, 0, 1, 1], jnp.float32)[:, None]  # slot 1 must not change
    g = jnp.where(idle > 0, g, 1.0)
    phi_k = phi_k * idle[:, :, None, None]
    want_acc, want_state = kernel.retention_step_xla(state, phi_q, phi_k, v_aug, g, layer=1)
    got_acc, got_state = jax.jit(functools.partial(kernel.retention_step, layer=1, interpret=True),
                                 donate_argnums=0)(state + 0.0, phi_q, phi_k, v_aug, g)
    np.testing.assert_allclose(got_acc, want_acc, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-6, atol=1e-6)
    got_state, state = np.asarray(got_state), np.asarray(state)
    np.testing.assert_array_equal(got_state[[0, 2]], state[[0, 2]])  # other layers
    np.testing.assert_array_equal(got_state[1, 1], state[1, 1])  # the idle slot
    monkeypatch.undo()
    assert kernel.state_block(9216) == 2304 and kernel.state_block(192) == 192


# -- (c) pt.Trainer: loss and gradients against the reference's ---------------

def test_trainer_loss_and_gradients_are_the_references():
    spec = small_model(seq_len=24)  # three chunks of 8: the chunked form, differentiated
    cfg = spec.extra["cfg"]
    ids, labels = spec.synth_batch(3, np.random.RandomState(1))
    variables = spec.model.init(0, ids, labels)
    params = {k: jnp.asarray(v) for k, v in variables.params.items()}

    mean_loss = lambda p: ref.loss_sum(p, ids, labels, cfg, refc.mm_f32) / labels.size
    want_loss, want_grad = jax.value_and_grad(mean_loss)(params)

    trainer = pt.Trainer(lambda: spec.model, lambda: pt.optimizer.SGD(learning_rate=1.0))
    # the step consumes the state it is handed: the Trainer gets a copy
    trainer.variables = trainer.exe.put(pt.framework.Variables(
        {k: jnp.array(v) for k, v in params.items()}, {}))
    trainer.opt_state = trainer.exe.put(trainer.optimizer.create_state(trainer.variables.params))
    losses = []
    trainer.train(num_epochs=1, reader=lambda: iter([(ids, labels)]),
                  event_handler=lambda ev: losses.append(ev.metrics)
                  if isinstance(ev, pt.trainer.EndStepEvent) else None)
    got_loss = np.asarray(losses[0]).reshape(-1)[0]
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for name, g in want_grad.items():  # SGD at rate 1: the step is the gradient
        got = params[name] - trainer.variables.params[name]
        np.testing.assert_allclose(got, g, rtol=2e-3, atol=2e-6, err_msg=name)


def test_the_model_is_in_the_registry_and_holds_bfloat16_by_default():
    spec = models.get_model("retention_lm", seq_len=8, vocab=97, d_model=32, d_inner=64,
                            num_heads=2, num_kv_heads=1, head_dim=16, n_layers=1, ret_tile=8)
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    assert {v.dtype for v in variables.params.values()} == {jnp.dtype("bfloat16")}
    assert set(variables.params) == set(R.param_shapes(spec.extra["cfg"]))
    (loss, _, logits), _ = spec.model.apply(variables, ids, labels)
    assert np.isfinite(float(loss)) and logits.dtype == jnp.float32
    assert models.serving_programs(spec.extra["cfg"]).cache == "state"
    assert models.serving_programs({"d_model": 8}).cache == "pages"
