"""Model-zoo tests — the "book tests" analogue (reference
``python/paddle/fluid/tests/book/``): train each model config a few steps on
synthetic data and assert the loss decreases; shape-check the heavy towers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models


def _train_steps(spec, batch_size=4, steps=4, seed=0):
    rng = np.random.RandomState(seed)
    batch = spec.synth_batch(batch_size, rng)
    variables = spec.model.init(0, *batch)
    opt = spec.optimizer()
    opt_state = opt.create_state(variables.params)
    step_fn = jax.jit(opt.minimize(spec.model))
    losses = []
    for i in range(steps):
        out = step_fn(variables, opt_state, *batch, rng=jax.random.PRNGKey(i))
        variables, opt_state = out.variables, out.opt_state
        losses.append(float(out.loss))
    return losses


def test_mnist_trains():
    spec = models.get_model("mnist")
    losses = _train_steps(spec, batch_size=8, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_resnet_cifar_trains():
    spec = models.get_model("resnet", dataset="cifar10", depth=20)
    losses = _train_steps(spec, batch_size=4, steps=4)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_resnet50_imagenet_forward_shape():
    spec = models.get_model("resnet", dataset="flowers", depth=50, image_size=64, class_dim=17)
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    (loss, acc, logits), _ = spec.model.apply(variables, *batch)
    assert logits.shape == (2, 17)
    assert np.isfinite(float(loss))


def test_vgg_trains():
    spec = models.get_model("vgg", dataset="cifar10")
    losses = _train_steps(spec, batch_size=4, steps=4)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_se_resnext_forward_shape():
    spec = models.get_model("se_resnext", depth=50, image_size=64, class_dim=11)
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    (loss, acc, logits), _ = spec.model.apply(variables, *batch)
    assert logits.shape == (2, 11)
    assert np.isfinite(float(loss))


def test_transformer_trains():
    spec = models.get_model(
        "transformer",
        seq_len=12,
        src_vocab=120,
        trg_vocab=120,
        d_model=32,
        d_inner=64,
        num_heads=4,
        n_layers=2,
        warmup_steps=10,
    )
    losses = _train_steps(spec, batch_size=4, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_transformer_loss_near_uniform_at_init():
    # label-smoothed CE at random init should sit near log(vocab)
    vocab = 120
    spec = models.get_model(
        "transformer", seq_len=8, src_vocab=vocab, trg_vocab=vocab,
        d_model=32, d_inner=64, num_heads=4, n_layers=1,
    )
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(4, rng)
    variables = spec.model.init(0, *batch)
    (loss, n_tok, _), _ = spec.model.apply(variables, *batch)
    assert abs(float(loss) - np.log(vocab)) < 1.5


def test_stacked_lstm_trains():
    spec = models.get_model(
        "stacked_dynamic_lstm", vocab_size=200, emb_dim=32, hidden_dim=32,
        stacked_num=2, seq_len=16,
    )
    losses = _train_steps(spec, batch_size=4, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_machine_translation_trains():
    spec = models.get_model(
        "machine_translation", vocab_size=150, emb_dim=32, hidden_dim=32, seq_len=10,
    )
    losses = _train_steps(spec, batch_size=4, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_model_registry_unknown():
    with pytest.raises(KeyError):
        models.get_model("nope")


def test_transformer_lm_trains():
    spec = models.get_model(
        "transformer_lm", seq_len=32, vocab=128, d_model=64, d_inner=128,
        num_heads=4, n_layers=2,
    )
    losses = _train_steps(spec, batch_size=4, steps=5)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_transformer_lm_flash_and_bf16_flags_match_composed():
    """The flag-routed flash+bf16 LM forward stays close to the plain f32
    composed path (same params, same batch)."""
    spec = models.get_model(
        "transformer_lm", seq_len=32, vocab=128, d_model=64, d_inner=128,
        num_heads=4, n_layers=2,
    )
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(4, rng)
    variables = spec.model.init(0, *batch)

    (loss_plain, _, _), _ = spec.model.apply(variables, *batch, is_train=False)
    pt.core.config.set_flags(use_flash_attention=True, use_bf16_compute=True)
    try:
        (loss_flash, _, _), _ = spec.model.apply(variables, *batch, is_train=False)
    finally:
        pt.core.config.set_flags(use_flash_attention=False, use_bf16_compute=False)
    np.testing.assert_allclose(float(loss_plain), float(loss_flash), rtol=2e-2)


def test_bf16_compute_flag_halves_matmul_inputs():
    """use_bf16_compute must actually reach the MXU ops: the jitted fc
    jaxpr contains a bf16 dot_general."""
    def net(x):
        return jnp.sum(pt.layers.fc(x, size=8))

    model = pt.build(net)
    x = jnp.ones((4, 8), jnp.float32)
    variables = model.init(0, x)
    pt.core.config.set_flags(use_bf16_compute=True)
    try:
        jaxpr = jax.make_jaxpr(lambda v, x: model.apply(v, x)[0])(variables, x)
    finally:
        pt.core.config.set_flags(use_bf16_compute=False)
    assert "bf16" in str(jaxpr), str(jaxpr)[:500]


def test_transformer_lm_generate_matches_naive_decode():
    """Cached scan decode == naive grow-the-prompt greedy decode through
    the training forward (validates the k/v cache exactly)."""
    from paddle_tpu.models import transformer_lm

    cfg_kw = dict(seq_len=8, vocab=64, d_model=32, d_inner=64, num_heads=2, n_layers=2)
    spec = models.get_model("transformer_lm", **cfg_kw)
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]

    prompt = jnp.asarray(rng.randint(1, 64, size=(2, 8)).astype(np.int32))
    out = transformer_lm.generate(variables, prompt, max_new_tokens=5, cfg=cfg)
    assert out.shape == (2, 5) and out.dtype == jnp.int32

    # naive: rerun the full forward on the growing sequence each step
    seq = prompt
    naive = []
    for _ in range(5):
        ids = seq
        labels = jnp.zeros_like(ids)
        (_, _, logits), _ = spec.model.apply(variables, ids, labels, is_train=False)
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    naive = jnp.stack(naive, axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))


def test_transformer_lm_generate_sampling_shapes():
    from paddle_tpu.models import transformer_lm

    spec = models.get_model(
        "transformer_lm", seq_len=8, vocab=32, d_model=16, d_inner=32,
        num_heads=2, n_layers=1,
    )
    rng = np.random.RandomState(1)
    variables = spec.model.init(0, *spec.synth_batch(2, rng))
    prompt = jnp.asarray(rng.randint(1, 32, size=(2, 8)).astype(np.int32))
    out = transformer_lm.generate(
        variables, prompt, max_new_tokens=4, cfg=spec.extra["cfg"],
        temperature=0.8, rng=jax.random.PRNGKey(7),
    )
    assert out.shape == (2, 4)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < 32))


def test_transformer_nmt_structural_masking_matches_additive():
    """With use_flash_attention on, the NMT transformer swaps additive
    pad/causal masks for kv_len bounds + kernel causality; the loss (which
    zero-weights pad tokens) must match the mask path to kernel precision."""
    spec = models.get_model(
        "transformer", seq_len=16, src_vocab=64, trg_vocab=64, d_model=32,
        d_inner=64, num_heads=2, n_layers=2, max_len=32,
        attn_dropout=0.0, relu_dropout=0.0, residual_dropout=0.0,
    )
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(4, rng)
    variables = spec.model.init(0, *batch)

    (loss_mask, _, _), _ = spec.model.apply(variables, *batch, is_train=False)
    pt.core.config.set_flags(use_flash_attention=True)
    try:
        (loss_flash, _, _), _ = spec.model.apply(variables, *batch, is_train=False)
    finally:
        pt.core.config.set_flags(use_flash_attention=False)
    np.testing.assert_allclose(float(loss_mask), float(loss_flash), rtol=1e-4)


def test_transformer_lm_remat_matches_plain():
    """cfg remat=True: same loss AND same gradients, just recomputed."""
    kw = dict(seq_len=16, vocab=64, d_model=32, d_inner=64, num_heads=2, n_layers=2)
    plain = models.get_model("transformer_lm", **kw)
    remat = models.get_model("transformer_lm", remat=True, **kw)
    rng = np.random.RandomState(0)
    batch = plain.synth_batch(4, rng)
    # init THROUGH the remat model: param creation must not leak tracers
    # out of the checkpoint region (regression: UnexpectedTracerError)
    variables = remat.model.init(0, *batch)

    opt = pt.optimizer.SGD(learning_rate=0.1)
    o1 = jax.jit(opt.minimize(plain.model))(variables, opt.create_state(variables.params), *batch)
    o2 = jax.jit(opt.minimize(remat.model))(variables, opt.create_state(variables.params), *batch)
    np.testing.assert_allclose(float(o1.loss), float(o2.loss), rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(o1.variables.params),
        jax.tree_util.tree_leaves(o2.variables.params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_transformer_nmt_structural_masking_training_trajectory():
    """Training trajectories under structural masking (flash flag) are
    IDENTICAL to the additive-mask path — gradient-level equivalence of
    kv_len + kernel causality on the NMT transformer."""
    def run(flag):
        pt.core.config.set_flags(use_flash_attention=flag)
        try:
            # dropout must be 0: the flash routing gate rejects training-mode
            # dropout, and the whole point is to exercise the kernel path
            spec = models.get_model(
                "transformer", seq_len=16, src_vocab=64, trg_vocab=64,
                d_model=32, d_inner=64, num_heads=2, n_layers=1, max_len=32,
                learning_rate=0.5, warmup_steps=2,
                attn_dropout=0.0, relu_dropout=0.0, residual_dropout=0.0,
            )
            return _train_steps(spec, batch_size=4, steps=5)
        finally:
            pt.core.config.set_flags(use_flash_attention=False)

    np.testing.assert_allclose(run(False), run(True), rtol=1e-5)


def test_transformer_lm_generate_gqa_matches_naive_decode():
    """GQA model (num_kv_heads < num_heads): the H_kv-head static cache
    decode must equal the naive grow-the-prompt greedy decode."""
    from paddle_tpu.models import transformer_lm

    cfg_kw = dict(seq_len=8, vocab=64, d_model=32, d_inner=64, num_heads=4,
                  num_kv_heads=2, n_layers=2)
    spec = models.get_model("transformer_lm", **cfg_kw)
    rng = np.random.RandomState(0)
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]

    prompt = jnp.asarray(rng.randint(1, 64, size=(2, 8)).astype(np.int32))
    out = transformer_lm.generate(variables, prompt, max_new_tokens=5, cfg=cfg)

    seq = prompt
    naive = []
    for _ in range(5):
        (_, _, logits), _ = spec.model.apply(
            variables, seq, jnp.zeros_like(seq), is_train=False
        )
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.stack(naive, 1)))


def test_modern_lm_stack_trains():
    """RoPE + GQA + SwiGLU together (the modern decoder stack) train and
    decrease loss; generate() guards fire for the unsupported decode combo."""
    rng = np.random.RandomState(0)
    spec = models.get_model(
        "transformer_lm", seq_len=32, vocab=64, d_model=32, num_heads=4,
        num_kv_heads=2, n_layers=1, max_len=32, pos_encoding="rope",
        ffn_activation="swiglu",
    )
    batch = spec.synth_batch(4, rng)
    v = spec.model.init(0, *batch)
    assert "layer_0/ffn/gate/w" in v.params
    assert v.params["layer_0/self_attn/k/w"].shape[1] == 16  # 2 kv heads * 8
    opt = spec.optimizer()
    os_ = opt.create_state(v.params)
    step = jax.jit(opt.minimize(spec.model))
    losses = []
    for i in range(4):
        out = step(v, os_, *[jnp.asarray(b) for b in batch], rng=jax.random.PRNGKey(i))
        v, os_ = out.variables, out.opt_state
        losses.append(float(out.loss))
    assert losses[-1] < losses[0]


def test_lm_attention_window_trains_and_limits_context():
    """attention_window: the LM trains, and a token's logits are invariant
    to tokens further back than the window."""
    rng = np.random.RandomState(0)
    kw = dict(seq_len=32, vocab=64, d_model=32, num_heads=2, n_layers=1,
              max_len=32, attention_window=8)
    spec = models.get_model("transformer_lm", **kw)
    batch = spec.synth_batch(2, rng)
    v = spec.model.init(0, *batch)

    ids = np.asarray(batch[0]).copy()
    (_, _, logits_a), _ = spec.model.apply(v, jnp.asarray(ids), jnp.asarray(batch[1]), is_train=False)
    # perturb a token 20 positions before the last: outside window 8
    ids_b = ids.copy()
    ids_b[:, 11] = (ids_b[:, 11] + 7) % 63 + 1
    (_, _, logits_b), _ = spec.model.apply(v, jnp.asarray(ids_b), jnp.asarray(batch[1]), is_train=False)
    np.testing.assert_allclose(
        np.asarray(logits_a[:, -1]), np.asarray(logits_b[:, -1]), rtol=1e-5, atol=1e-6
    )
    # ... but a token INSIDE the window changes the logits
    ids_c = ids.copy()
    ids_c[:, 30] = (ids_c[:, 30] + 7) % 63 + 1
    (_, _, logits_c), _ = spec.model.apply(v, jnp.asarray(ids_c), jnp.asarray(batch[1]), is_train=False)
    assert float(np.abs(np.asarray(logits_c[:, -1]) - np.asarray(logits_a[:, -1])).max()) > 1e-4

    opt = spec.optimizer()
    os_ = opt.create_state(v.params)
    out = jax.jit(opt.minimize(spec.model))(v, os_, *[jnp.asarray(b) for b in batch], rng=jax.random.PRNGKey(0))
    assert np.isfinite(float(out.loss))


def test_transformer_lm_generate_beam_matches_greedy_at_k1():
    """beam_size=1 beam decode == greedy generate (the decode-math pin for
    generate_beam), GQA config included; wider beams score >= the greedy
    path's sequence under the same model."""
    from paddle_tpu.models import transformer_lm

    rng = np.random.RandomState(0)
    for kw in (
        dict(seq_len=8, vocab=64, d_model=32, d_inner=64, num_heads=2, n_layers=2),
        dict(seq_len=8, vocab=64, d_model=32, d_inner=64, num_heads=4,
             num_kv_heads=2, n_layers=1),
    ):
        spec = models.get_model("transformer_lm", **kw)
        batch = spec.synth_batch(2, rng)
        variables = spec.model.init(0, *batch)
        cfg = spec.extra["cfg"]
        prompt = jnp.asarray(rng.randint(2, 64, size=(2, 6)).astype(np.int32))

        greedy = transformer_lm.generate(variables, prompt, 5, cfg)
        seqs, scores = transformer_lm.generate_beam(
            variables, prompt, 5, cfg, beam_size=1, eos_id=1
        )
        np.testing.assert_array_equal(np.asarray(seqs[:, 0]), np.asarray(greedy))

        seqs4, scores4 = transformer_lm.generate_beam(
            variables, prompt, 5, cfg, beam_size=4, eos_id=1
        )
        # beams come back best-first and the best is at least the greedy score
        assert np.all(np.diff(np.asarray(scores4), axis=1) <= 1e-6)
        assert np.all(np.asarray(scores4[:, 0]) >= np.asarray(scores[:, 0]) - 1e-5)


def test_transformer_lm_generate_swiglu_matches_naive_decode():
    """SwiGLU decode parity (advisor r3 high): a swiglu-trained model must
    decode through the gate weights — cached scan decode AND beam_size=1
    beam decode must exactly match naive grow-the-prompt greedy decode
    through the swiglu training forward."""
    from paddle_tpu.models import transformer_lm

    rng = np.random.RandomState(0)
    spec = models.get_model(
        "transformer_lm", seq_len=8, vocab=64, d_model=32, d_inner=64,
        num_heads=2, n_layers=2, ffn_activation="swiglu",
    )
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]
    prompt = jnp.asarray(rng.randint(2, 64, size=(2, 8)).astype(np.int32))

    out = transformer_lm.generate(variables, prompt, max_new_tokens=5, cfg=cfg)
    seq = prompt
    naive = []
    for _ in range(5):
        (_, _, logits), _ = spec.model.apply(
            variables, seq, jnp.zeros_like(seq), is_train=False
        )
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    naive = jnp.stack(naive, 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))
    seqs, _ = transformer_lm.generate_beam(variables, prompt, 5, cfg, beam_size=1)
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]), np.asarray(naive))


def test_transformer_lm_generate_window_matches_naive_decode():
    """Sliding-window decode parity (advisor r3 medium): with
    attention_window set, prefill masks the same band and decode attends
    only the last W cache positions — exact match vs the training forward
    (whose scaled_dot_product_attention applies the window mask)."""
    from paddle_tpu.models import transformer_lm

    rng = np.random.RandomState(1)
    spec = models.get_model(
        "transformer_lm", seq_len=8, vocab=64, d_model=32, d_inner=64,
        num_heads=2, n_layers=2, attention_window=3,
    )
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]
    prompt = jnp.asarray(rng.randint(2, 64, size=(2, 8)).astype(np.int32))

    out = transformer_lm.generate(variables, prompt, max_new_tokens=6, cfg=cfg)
    seq = prompt
    naive = []
    for _ in range(6):
        (_, _, logits), _ = spec.model.apply(
            variables, seq, jnp.zeros_like(seq), is_train=False
        )
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    naive = jnp.stack(naive, 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))
    # beam_size=1 greedy beam equals naive token-for-token UNTIL naive
    # emits the beam's eos (default eos_id=1): the beam finishes that row
    # there and eos-pads the remainder, while the naive loop above keeps
    # decoding past it. A blanket equality is wrong whenever the model
    # happens to emit token 1 mid-generation — compare with eos
    # semantics, exactly, in both regimes.
    seqs, _ = transformer_lm.generate_beam(variables, prompt, 6, cfg,
                                           beam_size=1)
    beam = np.asarray(seqs[:, 0])
    ref = np.asarray(naive)
    for b in range(ref.shape[0]):
        hits = np.flatnonzero(ref[b] == 1)
        if hits.size:
            j = int(hits[0])
            np.testing.assert_array_equal(beam[b, :j + 1], ref[b, :j + 1])
            np.testing.assert_array_equal(
                beam[b, j + 1:], np.ones_like(beam[b, j + 1:]))
        else:
            np.testing.assert_array_equal(beam[b], ref[b])


def test_transformer_lm_generate_rope_matches_naive_decode():
    """RoPE cached decode: K is cached pre-rotated at its own position, so
    the scan decode must exactly match naive grow-the-prompt greedy decode
    through the rope training forward."""
    from paddle_tpu.models import transformer_lm

    rng = np.random.RandomState(0)
    spec = models.get_model(
        "transformer_lm", seq_len=8, vocab=64, d_model=32, d_inner=64,
        num_heads=2, n_layers=2, pos_encoding="rope",
    )
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]
    prompt = jnp.asarray(rng.randint(2, 64, size=(2, 8)).astype(np.int32))

    out = transformer_lm.generate(variables, prompt, max_new_tokens=5, cfg=cfg)
    seq = prompt
    naive = []
    for _ in range(5):
        (_, _, logits), _ = spec.model.apply(
            variables, seq, jnp.zeros_like(seq), is_train=False
        )
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jnp.stack(naive, 1)))


def test_transformer_lm_generate_topk_topp():
    """top_k=1 sampling == greedy; top_p nucleus sampling yields valid ids."""
    from paddle_tpu.models import transformer_lm

    rng = np.random.RandomState(0)
    spec = models.get_model(
        "transformer_lm", seq_len=8, vocab=64, d_model=32, d_inner=64,
        num_heads=2, n_layers=1,
    )
    batch = spec.synth_batch(2, rng)
    v = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]
    prompt = jnp.asarray(rng.randint(2, 64, size=(2, 6)).astype(np.int32))

    greedy = transformer_lm.generate(v, prompt, 4, cfg)
    k1 = transformer_lm.generate(
        v, prompt, 4, cfg, temperature=1.0, rng=jax.random.PRNGKey(7), top_k=1
    )
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(greedy))

    p9 = transformer_lm.generate(
        v, prompt, 4, cfg, temperature=0.8, rng=jax.random.PRNGKey(7), top_p=0.9
    )
    ids = np.asarray(p9)
    assert ids.shape == (2, 4) and (0 <= ids).all() and (ids < 64).all()


def _memorize_lm(spec, seed=0, steps=120):
    """Train an LM to memorize a fixed next-token batch (confident logits
    so decode A/B tests are deterministic). Returns (variables, prompt)."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, 64, size=(4, 16)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    v = spec.model.init(0, ids, labels)
    opt = spec.optimizer()
    o = opt.create_state(v.params)
    step = jax.jit(opt.minimize(spec.model))
    for s in range(steps):
        res = step(v, o, ids, labels, rng=jax.random.PRNGKey(s))
        v, o = res.variables, res.opt_state
    assert float(res.loss) < 0.5, float(res.loss)
    return v, jnp.asarray(ids[:, :8])


def test_transformer_lm_generate_bf16_cache_matches_f32_when_confident():
    """cache_dtype=bf16 (half the decode HBM traffic) decodes the same
    tokens as the f32 cache once the model is confident: memorize a fixed
    next-token batch, then greedy-decode with both cache dtypes."""
    from paddle_tpu.models import transformer_lm

    spec = models.get_model(
        "transformer_lm", seq_len=16, vocab=64, d_model=32, d_inner=64,
        num_heads=2, n_layers=2,
    )
    v, prompt = _memorize_lm(spec, seed=0)
    cfg = spec.extra["cfg"]
    out32 = transformer_lm.generate(v, prompt, 6, cfg)
    out16 = transformer_lm.generate(v, prompt, 6, cfg, cache_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out32), np.asarray(out16))

    seqs32, _ = transformer_lm.generate_beam(v, prompt, 6, cfg, beam_size=1)
    seqs16, _ = transformer_lm.generate_beam(
        v, prompt, 6, cfg, beam_size=1, cache_dtype=jnp.bfloat16
    )
    np.testing.assert_array_equal(np.asarray(seqs32), np.asarray(seqs16))


def test_transformer_lm_generate_modern_stack_matches_naive_decode():
    """All modern-stack options AT ONCE — RoPE + GQA + SwiGLU + sliding
    window: cached decode and beam_size=1 beam both exactly match naive
    grow-the-prompt greedy decode through the training forward."""
    from paddle_tpu.models import transformer_lm

    rng = np.random.RandomState(5)
    spec = models.get_model(
        "transformer_lm", seq_len=8, vocab=64, d_model=32, d_inner=64,
        num_heads=4, num_kv_heads=2, n_layers=2, pos_encoding="rope",
        ffn_activation="swiglu", attention_window=4,
    )
    batch = spec.synth_batch(2, rng)
    variables = spec.model.init(0, *batch)
    cfg = spec.extra["cfg"]
    prompt = jnp.asarray(rng.randint(2, 64, size=(2, 8)).astype(np.int32))

    out = transformer_lm.generate(variables, prompt, max_new_tokens=6, cfg=cfg)
    seq = prompt
    naive = []
    for _ in range(6):
        (_, _, logits), _ = spec.model.apply(
            variables, seq, jnp.zeros_like(seq), is_train=False
        )
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    naive = jnp.stack(naive, 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(naive))
    seqs, _ = transformer_lm.generate_beam(variables, prompt, 6, cfg, beam_size=1)
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]), np.asarray(naive))


def test_transformer_lm_generate_flash_prefill_matches_composed():
    """With use_flash_attention ON, prefill routes through the fused kernel
    (no [Tp, Tp] materialization); a confident (memorized) model must decode
    the same tokens as the flag-off composed path, greedy and beam."""
    from paddle_tpu.models import transformer_lm

    spec = models.get_model(
        "transformer_lm", seq_len=16, vocab=64, d_model=32, d_inner=64,
        num_heads=4, num_kv_heads=2, n_layers=2, attention_window=8,
    )
    v, prompt = _memorize_lm(spec, seed=2)
    cfg = spec.extra["cfg"]
    out_composed = transformer_lm.generate(v, prompt, 6, cfg)
    beam_composed, _ = transformer_lm.generate_beam(v, prompt, 6, cfg, beam_size=1)
    pt.core.config.set_flags(use_flash_attention=True)
    try:
        out_flash = transformer_lm.generate(v, prompt, 6, cfg)
        beam_flash, _ = transformer_lm.generate_beam(v, prompt, 6, cfg, beam_size=1)
    finally:
        pt.core.config.set_flags(use_flash_attention=False)
    np.testing.assert_array_equal(np.asarray(out_composed), np.asarray(out_flash))
    np.testing.assert_array_equal(np.asarray(beam_composed), np.asarray(beam_flash))


@pytest.mark.parametrize("entry", [
    "generate", "generate_scan", "generate_beam",
    "paged_prefill_chunk", "paged_decode_step", "paged_verify_step"])
def test_transformer_lm_decoders_share_one_block(monkeypatch, entry):
    """Every decode-side entry point runs its layers through the module's
    one ``decode_block`` — n_layers calls a pass over the layers (the static
    cache's two decoders make two passes: the prompt, then the token step
    their scan traces once), a single call a pass under the layer scan — and
    the decode-side parameter prefix is spelled in that one place."""
    import functools
    import inspect

    from paddle_tpu.models import transformer_lm as tlm

    n_layers = 3
    spec = models.get_model("transformer_lm", seq_len=16, vocab=32, d_model=16,
                            d_inner=32, num_heads=2, n_layers=n_layers,
                            scan_layers=entry == "generate_scan")
    cfg = spec.extra["cfg"]
    variables = jax.eval_shape(
        lambda: spec.model.init(0, *spec.synth_batch(1, np.random.RandomState(0))))
    calls = []
    block = tlm.decode_block
    monkeypatch.setattr(
        tlm, "decode_block", lambda *a, **k: (calls.append(a[2]), block(*a, **k))[1])

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    slots, page, per_slot = 2, 4, 4
    pages = jax.ShapeDtypeStruct(
        tlm.paged_cache_shape(cfg, 1 + slots * per_slot, page), jnp.float32)
    paged = dict(cfg=cfg, page_size=page)
    fn, args, passes = {
        "generate": (functools.partial(tlm.generate, max_new_tokens=3, cfg=cfg),
                     (i32(2, 5),), 2),
        "generate_scan": (functools.partial(tlm.generate, max_new_tokens=3, cfg=cfg),
                          (i32(2, 5),), 2),
        "generate_beam": (functools.partial(tlm.generate_beam, max_new_tokens=3,
                                            cfg=cfg, beam_size=2), (i32(2, 5),), 2),
        "paged_prefill_chunk": (functools.partial(tlm.paged_prefill_chunk, **paged),
                                (i32(8), i32(), i32(), i32(per_slot), pages, pages), 1),
        "paged_decode_step": (functools.partial(tlm.paged_decode_step, **paged),
                              (i32(slots), i32(slots), i32(slots, per_slot), pages, pages), 1),
        "paged_verify_step": (functools.partial(tlm.paged_verify_step, **paged),
                              (i32(slots, 3), i32(slots), i32(slots, per_slot), pages, pages), 1),
    }[entry]
    jax.eval_shape(fn, variables.params, *args)
    if entry == "generate_scan":
        assert calls == ["SCAN"] * passes
    else:
        assert calls == list(range(n_layers)) * passes
    assert inspect.getsource(tlm).count('/self_attn"') == 1
