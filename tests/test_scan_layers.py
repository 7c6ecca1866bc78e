"""scan-over-layers (``transformer_lm`` ``scan_layers=True``): the layer
stack compiles as ONE ``lax.scan`` body over stacked params — math must
match the unrolled loop exactly (deterministic configs), gradients
included, across the modern-stack feature matrix.
"""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import models
from paddle_tpu.models import transformer_lm


def _pair(seed=0, **cfg):
    """(unrolled_spec, scanned_spec) with identical params."""
    a = models.get_model("transformer_lm", seq_len=16, vocab=128, d_model=32,
                         d_inner=64, num_heads=4, n_layers=3, max_len=32,
                         scan_layers=False, **cfg)
    b = models.get_model("transformer_lm", seq_len=16, vocab=128, d_model=32,
                         d_inner=64, num_heads=4, n_layers=3, max_len=32,
                         scan_layers=True, **cfg)
    rng = np.random.RandomState(seed)
    batch = a.synth_batch(2, rng)
    va = a.model.init(0, *batch)
    vb = b.model.init(0, *batch)
    for k in va.params:
        np.testing.assert_array_equal(va.params[k], vb.params[k])
    return a, b, va, vb, batch


def _loss_and_grads(spec, variables, batch, **apply_kw):
    def loss_fn(v):
        (loss, *_), _ = spec.model.apply(v, *batch, **apply_kw)
        return loss

    loss, grads = jax.value_and_grad(lambda v: loss_fn(v))(variables)
    return float(loss), grads


def _assert_match(a, b, va, vb, batch, **apply_kw):
    la, ga = _loss_and_grads(a, va, batch, **apply_kw)
    lb, gb = _loss_and_grads(b, vb, batch, **apply_kw)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for k in ga.params:
        np.testing.assert_allclose(
            ga.params[k], gb.params[k], rtol=2e-4, atol=1e-5,
            err_msg=f"grad mismatch for {k}",
        )


def test_scan_matches_unrolled_fwd_bwd():
    _assert_match(*_pair())


def test_scan_matches_with_ragged_seq_lens():
    a, b, va, vb, batch = _pair()
    seq_lens = np.array([9, 16], np.int32)
    ba = (batch[0], batch[1], seq_lens)
    la, ga = _loss_and_grads(a, va, ba)
    lb, gb = _loss_and_grads(b, vb, ba)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for k in ga.params:
        np.testing.assert_allclose(ga.params[k], gb.params[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


def test_scan_matches_modern_stack():
    # rope x GQA x swiglu x sliding window through the scanned body
    _assert_match(*_pair(pos_encoding="rope", num_kv_heads=2,
                         ffn_activation="swiglu", attention_window=8))


def test_scan_remat_matches_no_remat():
    a, b, va, vb, batch = _pair()
    br = models.get_model("transformer_lm", seq_len=16, vocab=128, d_model=32,
                          d_inner=64, num_heads=4, n_layers=3, max_len=32,
                          scan_layers=True, remat=True)
    vr = br.model.init(0, *batch)
    for k in va.params:
        np.testing.assert_array_equal(va.params[k], vr.params[k])
    la, ga = _loss_and_grads(a, va, batch, is_train=True)
    lr, gr = _loss_and_grads(br, vr, batch, is_train=True)
    np.testing.assert_allclose(la, lr, rtol=1e-5, atol=1e-6)
    for k in ga.params:
        np.testing.assert_allclose(ga.params[k], gr.params[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


def test_scan_dropout_runs_finite():
    # dropout draws per-layer pre-split keys under scan (stream differs from
    # unrolled by design) — train-mode loss must stay finite and grad flow
    b = models.get_model("transformer_lm", seq_len=16, vocab=128, d_model=32,
                         d_inner=64, num_heads=4, n_layers=3, max_len=32,
                         scan_layers=True, residual_dropout=0.3,
                         attn_dropout=0.1)
    rng = np.random.RandomState(0)
    batch = b.synth_batch(2, rng)
    vb = b.model.init(0, *batch)

    def loss_fn(v):
        (loss, *_), _ = b.model.apply(v, *batch, rng=jax.random.PRNGKey(7),
                                      is_train=True)
        return loss

    loss, grads = jax.value_and_grad(loss_fn)(vb)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.square(g))) for g in grads.params.values())
    assert np.isfinite(gnorm) and gnorm > 0


import pytest


@pytest.mark.parametrize("bf16", [False, True])
def test_scan_composes_with_flash_route(bf16):
    """The bench lm_large config runs scan_layers WITH the flash flag on
    chip — pin the composition here: flash-routed attention inside the
    scanned body (interpret-mode kernels off-TPU) matches the unrolled
    flash-routed stack, gradients included. bf16=True is the exact bench
    flag set (looser tolerances); bf16=False keeps the tight-f32 check."""
    from paddle_tpu.core.config import flags, set_flags

    prev = flags().use_flash_attention
    prev_bf16 = flags().use_bf16_compute
    set_flags(use_flash_attention=True, use_bf16_compute=bf16)
    try:
        a = models.get_model("transformer_lm", seq_len=16, vocab=128,
                             d_model=32, d_inner=64, num_heads=4, n_layers=2,
                             max_len=32, scan_layers=False)
        b = models.get_model("transformer_lm", seq_len=16, vocab=128,
                             d_model=32, d_inner=64, num_heads=4, n_layers=2,
                             max_len=32, scan_layers=True)
        rng = np.random.RandomState(0)
        batch = a.synth_batch(2, rng)
        va = a.model.init(0, *batch)
        vb = b.model.init(0, *batch)
        la, ga = _loss_and_grads(a, va, batch)
        lb, gb = _loss_and_grads(b, vb, batch)
        rtol, atol = (5e-3, 1e-4) if bf16 else (2e-4, 1e-5)
        np.testing.assert_allclose(la, lb, rtol=max(rtol, 1e-4), atol=atol)
        for k in ga.params:
            np.testing.assert_allclose(ga.params[k], gb.params[k],
                                       rtol=rtol, atol=atol, err_msg=k)
    finally:
        set_flags(use_flash_attention=prev, use_bf16_compute=prev_bf16)


def _nmt_pair(**cfg):
    kw = dict(seq_len=12, src_vocab=64, trg_vocab=64, d_model=32, d_inner=64,
              num_heads=4, n_layers=3, max_len=32, attn_dropout=0.0,
              relu_dropout=0.0, residual_dropout=0.0)
    kw.update(cfg)
    a = models.get_model("transformer", scan_layers=False, **kw)
    b = models.get_model("transformer", scan_layers=True, **kw)
    rng = np.random.RandomState(0)
    batch = a.synth_batch(2, rng)
    va = a.model.init(0, *batch)
    vb = b.model.init(0, *batch)
    for k in va.params:
        np.testing.assert_array_equal(va.params[k], vb.params[k])
    return a, b, va, vb, batch


def test_nmt_scan_matches_unrolled_fwd_bwd():
    """Encoder AND decoder stacks (incl. cross-attention closure over
    enc_out) through scan_layer_stack."""
    a, b, va, vb, batch = _nmt_pair()
    la, ga = _loss_and_grads(a, va, batch)
    lb, gb = _loss_and_grads(b, vb, batch)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    for k in ga.params:
        np.testing.assert_allclose(ga.params[k], gb.params[k],
                                   rtol=2e-4, atol=1e-5, err_msg=k)


def test_nmt_scan_eval_logits_match():
    """Eval-mode forward (the inference path) matches between the scanned
    and unrolled stacks."""
    a, b, va, vb, batch = _nmt_pair()
    (la, _, logits_a), _ = a.model.apply(va, *batch, is_train=False)
    (lb, _, logits_b), _ = b.model.apply(vb, *batch, is_train=False)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b),
                               rtol=1e-4, atol=1e-5)


def test_scan_decode_parity():
    """generate() honors scan_layers (prefill AND per-token layer loops run
    as lax.scan): decoded tokens match the unrolled decode exactly."""
    a, b, va, vb, batch = _pair()
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(1, 128, size=(2, 5)).astype(np.int32)
    )
    cfg_a = a.extra["cfg"]
    cfg_b = b.extra["cfg"]
    ta = transformer_lm.generate(va, prompt, max_new_tokens=6, cfg=cfg_a)
    tb = transformer_lm.generate(vb, prompt, max_new_tokens=6, cfg=cfg_b)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


def test_scan_decode_bf16_cache_and_prestacked():
    """The exact bench decode path: scanned decode with an explicitly
    prestacked param tree (stack_decode_params, built outside jit) and the
    bf16 KV cache — tokens match the unrolled decode with the same cache
    dtype."""
    a, b, va, vb, batch = _pair()
    prompt = jnp.asarray(
        np.random.RandomState(11).randint(1, 128, size=(2, 6)).astype(np.int32)
    )
    stacked = transformer_lm.stack_decode_params(vb, b.extra["cfg"])
    ta = transformer_lm.generate(va, prompt, max_new_tokens=5,
                                 cfg=a.extra["cfg"], cache_dtype=jnp.bfloat16)
    tb = transformer_lm.generate(vb, prompt, max_new_tokens=5,
                                 cfg=b.extra["cfg"], cache_dtype=jnp.bfloat16,
                                 stacked_params=stacked)
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


def test_scan_decode_parity_modern_stack():
    """Scanned decode through rope x GQA x swiglu x sliding-window — the
    full cached-decode feature matrix under the layer scan."""
    a, b, va, vb, batch = _pair(pos_encoding="rope", num_kv_heads=2,
                                ffn_activation="swiglu", attention_window=8)
    prompt = jnp.asarray(
        np.random.RandomState(5).randint(1, 128, size=(2, 7)).astype(np.int32)
    )
    ta = transformer_lm.generate(va, prompt, max_new_tokens=5,
                                 cfg=a.extra["cfg"])
    tb = transformer_lm.generate(vb, prompt, max_new_tokens=5,
                                 cfg=b.extra["cfg"])
    np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))


def test_bench_lm_large_config_traces():
    """lm_big's widths (d_model 1024, 12 layers, 16 heads, T 2048) with
    scan_layers, as the train cells build them, only execute on a chip —
    trace the full train step abstractly here (jax.eval_shape: no compile)
    so a config/shape bug surfaces without a chip. Runs with the cells'
    flags set (bf16 + flash routing)."""
    import jax

    from paddle_tpu.core.config import flags, set_flags

    prev_f = flags().use_flash_attention
    prev_b = flags().use_bf16_compute
    set_flags(use_flash_attention=True, use_bf16_compute=True)
    try:
        spec = models.get_model(
            "transformer_lm", seq_len=2048, d_model=1024, d_inner=4096,
            num_heads=16, n_layers=12, max_len=2048, scan_layers=True,
        )
        rng = np.random.RandomState(0)
        batch = spec.synth_batch(2, rng)
        # fully abstract: ShapeDtypeStructs end to end — no 2.6GB of
        # concrete zeros for a 217M-param model's variables + Adam slots
        v = jax.eval_shape(lambda: spec.model.init(0, *batch))
        opt = spec.optimizer()
        o = jax.eval_shape(opt.create_state, v.params)
        out = jax.eval_shape(
            opt.minimize(spec.model), v, o, *batch,
            rng=jax.random.PRNGKey(0),
        )
        assert out.loss.shape == ()
        assert set(out.variables.params) == set(v.params)
    finally:
        set_flags(use_flash_attention=prev_f, use_bf16_compute=prev_b)


def test_bench_decode_and_transformer_configs_trace():
    """The bench decode section (seq-512 LM, scanned, prestacked params,
    Tp=128 prompt) and transformer section (default NMT, scanned) also run
    only on-chip — abstract-trace both so their configs can't break
    unnoticed."""
    import functools

    import jax

    from paddle_tpu.core.config import flags, set_flags

    prev_f = flags().use_flash_attention
    prev_b = flags().use_bf16_compute
    set_flags(use_flash_attention=True, use_bf16_compute=True)
    try:
        # decode section
        dspec = models.get_model("transformer_lm", seq_len=512,
                                 scan_layers=True)
        dcfg = dspec.extra["cfg"]
        rng = np.random.RandomState(0)
        v = jax.eval_shape(lambda: dspec.model.init(0, *dspec.synth_batch(1, rng)))
        stacked = jax.eval_shape(
            lambda p: transformer_lm.stack_decode_params(p, dcfg), v
        )
        prompt_shape = jax.ShapeDtypeStruct((8, 128), np.int32)
        out = jax.eval_shape(
            functools.partial(transformer_lm.generate, max_new_tokens=65,
                              cfg=dcfg, stacked_params=stacked),
            v, prompt_shape,
        )
        assert out.shape == (8, 65)

        # transformer section
        tspec = models.get_model("transformer", seq_len=256, scan_layers=True)
        tb = tspec.synth_batch(4, rng)
        tv = jax.eval_shape(lambda: tspec.model.init(0, *tb))
        topt = tspec.optimizer()
        to = jax.eval_shape(topt.create_state, tv.params)
        tout = jax.eval_shape(topt.minimize(tspec.model), tv, to, *tb,
                              rng=jax.random.PRNGKey(0))
        assert tout.loss.shape == ()
    finally:
        set_flags(use_flash_attention=prev_f, use_bf16_compute=prev_b)


def test_stack_layer_params_rejects_extra_suffixes():
    """ADVICE r4: a layer with suffixes layer 0 lacks (MoE checkpoint under
    a dense cfg) must raise the structured error, not be silently dropped."""
    import jax.numpy as jnp
    import pytest

    from paddle_tpu.core.enforce import EnforceError
    from paddle_tpu.framework import stack_layer_params

    name_of = lambda i: f"layer_{i}"
    params = {
        "layer_0/w": jnp.ones((2,)),
        "layer_1/w": jnp.ones((2,)),
        "layer_1/expert_0/w": jnp.ones((2,)),  # extra vs layer 0
    }
    with pytest.raises(EnforceError, match="not present in layer 0"):
        stack_layer_params(params, 2, name_of)


def _beam_scan_vs_unrolled(cfg_overrides, beam_size=2, mnt=4):
    """Exact-match harness: generate_beam with scan_layers=True must equal
    the unrolled beam decode token-for-token and score-for-score (same
    params, same prompt). VERDICT r4 #6."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.models import transformer_lm

    base = dict(seq_len=16, vocab=97, d_model=32, d_inner=48, num_heads=4,
                n_layers=3, max_len=64)
    base.update(cfg_overrides)
    spec = models.get_model("transformer_lm", **base)
    cfg = dict(spec.extra["cfg"])
    rng = np.random.RandomState(7)
    v = spec.model.init(0, *spec.synth_batch(2, rng))
    prompt = jnp.asarray(rng.randint(1, cfg["vocab"], size=(2, 5)).astype(np.int32))

    cfg_unrolled = dict(cfg, scan_layers=False)
    seqs_u, scores_u = transformer_lm.generate_beam(
        v, prompt, mnt, cfg_unrolled, beam_size=beam_size
    )
    cfg_scan = dict(cfg, scan_layers=True)
    stacked = transformer_lm.stack_decode_params(v, cfg_scan)
    seqs_s, scores_s = transformer_lm.generate_beam(
        v, prompt, mnt, cfg_scan, beam_size=beam_size, stacked_params=stacked
    )
    np.testing.assert_array_equal(np.asarray(seqs_u), np.asarray(seqs_s))
    np.testing.assert_allclose(
        np.asarray(scores_u), np.asarray(scores_s), rtol=2e-5, atol=2e-5
    )


def test_beam_scan_matches_unrolled_base():
    _beam_scan_vs_unrolled({})


def test_beam_scan_matches_unrolled_swiglu_window_gqa():
    """The configs the verdict singled out: SwiGLU FFN + sliding window,
    plus GQA so the cache holds fewer kv heads than query heads."""
    _beam_scan_vs_unrolled(
        dict(ffn_activation="swiglu", attention_window=4, num_kv_heads=2),
        beam_size=3,
    )


def test_beam_scan_matches_unrolled_rope():
    _beam_scan_vs_unrolled(dict(pos_encoding="rope"))


def test_stack_layer_params_multi_segment_names():
    """code-review r5: name_of values containing '/' (scoped layer names)
    must still bucket correctly in the single-pass rewrite."""
    import jax.numpy as jnp

    from paddle_tpu.framework import stack_layer_params

    params = {
        "blocks/layer_0/w": jnp.zeros((2,)),
        "blocks/layer_1/w": jnp.ones((2,)),
        "other/x": jnp.ones((1,)),
    }
    stacked = stack_layer_params(params, 2, lambda i: f"blocks/layer_{i}")
    assert set(stacked) == {"w"} and stacked["w"].shape == (2, 2)
