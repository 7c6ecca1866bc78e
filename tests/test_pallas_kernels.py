"""Pallas kernel tests in interpret mode on CPU (the kernels compile for
real on the TPU chip; see .claude/skills/verify)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention


def _ref_attention(q, k, v, causal):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        s = np.where(np.tril(np.ones((T, S), bool)), s, -1e9)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(rng, causal):
    B, H, T, d = 2, 2, 64, 16
    q = rng.randn(B, H, T, d).astype(np.float32)
    k = rng.randn(B, H, T, d).astype(np.float32)
    v = rng.randn(B, H, T, d).astype(np.float32)
    out = jax.jit(
        lambda a, b, c: flash_attention(a, b, c, causal=causal, block_q=16, block_k=16)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), _ref_attention(q, k, v, causal), rtol=2e-4, atol=2e-5
    )


def test_flash_attention_single_block(rng):
    B, H, T, d = 1, 1, 8, 4
    q = rng.randn(B, H, T, d).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q))
    np.testing.assert_allclose(
        np.asarray(out), _ref_attention(q, q, q, False), rtol=2e-4, atol=2e-5
    )


def test_flash_attention_grad(rng):
    B, H, T, d = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=8, block_k=8) ** 2)

    g_q, g_k, g_v = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    # compare against grads of the plain composed attention
    def ref_loss(q, k, v):
        d_ = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(d_)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -1e9)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) ** 2)

    r_q, r_k, r_v = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_q), np.asarray(r_q), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(r_k), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_v), np.asarray(r_v), rtol=1e-3, atol=1e-4)


def test_flash_attention_bf16_forward(rng):
    B, H, T, d = 1, 1, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32)).astype(jnp.bfloat16)
    out = flash_attention(q, q, q, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)),
        _ref_attention(*(np.asarray(q.astype(jnp.float32)),) * 3, False),
        rtol=5e-2, atol=5e-2,
    )


def test_flag_routes_sdpa_through_flash(rng):
    from paddle_tpu.core import config
    from paddle_tpu.ops import attention as oattn

    B, H, T, d = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    base = oattn.scaled_dot_product_attention(q, q, q)
    config.set_flags(use_flash_attention=True)
    try:
        flashed = oattn.scaled_dot_product_attention(q, q, q)
    finally:
        config.set_flags(use_flash_attention=False)
    np.testing.assert_allclose(np.asarray(base), np.asarray(flashed), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("axes,batch", [
    (dict(data=4), 4), (dict(data=2, model=2), 4), (dict(data=4), 3),
], ids=["data4", "data2_model2", "ragged_batch_replicated"])
def test_flash_runs_per_shard_under_a_mesh(rng, axes, batch):
    """Under an ambient mesh (jax.set_mesh, as DataParallel.step sets it)
    sdpa wraps the kernel in shard_map — a Mosaic kernel cannot be
    partitioned automatically on real chips (tests/test_chip_compile.py
    holds that compile) — and the result equals the bare call."""
    from paddle_tpu.core import config
    from paddle_tpu.ops import attention as oattn
    from paddle_tpu.parallel.mesh import make_mesh

    H, T, d = 4, 32, 8
    q, k, v = (jnp.asarray(rng.randn(batch, H, T, d).astype(np.float32))
               for _ in range(3))
    kv_len = jnp.asarray(rng.randint(1, T + 1, size=(batch,)).astype(np.int32))
    fn = lambda a, b, c, n: oattn.scaled_dot_product_attention(
        a, b, c, causal=True, kv_len=n)
    config.set_flags(use_flash_attention=True)
    try:
        bare = jax.jit(fn)(q, k, v, kv_len)
        with jax.set_mesh(make_mesh(devices=jax.devices()[:4], **axes)):
            text = str(jax.make_jaxpr(fn)(q, k, v, kv_len))
            sharded = jax.jit(fn)(q, k, v, kv_len)
    finally:
        config.set_flags(use_flash_attention=False)
    assert "shard_map" in text
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(bare), rtol=1e-5, atol=1e-6)


def test_flash_under_partly_manual_mesh_raises(rng):
    """No quiet way out to XLA attention: a mapping the wrapper cannot
    make is an error."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.core import config
    from paddle_tpu.core.compat import shard_map
    from paddle_tpu.ops import attention as oattn
    from paddle_tpu.parallel.mesh import make_mesh

    q = jnp.asarray(rng.randn(4, 4, 32, 8).astype(np.float32))
    mesh = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    inner = lambda a: oattn.scaled_dot_product_attention(a, a, a, causal=True)
    config.set_flags(use_flash_attention=True)
    try:
        with jax.set_mesh(mesh), pytest.raises(NotImplementedError, match="cannot be mapped"):
            jax.jit(shard_map(inner, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                              axis_names={"data"}, check_vma=False))(q)
    finally:
        config.set_flags(use_flash_attention=False)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("streamed", [False, True])
def test_flash_fused_backward_matches_reference(rng, causal, streamed, monkeypatch):
    """Fused Pallas backward (dKV + dQ kernels) vs grads of composed
    attention, on both the VMEM-resident and the streamed-K/V forward."""
    import importlib

    fa_mod = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    if streamed:
        monkeypatch.setattr(fa_mod, "_VMEM_RESIDENT_BYTES", 0)
    B, H, T, d = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=8, block_k=8) * w
        )

    def ref_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(d)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e9)
        p = jax.nn.softmax(s, -1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) * w)

    g = jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v)
    gr = jax.grad(ref_loss, (0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}"
        )


def test_flash_fused_backward_flag_fallback(rng):
    """flash_fused_bwd=False falls back to the recomputed-XLA vjp and
    produces the same gradients."""
    from paddle_tpu.core.config import set_flags

    B, H, T, d = 1, 1, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    def loss(q):
        return jnp.sum(flash_attention(q, q, q, causal=True, block_q=8, block_k=8) ** 2)

    g_fused = jax.grad(loss)(q)
    set_flags(flash_fused_bwd=False)
    try:
        g_recomp = jax.grad(loss)(q)
    finally:
        set_flags(flash_fused_bwd=True)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_recomp), rtol=2e-4, atol=2e-4
    )


def test_flash_attention_bf16(rng):
    """bf16 inputs: fused fwd+bwd run and stay close to the f32 reference."""
    B, H, T, d = 1, 2, 32, 8
    q32 = rng.randn(B, H, T, d).astype(np.float32)
    q = jnp.asarray(q32).astype(jnp.bfloat16)

    out = flash_attention(q, q, q, causal=True, block_q=8, block_k=8)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        _ref_attention(q32, q32, q32, True),
        rtol=5e-2, atol=5e-2,
    )

    def loss(q):
        return jnp.sum(
            flash_attention(q, q, q, causal=True, block_q=8, block_k=8).astype(jnp.float32) ** 2
        )

    g = jax.grad(loss)(q)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


@pytest.mark.parametrize("streamed", [False, True])
def test_flash_attention_kv_len_fwd_bwd(rng, streamed, monkeypatch):
    """Variable-length (suffix-padding) masking via kv_len: forward AND
    fused backward match the additively-masked reference on both the
    VMEM-resident and streamed kernel paths."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    if streamed:
        monkeypatch.setattr(fa, "_VMEM_RESIDENT_BYTES", 0)

    B, H, T, d = 3, 2, 32, 8
    q, k, v = (jnp.asarray(rng.randn(B, H, T, d).astype(np.float32)) for _ in range(3))
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    kv_len = jnp.asarray([32, 17, 5], jnp.int32)

    def ref(q, k, v):
        return fa._reference_attention(q, k, v, False, d ** -0.5, kv_len)

    out = jax.jit(
        lambda a, b, c: fa.flash_attention(a, b, c, block_q=8, block_k=8, kv_len=kv_len)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)), rtol=2e-4, atol=2e-5)

    g = jax.jit(jax.grad(
        lambda a, b, c: jnp.sum(
            fa.flash_attention(a, b, c, block_q=8, block_k=8, kv_len=kv_len) * w
        ), (0, 1, 2),
    ))(q, k, v)
    gr = jax.grad(lambda a, b, c: jnp.sum(ref(a, b, c) * w), (0, 1, 2))(q, k, v)
    for a, b, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("h_kv", [1, 2])
def test_flash_attention_gqa_fwd_bwd(rng, h_kv):
    """GQA through the flash kernels: forward matches the repeated-KV
    reference and the FUSED backward produces group-summed dk/dv at the kv
    head count (kernel index maps route shared kv blocks; the dkv grid's
    innermost dim streams group * q-blocks)."""
    from paddle_tpu.core.config import set_flags
    from paddle_tpu.ops.pallas.flash_attention import (
        _reference_attention,
        flash_attention,
    )

    B, H, T, d = 2, 4, 64, 16
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, h_kv, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, h_kv, T, d).astype(np.float32))

    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = _reference_attention(q, k, v, True, d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    def loss_flash(a, b, c):
        return flash_attention(a, b, c, causal=True, block_q=16, block_k=16).sum()

    def loss_ref(a, b, c):
        return _reference_attention(a, b, c, True, d ** -0.5).sum()

    set_flags(flash_fused_bwd=True)
    try:
        g_f = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    finally:
        set_flags(flash_fused_bwd=True)
    g_r = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    assert g_f[1].shape == (B, h_kv, T, d)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_flash_attention_gqa_with_kvlen(rng):
    """GQA + variable kv_len masking together."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _reference_attention,
        flash_attention,
    )

    B, H, h_kv, T, d = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, h_kv, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, h_kv, T, d).astype(np.float32))
    kv_len = jnp.asarray(np.array([37, 64], np.int32))

    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16, kv_len=kv_len)
    ref = _reference_attention(q, k, v, False, d ** -0.5, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [16, 24, 64])
def test_flash_attention_sliding_window(rng, window):
    """Sliding-window attention (causal, last `window` keys only): flash
    output and fused gradients match the masked reference; out-of-window
    blocks are skip-computed in both directions."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _reference_attention,
        flash_attention,
    )

    B, H, T, d = 2, 2, 64, 16
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    out = flash_attention(q, k, v, causal=True, window=window, block_q=16, block_k=16)
    ref = _reference_attention(q, k, v, True, d ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    g_f = jax.grad(
        lambda a, b, c: flash_attention(a, b, c, causal=True, window=window,
                                        block_q=16, block_k=16).sum(), (0, 1, 2)
    )(q, k, v)
    g_r = jax.grad(
        lambda a, b, c: _reference_attention(a, b, c, True, d ** -0.5,
                                             window=window).sum(), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_flash_sliding_window_requires_causal(rng):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.core.enforce import EnforceError

    q = jnp.zeros((1, 1, 16, 8), jnp.float32)
    with pytest.raises(EnforceError, match="causal"):
        flash_attention(q, q, q, causal=False, window=8)


def test_flash_attention_gqa_with_window(rng):
    """GQA and sliding window together through the fused kernels."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _reference_attention,
        flash_attention,
    )

    B, H, Hkv, T, d, W = 1, 4, 2, 64, 16, 24
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))

    out = flash_attention(q, k, v, causal=True, window=W, block_q=16, block_k=16)
    ref = _reference_attention(q, k, v, True, d ** -0.5, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)

    g_f = jax.grad(
        lambda a, b, c: flash_attention(a, b, c, causal=True, window=W,
                                        block_q=16, block_k=16).sum(), (0, 1, 2)
    )(q, k, v)
    g_r = jax.grad(
        lambda a, b, c: _reference_attention(a, b, c, True, d ** -0.5,
                                             window=W).sum(), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def test_tuned_blocks_resolution(monkeypatch):
    """tuned_blocks: a shape the table lacks takes the rule (the largest
    fitted blocks under the VMEM budget); a row applies per kernel, and only
    to the self-attention shape, head size and itemsize it was measured at."""
    import importlib

    # the package re-exports the flash_attention FUNCTION under the same
    # name, so plain `import ... as fa` resolves to it — load the module
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

    monkeypatch.setattr(fa, "_TUNED_BLOCKS", {})
    assert fa.tuned_blocks(1024, 1024) == fa.rule_blocks(1024, 1024)
    bq, bk = fa.rule_blocks(1024, 1024, d=64, itemsize=2, kernel="dkv")
    assert 1024 % bq == 0 and 1024 % bk == 0 and bq >= 128 and bk >= 128
    assert fa.working_set_bytes("dkv", bq, bk, 64, 2, 1024) <= fa._RULE_VMEM_BYTES
    monkeypatch.setattr(fa, "_TUNED_BLOCKS", {
        (4096, 128, 2): {"fwd": (512, 256), "dkv": (256, 512), "dq": (128, 1024)}})
    assert fa.tuned_blocks(4096, 4096) == (512, 256)
    assert fa.tuned_blocks(4096, 4096, kernel="dkv") == (256, 512)
    assert fa.tuned_blocks(4096, 4096, kernel="dq") == (128, 1024)
    # another kv length, head size or itemsize: the row must NOT apply
    assert fa.tuned_blocks(4096, 1920) == fa.rule_blocks(4096, 1920)
    assert fa.tuned_blocks(4096, 4096, d=64) == fa.rule_blocks(4096, 4096, d=64)
    assert fa.tuned_blocks(4096, 4096, itemsize=4) == fa.rule_blocks(4096, 4096, itemsize=4)
    assert fa.tuned_blocks(1024, 1024) == fa.rule_blocks(1024, 1024)


# ---- the three kernels over their degrees of freedom ------------------------

# name -> (B, H, h_kv, T, d), call keywords, (q_off, k_off), table row or None
_KERNEL_CASES = {
    "blocks_differ": ((2, 2, 2, 64, 16), dict(causal=True, block_q=32, block_k=16), (0, 0), None),
    "per_kernel_blocks": ((2, 2, 2, 64, 16), dict(causal=True), (0, 0),
                          {"fwd": (32, 16), "dkv": (16, 32), "dq": (64, 16)}),
    "full_square": ((1, 2, 2, 64, 16), dict(causal=False, block_q=16, block_k=32), (0, 0), None),
    "gqa4": ((2, 4, 1, 64, 16), dict(causal=True, block_q=16, block_k=16), (0, 0), None),
    "window": ((1, 4, 2, 64, 16), dict(causal=True, window=24, block_q=16, block_k=16), (0, 0), None),
    "kvlen_padded_tail": ((2, 2, 2, 64, 16), dict(causal=False, kv_len=(20, 64), block_q=16, block_k=16),
                          (0, 0), None),
    "kvlen_causal": ((2, 2, 1, 64, 16), dict(causal=True, kv_len=(33, 7), block_q=16, block_k=16),
                     (0, 0), None),
    "ring_offsets": ((1, 2, 2, 32, 16), dict(causal=True, block_q=8, block_k=8), (32, 16), None),
    "ring_offsets_window": ((1, 2, 2, 32, 16), dict(causal=True, window=20, block_q=16, block_k=8),
                            (32, 16), None),
    "t192_fitted": ((1, 2, 2, 192, 16), dict(causal=True, block_q=128, block_k=128), (0, 0), None),
}
# largest error over the largest reference value: float32 operands leave
# rounding of the sums only; bfloat16 rounds the inputs' products' operands
# P and dS to 8 bits (2^-8 = 3.9e-3 each) and the outputs once more
_KERNEL_TOL = {"float32": 2e-5, "bfloat16": 2.5e-2}


def _global_reference(q, k, v, causal, window, kv_len, q_off, k_off):
    """softmax(QK^T / sqrt(d)) V and its row logsumexp with every mask at
    GLOBAL positions, float32 throughout; GQA by repeating kv heads."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    q_pos = q_off + jnp.arange(q.shape[2])[:, None]
    k_pos = k_off + jnp.arange(k.shape[2])[None, :]
    keep = jnp.ones(s.shape[-2:], bool)
    if causal:
        keep = q_pos >= k_pos
        if window is not None:
            keep = keep & (q_pos - k_pos < window)
    keep = jnp.broadcast_to(keep, s.shape)
    if kv_len is not None:
        keep = keep & (k_pos[None, None] < kv_len[:, None, None, None])
    s = jnp.where(keep, s, -1e9)
    lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse), v), lse


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["resident", "streamed"])
@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_flash_kernels_against_reference(rng, monkeypatch, case, form, dtype):
    """Forward (out, lse) and the fused backward (dq, dk, dv) of the block
    API ring attention calls, against the float32 reference with global
    masks: resident and streamed forms on the same inputs (the bound is
    forced), block_q != block_k, per-kernel blocks from the table, both
    operand dtypes, GQA, window, kv_len with a fully padded tail block,
    non-zero offsets handed over TRACED as ring attention does, T 192."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    (B, H, h_kv, T, d), kw, (q_off, k_off), row = _KERNEL_CASES[case]
    kw = dict(kw)
    kv_len = kw.pop("kv_len", None)
    kv_len = None if kv_len is None else jnp.asarray(kv_len, jnp.int32)
    if form == "streamed":
        monkeypatch.setattr(fa, "_VMEM_RESIDENT_BYTES", 0)
    if row is not None:
        monkeypatch.setattr(fa, "_TUNED_BLOCKS", {(T, d, jnp.dtype(dtype).itemsize): row})
    q, g = (jnp.asarray(rng.randn(B, H, T, d), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(B, h_kv, T, d), dtype) for _ in range(2))

    @jax.jit
    def run(q, k, v, g, q_off, k_off):
        out, lse = fa.flash_attention_with_lse(
            q, k, v, kv_len=kv_len, q_off=q_off, k_off=k_off, interpret=True, **kw)
        return (out, lse) + fa.flash_attention_bwd_block(
            q, k, v, out, lse, g, kv_len=kv_len, q_off=q_off, k_off=k_off,
            interpret=True, **kw)

    fa.take_resolved()  # what earlier tests of this process traced
    got = run(q, k, v, g, jnp.int32(q_off), jnp.int32(k_off))
    resolved = fa.take_resolved()
    suffix = "_resident" if form == "resident" else ""
    assert set(resolved) == {f"flash_fwd{suffix}", f"flash_bwd_dkv{suffix}", f"flash_bwd_dq{suffix}"}
    if row is not None:
        assert resolved[f"flash_bwd_dkv{suffix}"] == f"16x32 table {form}", resolved

    ref_fn = lambda a, b, c: _global_reference(
        a, b, c, kw["causal"], kw.get("window"), kv_len, q_off, k_off)
    (ref_out, ref_lse), vjp = jax.vjp(ref_fn, q, k, v)
    ref = (ref_out, ref_lse) + vjp((g.astype(jnp.float32), jnp.zeros_like(ref_lse)))
    assert got[0].dtype == q.dtype and got[2].dtype == q.dtype
    assert got[3].dtype == k.dtype and got[3].shape == k.shape
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= _KERNEL_TOL[dtype] * np.abs(b).max(), (
            name, np.abs(a - b).max(), np.abs(b).max())
