"""Pipeline / MoE / ring-attention tests on the virtual 8-device CPU mesh
(the analogue of the reference's fake-device op-handle tests,
``details/broadcast_op_handle_test.cc`` — multi-device semantics without a
cluster)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ops.ring_attention import ring_attention_sharded
from paddle_tpu.parallel import (
    make_mesh,
    moe_ffn,
    pipeline_apply,
    split_microbatches,
    stack_stage_params,
    switch_gate,
)


# ------------------------------------------------------------------ pipeline
def test_pipeline_matches_sequential(rng):
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    mesh = make_mesh(pipe=n_stages, data=2)

    stage_params = [
        {
            "w": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.3),
            "b": jnp.asarray(rng.randn(d).astype(np.float32) * 0.1),
        }
        for _ in range(n_stages)
    ]

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    x = jnp.asarray(rng.randn(n_micro * mb, d).astype(np.float32))
    mbs = split_microbatches(x, n_micro)
    stacked = stack_stage_params(stage_params)

    out = jax.jit(
        lambda sp, m: pipeline_apply(stage_fn, sp, m, mesh)
    )(stacked, mbs)
    assert out.shape == (n_micro, mb, d)

    ref = x
    for p in stage_params:
        ref = jnp.tanh(ref @ p["w"] + p["b"])
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, d), np.asarray(ref), rtol=2e-5, atol=2e-6
    )


def test_pipeline_is_differentiable(rng):
    n_stages, n_micro, mb, d = 2, 4, 4, 8
    mesh = make_mesh(pipe=n_stages, data=4)
    stage_params = [
        {"w": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.3)}
        for _ in range(n_stages)
    ]
    stacked = stack_stage_params(stage_params)
    x = jnp.asarray(rng.randn(n_micro * mb, d).astype(np.float32))
    mbs = split_microbatches(x, n_micro)

    def loss(sp):
        out = pipeline_apply(lambda p, h: jnp.tanh(h @ p["w"]), sp, mbs, mesh)
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss))(stacked)
    g_np = np.asarray(g["w"])
    assert g_np.shape == (n_stages, d, d)
    assert np.all(np.isfinite(g_np))
    assert np.abs(g_np).max() > 0

    # grads match the unpipelined computation
    def ref_loss(sp):
        h = x
        for i in range(n_stages):
            h = jnp.tanh(h @ sp["w"][i])
        return jnp.sum(h ** 2)

    g_ref = jax.grad(ref_loss)(stacked)
    np.testing.assert_allclose(g_np, np.asarray(g_ref["w"]), rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------- moe
def test_switch_gate_respects_capacity():
    # 4 tokens all prefer expert 0; capacity 2 -> 2 dropped
    logits = jnp.asarray(np.array([[5.0, 0.0]] * 4, np.float32))
    dispatch, combine, aux = switch_gate(logits, capacity=2)
    assert dispatch.shape == (4, 2, 2)
    kept = np.asarray(dispatch).sum()
    assert kept == 2
    # positions are distinct within the expert buffer
    occupancy = np.asarray(dispatch).sum(axis=(0, 1))
    assert list(occupancy) == [1, 1]
    assert float(aux) > 0


def test_moe_identical_experts_equal_dense(rng):
    """With identical expert weights and ample capacity, MoE equals the plain
    FFN scaled by the router's top-1 probability (Switch semantics)."""
    B, T, D, F, E = 2, 4, 8, 16, 4
    mesh = make_mesh(expert=4, data=2)

    def net(x):
        out = moe_ffn(x, num_experts=E, d_ff=F, capacity_factor=8.0)
        return out.output, out.aux_loss

    model = pt.build(net)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    variables = model.init(0, x)

    # overwrite experts with copies of expert 0
    params = dict(variables.params)
    for nm in ("w_in", "b_in", "w_out", "b_out"):
        full = f"moe/{nm}"
        p = np.array(params[full])  # writable copy
        p[:] = p[0:1]
        params[full] = jnp.asarray(p)

    (out, aux), _ = model.apply((params, variables.state), x)
    h = np.maximum(np.asarray(x) @ np.asarray(params["moe/w_in"][0]) + np.asarray(params["moe/b_in"][0]), 0)
    ffn = h @ np.asarray(params["moe/w_out"][0]) + np.asarray(params["moe/b_out"][0])
    # Switch scales by the chosen expert's router probability
    logits = np.asarray(x).reshape(-1, D) @ np.asarray(params["moe/w_gate"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    gate = probs.max(-1).reshape(B, T, 1)
    np.testing.assert_allclose(np.asarray(out), gate * ffn, rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_trains_under_mesh(rng):
    B, T, D, F, E = 4, 4, 8, 16, 4
    mesh = make_mesh(expert=E, data=8 // E)

    def net(x, y):
        out = moe_ffn(x, num_experts=E, d_ff=F)
        pred = jnp.mean(out.output, axis=(1, 2))
        return jnp.mean((pred - y) ** 2) + 0.01 * out.aux_loss

    model = pt.build(net)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    y = jnp.asarray(rng.randn(B).astype(np.float32))
    opt = pt.optimizer.Adam(learning_rate=0.01)

    from paddle_tpu.parallel import DataParallel

    dp = DataParallel(model, opt, mesh=mesh, donate=False)
    variables, opt_state = dp.init(0, x, y)
    # expert params sharded over the expert axis
    w_in_sharding = variables.params["moe/w_in"].sharding
    assert "expert" in str(w_in_sharding.spec)
    dev_batch = dp.put_batch(x, y)
    losses = []
    for _ in range(5):
        out = dp.step(variables, opt_state, *dev_batch)
        variables, opt_state = out.variables, out.opt_state
        losses.append(float(out.loss))
    assert losses[-1] < losses[0]


# -------------------------------------------------------------- ring attention
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(rng, causal):
    B, H, T, d = 2, 3, 16, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    out = jax.jit(
        lambda a, b, c: ring_attention_sharded(a, b, c, mesh, causal=causal)
    )(q, k, v)

    scores = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        scores = np.where(mask, scores, -1e9)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", w, np.asarray(v))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_finite(rng):
    B, H, T, d = 1, 2, 8, 4
    mesh = make_mesh(seq=8)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    def loss(q):
        return jnp.sum(ring_attention_sharded(q, q, q, mesh, causal=True) ** 2)

    g = jax.jit(jax.grad(loss))(q)
    assert np.all(np.isfinite(np.asarray(g)))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_matches_composed(rng, causal):
    """The flash-kernel ring body (per-block Pallas + lse merge) agrees with
    the composed-einsum ring, forward and backward."""
    B, H, T, d = 1, 2, 32, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    out_flash = jax.jit(
        lambda a, b, c: ring_attention_sharded(a, b, c, mesh, causal=causal, use_flash=True)
    )(q, k, v)
    out_comp = jax.jit(
        lambda a, b, c: ring_attention_sharded(a, b, c, mesh, causal=causal, use_flash=False)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_comp), rtol=2e-4, atol=2e-5
    )

    def loss(use_flash):
        def f(a, b, c):
            return jnp.sum(
                ring_attention_sharded(a, b, c, mesh, causal=causal, use_flash=use_flash) * w
            )
        return jax.jit(jax.grad(f, (0, 1, 2)))(q, k, v)

    g_flash = loss(True)
    g_comp = loss(False)
    for a, b, name in zip(g_flash, g_comp, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}"
        )


def test_ring_attention_flash_bf16_grads(rng):
    """bf16 q/k/v through the fused-backward ring: grads stay close to the
    f32 composed ring (carriers accumulate in f32)."""
    B, H, T, d = 1, 2, 32, 8
    mesh = make_mesh(seq=4, data=2)
    q32 = rng.randn(B, H, T, d).astype(np.float32)
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    q16 = jnp.asarray(q32).astype(jnp.bfloat16)

    def loss16(q):
        o = ring_attention_sharded(q, q, q, mesh, causal=True, use_flash=True)
        return jnp.sum(o.astype(jnp.float32) * w)

    def loss32(q):
        o = ring_attention_sharded(q, q, q, mesh, causal=True, use_flash=False)
        return jnp.sum(o * w)

    g16 = jax.jit(jax.grad(loss16))(q16)
    g32 = jax.grad(loss32)(jnp.asarray(q32))
    assert g16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(g16, np.float32), np.asarray(g32), rtol=6e-2, atol=6e-2
    )


def test_transformer_lm_ring_mesh_matches_plain(rng):
    """transformer_lm with ring_mesh (sequence-parallel ring attention)
    computes the same loss as the plain LM with identical params."""
    from paddle_tpu import models

    mesh = make_mesh(seq=4, data=2)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=2, n_layers=1)
    plain = models.get_model("transformer_lm", **kw)
    ringm = models.get_model("transformer_lm", ring_mesh=mesh, **kw)

    batch = plain.synth_batch(8, rng)
    variables = plain.model.init(0, *batch)
    (l_plain, _, _), _ = plain.model.apply(variables, *batch, is_train=False)
    (l_ring, _, _), _ = ringm.model.apply(variables, *batch, is_train=False)
    np.testing.assert_allclose(float(l_plain), float(l_ring), rtol=1e-4)

    # and it trains end-to-end under jit
    opt = ringm.optimizer()
    opt_state = opt.create_state(variables.params)
    step = jax.jit(opt.minimize(ringm.model))
    out = step(variables, opt_state, *batch, rng=jax.random.PRNGKey(0))
    assert np.isfinite(float(out.loss))


def test_top2_gate_pair_dispatch():
    """Each token reaches its two top experts with renormalized gates."""
    from paddle_tpu.parallel.moe import top2_gate

    logits = jnp.asarray(np.array(
        [[3.0, 2.0, -5.0], [0.0, 1.0, 2.0]], np.float32))
    dispatch, combine, aux = top2_gate(logits, capacity=4)
    d = np.asarray(dispatch)
    # token 0 -> experts 0,1; token 1 -> experts 2,1
    assert d[0, 0].any() and d[0, 1].any() and not d[0, 2].any()
    assert d[1, 2].any() and d[1, 1].any() and not d[1, 0].any()
    c = np.asarray(combine).sum(axis=(1, 2))
    np.testing.assert_allclose(c, [1.0, 1.0], rtol=1e-5)  # gates renormalized
    assert float(aux) > 0


def test_top2_gate_drops_second_choices_first():
    """Overflow: first choices occupy the buffer before any second choice."""
    from paddle_tpu.parallel.moe import top2_gate

    # all 4 tokens: first choice expert 0, second choice expert 1
    logits = jnp.asarray(np.array([[5.0, 4.0]] * 4, np.float32))
    dispatch, combine, aux = top2_gate(logits, capacity=4)
    d = np.asarray(dispatch)
    # expert 0 holds all 4 first choices; expert 1 all 4 second choices
    assert d[:, 0].sum() == 4 and d[:, 1].sum() == 4
    dispatch2, _, _ = top2_gate(logits, capacity=2)
    d2 = np.asarray(dispatch2)
    assert d2[:, 0].sum() == 2  # first choices kept up to capacity
    assert d2[:, 1].sum() == 2


def test_moe_top2_identical_experts_equal_dense(rng):
    """With identical experts and ample capacity, top-2 MoE equals the plain
    FFN exactly (pair gates renormalize to 1)."""
    B, T, D, F, E = 2, 4, 8, 16, 4

    def net(x):
        out = moe_ffn(x, num_experts=E, d_ff=F, capacity_factor=8.0, router="top2")
        return out.output, out.aux_loss

    model = pt.build(net)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    variables = model.init(0, x)
    params = dict(variables.params)
    for nm in ("w_in", "b_in", "w_out", "b_out"):
        full = f"moe/{nm}"
        p = np.array(params[full])
        p[:] = p[0:1]
        params[full] = jnp.asarray(p)
    (out, aux), _ = model.apply((params, variables.state), x)
    h = np.maximum(np.asarray(x) @ np.asarray(params["moe/w_in"][0]) + np.asarray(params["moe/b_in"][0]), 0)
    ffn = h @ np.asarray(params["moe/w_out"][0]) + np.asarray(params["moe/b_out"][0])
    # gates renormalize over the pair -> exactly the dense FFN
    np.testing.assert_allclose(np.asarray(out), ffn, rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_dataparallel_enforces_input_shardings(rng):
    """VERDICT r2 item 4: a raw host-numpy batch (no put_batch) must be fed
    SHARDED on the data axis — not silently replicated — and the compiled
    step must contain the gradient all-reduce (the XLA form of
    AllReduceOpHandle, ``details/all_reduce_op_handle.cc:48``)."""
    from paddle_tpu import models
    from paddle_tpu.parallel.data_parallel import DataParallel

    spec = models.get_model("mnist")
    dp = DataParallel(spec.model, spec.optimizer(), mesh=make_mesh(data=-1))
    batch = spec.synth_batch(16, rng)
    variables, opt_state = dp.init(0, *batch)

    out = dp.step(variables, opt_state, *batch, rng=jax.random.PRNGKey(0))
    assert np.isfinite(float(out.loss))

    lowered = dp._step_fn.lower(
        variables, opt_state, jax.random.PRNGKey(0), *batch
    ).compile()
    flat_in = lowered.input_shardings[0]
    # the last two inputs are (images, labels): both sharded on 'data'
    for s in flat_in[-2:]:
        assert "data" in str(s.spec), f"batch input not data-sharded: {s}"
    assert "all-reduce" in lowered.as_text()

    # rng=None replicated-path still compiles and runs
    out2 = dp.step(out.variables, out.opt_state, *batch, rng=None)
    assert np.isfinite(float(out2.loss))


def test_dp8_vs_dp1_loss_trajectory(rng):
    """VERDICT r2 item 9 / reference ``parallel_executor_test_base.py``: the
    same model trained dp=8 vs dp=1 must follow the same loss trajectory
    over >= 10 steps (mean-grad psum == AllReduce+ScaleLossGrad)."""
    from paddle_tpu import models
    from paddle_tpu.parallel.data_parallel import DataParallel

    spec = models.get_model("mnist")
    batch = spec.synth_batch(16, rng)

    v = spec.model.init(0, *batch)
    opt = spec.optimizer()
    step = jax.jit(opt.minimize(spec.model))
    v1, o1 = v, opt.create_state(v.params)
    base = []
    for i in range(12):
        out = step(v1, o1, *[jnp.asarray(b) for b in batch], rng=jax.random.PRNGKey(i))
        v1, o1 = out.variables, out.opt_state
        base.append(float(out.loss))

    dp = DataParallel(spec.model, spec.optimizer(), mesh=make_mesh(data=-1))
    v8, o8 = dp.init(0, *batch, variables=v)
    dp8 = []
    for i in range(12):
        out = dp.step(v8, o8, *batch, rng=jax.random.PRNGKey(i))
        v8, o8 = out.variables, out.opt_state
        dp8.append(float(out.loss))

    assert base[-1] < base[0]  # training is actually moving
    np.testing.assert_allclose(base, dp8, rtol=5e-4, atol=1e-5)


def _mnist_single_device_step(rng, rows):
    """(spec, batch, initial variables, one single-device step's StepOutput)."""
    from paddle_tpu import models

    spec = models.get_model("mnist")
    batch = spec.synth_batch(rows, rng)
    v = spec.model.init(0, *batch)
    opt = spec.optimizer()
    base = jax.jit(opt.minimize(spec.model))(
        v, opt.create_state(v.params), *[jnp.asarray(b) for b in batch],
        rng=jax.random.PRNGKey(0))
    return spec, batch, v, base


def test_dp_step_leaves_outputs_sharded_over_the_batch(rng):
    """A step moves the gradients across chips and nothing else: the
    model's outputs stay on the chips that computed them, one global array
    sharded over the batch axis (as ``eval_step``'s are), equal to the
    single-device step's; ``loss`` / ``finite`` come back replicated."""
    from paddle_tpu.core.config import flags, set_flags
    from paddle_tpu.parallel.data_parallel import DataParallel
    from paddle_tpu.parallel.sharding import replicated

    prev = flags().check_nan_inf
    set_flags(check_nan_inf=True)  # so that the step computes `finite`
    try:
        spec, batch, v, base = _mnist_single_device_step(rng, 16)
        dp = DataParallel(spec.model, spec.optimizer(), mesh=make_mesh(data=-1), donate=False)
        v8, o8 = dp.init(0, *batch, variables=v)
        out = dp.step(v8, o8, *batch, rng=jax.random.PRNGKey(0))
    finally:
        set_flags(check_nan_inf=prev)

    leaves = jax.tree_util.tree_leaves(out.outputs)
    base_leaves = jax.tree_util.tree_leaves(base.outputs)
    assert leaves and len(leaves) == len(base_leaves)
    batched = [a for a in leaves if a.ndim and a.shape[0] == 16]
    assert batched  # the logits among them
    for a in batched:
        assert not a.sharding.is_fully_replicated
        assert a.sharding.spec[0] == "data"
        assert {sh.data.shape[0] for sh in a.addressable_shards} == {2}  # 16 rows, 8 chips
    for a, b in zip(leaves, base_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5)
    assert out.loss.sharding.is_fully_replicated
    assert out.finite is not None and out.finite.sharding.is_fully_replicated
    assert bool(out.finite)
    # a caller that wants every chip to hold the whole array asks at the read
    whole = jax.device_put(batched[0], replicated(dp.mesh))
    assert whole.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(batched[0]))
    # eval_step's outputs lie the same way
    ev = [a for a in jax.tree_util.tree_leaves(dp.eval_step(v8, *batch))
          if a.ndim and a.shape[0] == 16]
    assert ev and all(not a.sharding.is_fully_replicated for a in ev)


def test_dp_second_step_accepts_the_first_steps_state(rng):
    """Why the state's out_shardings stay pinned: with an expert-sharded
    parameter the compiler, left alone, may hand back an updated parameter
    in another sharding, and the next step's declared in_shardings would
    reject it. The state's shardings before and after a step are equal, a
    second step takes the first's state as it is, and the outputs (left to
    the compiler) are not gathered."""
    from paddle_tpu.parallel import DataParallel

    B, T, D, F, E = 4, 4, 8, 16, 4
    mesh = make_mesh(expert=E, data=8 // E)

    def net(x, y):
        out = moe_ffn(x, num_experts=E, d_ff=F)
        pred = jnp.mean(out.output, axis=(1, 2))
        return jnp.mean((pred - y) ** 2) + 0.01 * out.aux_loss, out.output

    model = pt.build(net)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    y = jnp.asarray(rng.randn(B).astype(np.float32))
    dp = DataParallel(model, pt.optimizer.Adam(learning_rate=0.01), mesh=mesh)
    variables, opt_state = dp.init(0, x, y)
    assert "expert" in str(variables.params["moe/w_in"].sharding.spec)
    before = jax.tree_util.tree_map(lambda a: a.sharding, (variables, opt_state))
    dev_batch = dp.put_batch(x, y)
    out1 = dp.step(variables, opt_state, *dev_batch)
    same = jax.tree_util.tree_map(
        lambda s, a: s.is_equivalent_to(a.sharding, a.ndim), before,
        (out1.variables, out1.opt_state))
    assert all(jax.tree_util.tree_leaves(same))
    out2 = dp.step(out1.variables, out1.opt_state, *dev_batch)  # donated, unchanged
    assert np.isfinite(float(out2.loss)) and float(out2.loss) != float(out1.loss)
    assert dp._step_fn._cache_size() == 1  # the same compiled step took it
    (moe_out,) = [a for a in jax.tree_util.tree_leaves(out2.outputs) if a.shape == (B, T, D)]
    assert not moe_out.sharding.is_fully_replicated
    assert out2.loss.sharding.is_fully_replicated


def test_dp_step_ragged_outputs_as_before(rng):
    """The ragged tail is fed replicated, so its outputs are computed
    replicated: every chip holds the whole (small) array, equal to the
    single-device step's, and the state keeps its mesh shardings."""
    from paddle_tpu.parallel.data_parallel import DataParallel

    spec, batch, v, base = _mnist_single_device_step(rng, 5)  # 5 rows do not divide 8 chips
    dp = DataParallel(spec.model, spec.optimizer(), mesh=make_mesh(data=-1))
    v8, o8 = dp.init(0, *batch, variables=v)
    assert not dp.batch_divisible(*batch)
    out = dp.step_ragged(v8, o8, *batch, rng=jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(out.outputs)
    assert any(a.ndim and a.shape[0] == 5 for a in leaves)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(base.outputs)):
        assert a.sharding.is_fully_replicated
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(float(out.loss), float(base.loss), rtol=5e-4)
    for name, p in out.variables.params.items():
        assert p.sharding.is_equivalent_to(v8.params[name].sharding, p.ndim)


# ----------------------------------------------------------------- ulysses
@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(rng, causal):
    """All-to-all sequence parallelism: output must equal full attention
    (same contract as ring attention, different collective pattern)."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention
    from paddle_tpu.ops.ulysses import ulysses_attention_sharded

    B, H, T, d = 2, 4, 16, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    ref = _reference_attention(q, k, v, causal, d ** -0.5)
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal, use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_ulysses_attention_grads_match(rng):
    """Gradients flow through the two all_to_alls and match full attention."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention
    from paddle_tpu.ops.ulysses import ulysses_attention_sharded

    B, H, T, d = 1, 4, 16, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    g_ref = jax.grad(lambda a, b, c: _reference_attention(a, b, c, True, d ** -0.5).sum(), (0, 1, 2))(q, k, v)
    g_uly = jax.grad(
        lambda a, b, c: ulysses_attention_sharded(a, b, c, mesh, causal=True, use_flash=False).sum(),
        (0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5)


def test_ulysses_rejects_indivisible_heads(rng):
    from paddle_tpu.ops.ulysses import ulysses_attention_sharded
    from paddle_tpu.core.enforce import EnforceError

    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(2, 3, 16, 8).astype(np.float32))  # 3 heads, 4-way seq
    with pytest.raises(Exception):
        jax.block_until_ready(
            ulysses_attention_sharded(q, q, q, mesh, causal=False, use_flash=False)
        )


def test_transformer_lm_ulysses_mesh_matches_plain(rng):
    """transformer_lm with ulysses_mesh (all-to-all sequence parallelism)
    computes the same loss as the plain LM with identical params, and
    trains end-to-end under jit — the a2a twin of the ring-LM test."""
    from paddle_tpu import models

    mesh = make_mesh(seq=2, data=4)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=2, n_layers=1)
    plain = models.get_model("transformer_lm", **kw)
    ulym = models.get_model("transformer_lm", ulysses_mesh=mesh, **kw)

    batch = plain.synth_batch(8, rng)
    variables = plain.model.init(0, *batch)
    (l_plain, _, _), _ = plain.model.apply(variables, *batch, is_train=False)
    (l_uly, _, _), _ = ulym.model.apply(variables, *batch, is_train=False)
    np.testing.assert_allclose(float(l_plain), float(l_uly), rtol=1e-4)

    opt = ulym.optimizer()
    opt_state = opt.create_state(variables.params)
    step = jax.jit(opt.minimize(ulym.model))
    out = step(variables, opt_state, *batch, rng=jax.random.PRNGKey(0))
    assert np.isfinite(float(out.loss))


def test_zero1_optimizer_state_sharding(rng):
    """zero_shard_optimizer: Adam slot buffers live data-sharded (1/N HBM
    per device) and the loss trajectory matches the replicated-state run
    exactly — XLA materializes the reduce-scatter/all-gather pattern from
    the declared shardings (the reference's Reduce+Broadcast strategy,
    multi_devices_graph_pass.cc:397-446, done by the partitioner)."""
    from paddle_tpu import models
    from paddle_tpu.parallel.data_parallel import DataParallel

    spec = models.get_model(
        "transformer_lm", seq_len=16, vocab=64, d_model=32, d_inner=64,
        num_heads=2, n_layers=1, max_len=16,
    )
    batch = spec.synth_batch(16, rng)
    v0 = spec.model.init(0, *batch)

    def run(zero):
        dp = DataParallel(
            spec.model, pt.optimizer.Adam(learning_rate=1e-3),
            mesh=make_mesh(data=-1), zero_shard_optimizer=zero,
        )
        # fresh buffers: the donated step would otherwise delete v0's arrays
        v_copy = jax.tree_util.tree_map(jnp.array, v0)
        v, o = dp.init(0, *batch, variables=v_copy)
        if zero:
            # a large replicated param's moment buffer must be data-sharded
            name, slot = max(
                ((k, s) for s, d in o.slots.items() for k, s in d.items()),
                key=lambda kv: kv[1].size,
            )
            assert "data" in str(slot.sharding.spec), (name, slot.sharding)
        losses = []
        for i in range(6):
            out = dp.step(v, o, *batch, rng=jax.random.PRNGKey(i))
            v, o = out.variables, out.opt_state
            losses.append(float(out.loss))
        return losses

    base = run(zero=False)
    zero = run(zero=True)
    np.testing.assert_allclose(base, zero, rtol=2e-5, atol=1e-6)


def test_ring_attention_gqa_matches_full(rng):
    """GQA K/V rotate the ring at H_kv heads (less ICI traffic) and the
    result equals full-sequence GQA attention, fwd and bwd."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention

    B, H, Hkv, T, d = 1, 4, 2, 32, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))

    ref = _reference_attention(q, k, v, True, d ** -0.5)
    out = ring_attention_sharded(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-5)

    g = jax.grad(
        lambda a, b, c: jnp.sum(ring_attention_sharded(a, b, c, mesh, causal=True) ** 2),
        (0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: jnp.sum(_reference_attention(a, b, c, True, d ** -0.5) ** 2),
        (0, 1, 2),
    )(q, k, v)
    assert g[1].shape == (B, Hkv, T, d)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_transformer_lm_ring_gqa_trains(rng):
    """ring_mesh + num_kv_heads together: train step runs and loss matches
    the plain GQA LM with identical params."""
    from paddle_tpu import models

    mesh = make_mesh(seq=4, data=2)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=4,
              num_kv_heads=2, n_layers=1)
    plain = models.get_model("transformer_lm", **kw)
    ringm = models.get_model("transformer_lm", ring_mesh=mesh, **kw)
    batch = plain.synth_batch(8, rng)
    variables = plain.model.init(0, *batch)
    (l_plain, _, _), _ = plain.model.apply(variables, *batch, is_train=False)
    (l_ring, _, _), _ = ringm.model.apply(variables, *batch, is_train=False)
    np.testing.assert_allclose(float(l_plain), float(l_ring), rtol=1e-4)


def test_transformer_lm_rope_ring_matches_plain(rng):
    """RoPE composes with ring attention (rotation applied on the global
    arrays before sharding): loss equals the plain rope LM."""
    from paddle_tpu import models

    mesh = make_mesh(seq=4, data=2)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=2,
              n_layers=1, pos_encoding="rope")
    plain = models.get_model("transformer_lm", **kw)
    ringm = models.get_model("transformer_lm", ring_mesh=mesh, **kw)
    batch = plain.synth_batch(8, rng)
    v = plain.model.init(0, *batch)
    (l1, *_), _ = plain.model.apply(v, *batch, is_train=False)
    (l2, *_), _ = ringm.model.apply(v, *batch, is_train=False)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)


def test_ring_attention_window_matches_full(rng):
    """window x ring: the composed ring body applies the sliding-window band
    over GLOBAL positions; matches full windowed attention fwd + bwd."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention

    B, H, T, d, W = 1, 2, 32, 8, 12
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    ref = _reference_attention(q, k, v, True, d ** -0.5, window=W)
    out = ring_attention_sharded(q, k, v, mesh, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-5)

    g = jax.grad(lambda a: jnp.sum(ring_attention_sharded(a, k, v, mesh, causal=True, window=W) ** 2))(q)
    g_ref = jax.grad(lambda a: jnp.sum(_reference_attention(a, k, v, True, d ** -0.5, window=W) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-3, atol=1e-4)


def test_transformer_lm_window_seq_parallel_matches_plain(rng):
    """attention_window composes with both ring and ulysses sequence
    parallelism — loss equals the plain windowed LM."""
    from paddle_tpu import models

    mesh = make_mesh(seq=2, data=4)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=2,
              n_layers=1, attention_window=8)
    plain = models.get_model("transformer_lm", **kw)
    batch = plain.synth_batch(8, rng)
    v = plain.model.init(0, *batch)
    (l1, *_), _ = plain.model.apply(v, *batch, is_train=False)
    for m in (models.get_model("transformer_lm", ring_mesh=mesh, **kw),
              models.get_model("transformer_lm", ulysses_mesh=mesh, **kw)):
        (l2, *_), _ = m.model.apply(v, *batch, is_train=False)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)


def test_ring_attention_flash_gqa_matches_composed(rng):
    """GQA through the FLASH ring body (kernel kv-index maps + grouped
    fused block backward + H_kv gradient carriers) agrees with the composed
    ring, forward and backward."""
    B, H, Hkv, T, d = 1, 4, 2, 32, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))

    out_f = ring_attention_sharded(q, k, v, mesh, causal=True, use_flash=True)
    out_c = ring_attention_sharded(q, k, v, mesh, causal=True, use_flash=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_c), rtol=3e-4, atol=3e-5)

    def loss(fn_flash):
        return lambda a, b, c: jnp.sum(
            ring_attention_sharded(a, b, c, mesh, causal=True, use_flash=fn_flash) ** 2
        )

    g_f = jax.grad(loss(True), (0, 1, 2))(q, k, v)
    g_c = jax.grad(loss(False), (0, 1, 2))(q, k, v)
    assert g_f[1].shape == (B, Hkv, T, d)
    for a, b in zip(g_f, g_c):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


# ------------------------------------------------- r4: kv_len / window x flash
def test_ring_attention_flash_window_matches_composed(rng):
    """window x ring through the FLASH path (global-position offsets in the
    fused kernels): fwd + fused bwd match the composed windowed ring, so the
    O(T*W) skip no longer forfeits the flash kernels (VERDICT r3 missing #4)."""
    B, H, T, d, W = 1, 2, 64, 8, 24
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    out_f = jax.jit(lambda a, b, c: ring_attention_sharded(
        a, b, c, mesh, causal=True, window=W, use_flash=True))(q, k, v)
    out_c = jax.jit(lambda a, b, c: ring_attention_sharded(
        a, b, c, mesh, causal=True, window=W, use_flash=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_c),
                               rtol=2e-4, atol=2e-5)

    def grads(use_flash):
        f = lambda a, b, c: jnp.sum(ring_attention_sharded(
            a, b, c, mesh, causal=True, window=W, use_flash=use_flash) * w)
        return jax.jit(jax.grad(f, (0, 1, 2)))(q, k, v)

    for a, b, name in zip(grads(True), grads(False), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_ring_attention_kv_len_matches_full(rng):
    """kv_len x ring (ragged batches under sequence parallelism — the LoD
    replacement, VERDICT r3 missing #3): flash ring with global kv_len
    bounds matches full attention on all VALID rows, fwd + fused bwd (the
    cotangent is zeroed at pad positions, as a masked loss produces)."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention

    B, H, T, d = 2, 2, 64, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    kvl = jnp.asarray([50, 23], jnp.int32)
    valid = (jnp.arange(T)[None, :] < kvl[:, None])[:, None, :, None]
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32)) * valid

    ref = _reference_attention(q, k, v, True, d ** -0.5, kv_len=kvl)
    for use_flash in (True, False):
        out = jax.jit(lambda a, b, c: ring_attention_sharded(
            a, b, c, mesh, causal=True, kv_len=kvl, use_flash=use_flash))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, out, 0.0)),
            np.asarray(jnp.where(valid, ref, 0.0)),
            rtol=2e-4, atol=2e-5, err_msg=f"use_flash={use_flash}",
        )

    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        _reference_attention(a, b, c, True, d ** -0.5, kv_len=kvl) * w),
        (0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(lambda a, b, c: jnp.sum(ring_attention_sharded(
        a, b, c, mesh, causal=True, kv_len=kvl, use_flash=True) * w),
        (0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_ulysses_kv_len_matches_full(rng):
    """kv_len x ulysses: global lengths apply directly after the first
    all_to_all; valid rows match full attention, fwd + bwd."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention
    from paddle_tpu.ops.ulysses import ulysses_attention_sharded

    B, H, T, d = 2, 4, 64, 8
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    kvl = jnp.asarray([60, 17], jnp.int32)
    valid = (jnp.arange(T)[None, :] < kvl[:, None])[:, None, :, None]
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32)) * valid

    ref = _reference_attention(q, k, v, True, d ** -0.5, kv_len=kvl)
    for use_flash in (True, False):
        out = jax.jit(lambda a, b, c: ulysses_attention_sharded(
            a, b, c, mesh, causal=True, kv_len=kvl, use_flash=use_flash))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(jnp.where(valid, out, 0.0)),
            np.asarray(jnp.where(valid, ref, 0.0)),
            rtol=2e-4, atol=2e-5, err_msg=f"use_flash={use_flash}",
        )

    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        _reference_attention(a, b, c, True, d ** -0.5, kv_len=kvl) * w),
        (0, 1, 2))(q, k, v)
    g_uly = jax.jit(jax.grad(lambda a, b, c: jnp.sum(ulysses_attention_sharded(
        a, b, c, mesh, causal=True, kv_len=kvl, use_flash=True) * w),
        (0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_uly, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_ulysses_pads_to_flash_block(rng):
    """T % 128 != 0 with T > 128 no longer silently materializes [T, T]:
    the wrapper pads to the next 128 multiple, masks padded keys via
    kv_len, and slices the padded query rows off (VERDICT r3 weak #3)."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention
    from paddle_tpu.ops.ulysses import ulysses_attention_sharded

    B, H, T, d = 1, 4, 160, 8  # gathered T=160 -> pads to 256
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))

    ref = _reference_attention(q, k, v, True, d ** -0.5)
    out = jax.jit(lambda a, b, c: ulysses_attention_sharded(
        a, b, c, mesh, causal=True, use_flash=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_transformer_lm_ragged_seq_parallel_matches_plain(rng):
    """Ragged batches (seq_lens / the LoD replacement) compose with ring AND
    ulysses sequence parallelism: masked loss equals the plain LM's, and the
    train step runs under jit (closes VERDICT r3 missing #3 at the LM level)."""
    from paddle_tpu import models

    mesh = make_mesh(seq=4, data=2)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=4, n_layers=1)
    plain = models.get_model("transformer_lm", **kw)

    rng_np = np.random.RandomState(3)
    ids, labels = plain.synth_batch(8, rng_np)
    seq_lens = rng_np.randint(4, 33, size=(8,)).astype(np.int32)
    variables = plain.model.init(0, ids, labels, seq_lens)
    (l_plain, n_tok, _), _ = plain.model.apply(
        variables, ids, labels, seq_lens, is_train=False
    )
    assert float(n_tok) == float((seq_lens - 1).sum())

    for mesh_kw in ({"ring_mesh": mesh}, {"ulysses_mesh": mesh}):
        sp = models.get_model("transformer_lm", **mesh_kw, **kw)
        (l_sp, _, _), _ = sp.model.apply(
            variables, ids, labels, seq_lens, is_train=False
        )
        np.testing.assert_allclose(
            float(l_plain), float(l_sp), rtol=1e-4,
            err_msg=str(mesh_kw),
        )
        opt = sp.optimizer()
        opt_state = opt.create_state(variables.params)
        out = jax.jit(opt.minimize(sp.model))(
            variables, opt_state, ids, labels, seq_lens, rng=jax.random.PRNGKey(0)
        )
        assert np.isfinite(float(out.loss)), mesh_kw


def test_pipeline_remat_matches_plain(rng):
    """remat=True (per-step checkpoint -> 1F1B memory profile) is numerically
    identical to the plain schedule, values AND grads."""
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    mesh = make_mesh(pipe=n_stages, data=2)
    stage_params = [
        {"w": jnp.asarray(rng.randn(d, d).astype(np.float32) * 0.3),
         "b": jnp.asarray(rng.randn(d).astype(np.float32) * 0.1)}
        for _ in range(n_stages)
    ]

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    stacked = stack_stage_params(stage_params)
    x = jnp.asarray(rng.randn(n_micro * mb, d).astype(np.float32))
    mbs = split_microbatches(x, n_micro)

    out_plain = pipeline_apply(stage_fn, stacked, mbs, mesh)
    out_remat = pipeline_apply(stage_fn, stacked, mbs, mesh, remat=True)
    np.testing.assert_allclose(np.asarray(out_plain), np.asarray(out_remat),
                               rtol=1e-6, atol=1e-6)

    def loss(params, remat):
        return jnp.sum(pipeline_apply(stage_fn, params, mbs, mesh, remat=remat) ** 2)

    g_plain = jax.jit(jax.grad(lambda p: loss(p, False)))(stacked)
    g_remat = jax.jit(jax.grad(lambda p: loss(p, True)))(stacked)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        g_plain, g_remat,
    )


def test_ring_attention_gqa_kvlen_window_matches_full(rng):
    """The full r4 composition — GQA x kv_len x sliding window through the
    flash ring — matches full attention on valid rows, fwd + fused bwd."""
    from paddle_tpu.ops.pallas.flash_attention import _reference_attention

    B, H, Hkv, T, d, W = 2, 4, 2, 64, 8, 24
    mesh = make_mesh(seq=4, data=2)
    q = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, d).astype(np.float32))
    kvl = jnp.asarray([64, 40], jnp.int32)
    valid = (jnp.arange(T)[None, :] < kvl[:, None])[:, None, :, None]
    w = jnp.asarray(rng.randn(B, H, T, d).astype(np.float32)) * valid

    ref = _reference_attention(q, k, v, True, d ** -0.5, kv_len=kvl, window=W)
    out = jax.jit(lambda a, b, c: ring_attention_sharded(
        a, b, c, mesh, causal=True, window=W, kv_len=kvl, use_flash=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(jnp.where(valid, out, 0.0)),
        np.asarray(jnp.where(valid, ref, 0.0)),
        rtol=2e-4, atol=2e-5,
    )

    g_ref = jax.grad(lambda a, b, c: jnp.sum(
        _reference_attention(a, b, c, True, d ** -0.5, kv_len=kvl, window=W) * w),
        (0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(lambda a, b, c: jnp.sum(ring_attention_sharded(
        a, b, c, mesh, causal=True, window=W, kv_len=kvl, use_flash=True) * w),
        (0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=f"d{name}")


def test_transformer_lm_ragged_windowed_ring_matches_plain(rng):
    """seq_lens AND attention_window together under ring sequence
    parallelism: the masked loss equals the plain windowed LM's."""
    from paddle_tpu import models

    mesh = make_mesh(seq=4, data=2)
    kw = dict(seq_len=32, vocab=64, d_model=32, d_inner=64, num_heads=4,
              n_layers=1, attention_window=8)
    plain = models.get_model("transformer_lm", **kw)
    ringm = models.get_model("transformer_lm", ring_mesh=mesh, **kw)

    rng_np = np.random.RandomState(7)
    ids, labels = plain.synth_batch(8, rng_np)
    seq_lens = rng_np.randint(4, 33, size=(8,)).astype(np.int32)
    variables = plain.model.init(0, ids, labels, seq_lens)
    (l_plain, _, _), _ = plain.model.apply(
        variables, ids, labels, seq_lens, is_train=False
    )
    (l_ring, _, _), _ = ringm.model.apply(
        variables, ids, labels, seq_lens, is_train=False
    )
    np.testing.assert_allclose(float(l_plain), float(l_ring), rtol=1e-4)


# --------------------------------------------------- uneven final batch (r5)
def test_pad_batch_mask_and_repeat():
    """VERDICT r4 #4: pad_batch pads a ragged batch to the shard multiple by
    repeating the last real row, with a validity mask covering exactly the
    real rows."""
    from paddle_tpu.core.enforce import EnforceError
    from paddle_tpu.parallel.data_parallel import DataParallel
    from paddle_tpu.optimizer import SGD

    r = np.random.RandomState(0)
    model = pt.build(lambda x, y: pt.layers.mean(x), name="pad_net")
    dp = DataParallel(model, SGD(1e-2), mesh=make_mesh(data=8))

    x = r.rand(13, 4).astype(np.float32)
    y = r.randint(0, 5, size=(13, 1)).astype(np.int64)
    (px, py), mask = dp.pad_batch(x, y)
    assert px.shape == (16, 4) and py.shape == (16, 1)
    assert mask.tolist() == [1.0] * 13 + [0.0] * 3
    np.testing.assert_array_equal(px[13:], np.repeat(x[-1:], 3, axis=0))

    # to= pins the target (e.g. the regular batch size: single compile)
    (px, _), mask = dp.pad_batch(x, y, to=24)
    assert px.shape == (24, 4) and mask.sum() == 13

    # already-divisible batches pass through untouched
    (qx, _), mask = dp.pad_batch(x[:8], y[:8])
    assert qx is x[:8] or qx.shape == (8, 4)
    assert mask.sum() == 8

    with pytest.raises(EnforceError, match="divisible"):
        dp.pad_batch(x, y, to=15)


def test_trainer_evaluate_exact_over_ragged_test_set(rng):
    """Accuracy over EXACTLY N=52 samples with N % (devices*bs) != 0 on the
    8-device mesh: the evaluate() mask path must agree bit-for-bit with a
    direct unsharded computation over all 52 rows (reference guarantee:
    every sample evals once, data_balance_op_handle.cc:154)."""
    from paddle_tpu.trainer import Trainer

    D, C, N, BS = 8, 3, 52, 16  # 52 = 3*16 + ragged 4

    def net(x, y):
        logits = pt.layers.fc(x, C, name="clf")
        loss = pt.layers.mean(pt.layers.softmax_with_cross_entropy(logits, y))
        return loss, logits

    xs = rng.randn(N, D).astype(np.float32)
    ys = rng.randint(0, C, size=(N, 1)).astype(np.int64)

    def reader():  # test-set reader: ragged 4-row final batch
        for i in range(0, N, BS):
            yield xs[i:i + BS], ys[i:i + BS]

    def train_reader():  # train path still requires divisible batches
        yield xs[:BS], ys[:BS]

    tr = Trainer(
        lambda: pt.build(net, name="eval_net"),
        lambda: pt.optimizer.SGD(1e-2),
        parallel=True,
        parallel_kwargs=dict(mesh=make_mesh(data=8)),
    )
    tr.train(num_epochs=1, reader=train_reader)

    def accuracy(out, x, y):
        logits = out[1]
        return (np.asarray(jnp.argmax(logits, -1)) == np.asarray(y)[:, 0])

    acc = tr.evaluate(reader, accuracy)

    # direct, unsharded, all 52 rows at once
    out, _ = tr.model.apply(tr.variables, jnp.asarray(xs), jnp.asarray(ys),
                            is_train=False)
    want = float((np.asarray(jnp.argmax(out[1], -1)) == ys[:, 0]).mean())
    assert acc == pytest.approx(want, abs=1e-9)
    # ...and it is an exact-N average: 52 counted, not 48 or 64
    assert abs(acc * 52 - round(acc * 52)) < 1e-6


def test_evaluate_rejects_column_metric_and_handles_ragged_first(rng):
    """code-review r5: a [B,1] metric would broadcast to [B,B] — must raise;
    and a ragged batch FIRST in the stream must not crash the latched-target
    path."""
    from paddle_tpu.core.enforce import EnforceError
    from paddle_tpu.trainer import Trainer

    def net(x, y):
        logits = pt.layers.fc(x, 3, name="clf")
        return pt.layers.mean(
            pt.layers.softmax_with_cross_entropy(logits, y)
        ), logits

    xs = rng.randn(20, 4).astype(np.float32)
    ys = rng.randint(0, 3, size=(20, 1)).astype(np.int64)

    def ragged_first_reader():  # 4-row batch BEFORE the 16-row batch
        yield xs[:4], ys[:4]
        yield xs[4:20], ys[4:20]

    tr = Trainer(
        lambda: pt.build(net, name="eval_net2"),
        lambda: pt.optimizer.SGD(1e-2),
        parallel=True,
        parallel_kwargs=dict(mesh=make_mesh(data=8)),
    )
    tr.train(num_epochs=1, reader=lambda: iter([(xs[:16], ys[:16])]))

    with pytest.raises(EnforceError, match="one value per row"):
        tr.evaluate(
            ragged_first_reader,
            lambda out, x, y: (np.asarray(jnp.argmax(out[1], -1, keepdims=True))
                               == np.asarray(y)),  # [B,1] column: must raise
        )

    acc = tr.evaluate(
        ragged_first_reader,
        lambda out, x, y: (np.asarray(jnp.argmax(out[1], -1)) == np.asarray(y)[:, 0]),
    )
    out, _ = tr.model.apply(tr.variables, jnp.asarray(xs), jnp.asarray(ys),
                            is_train=False)
    want = float((np.asarray(jnp.argmax(out[1], -1)) == ys[:, 0]).mean())
    assert acc == pytest.approx(want, abs=1e-9)


def test_train_allow_ragged_matches_single_device(rng):
    """Train-side data_balance parity: with allow_ragged=True the
    (16,16,16,4)-batch epoch on the 8-device mesh must track a single-device
    run over the IDENTICAL batch sequence — the ragged batch trains
    replicated, so every sample trains exactly once."""
    from paddle_tpu.trainer import Trainer

    D, N, BS = 6, 52, 16

    def net(x, y):
        p = pt.layers.fc(x, 1, name="w")
        return pt.layers.mean(pt.layers.square_error_cost(p[:, 0], y))

    xs = rng.randn(N, D).astype(np.float32)
    ys = rng.randn(N).astype(np.float32)

    def reader():
        for i in range(0, N, BS):
            yield xs[i:i + BS], ys[i:i + BS]

    losses_par = []
    tr = Trainer(
        lambda: pt.build(net, name="rag_net"),
        lambda: pt.optimizer.SGD(1e-1),
        parallel=True,
        parallel_kwargs=dict(mesh=make_mesh(data=8), donate=False),
    )
    tr.train(num_epochs=2, reader=reader, allow_ragged=True,
             event_handler=lambda ev: losses_par.append(ev.metrics)
             if type(ev).__name__ == "EndStepEvent" else None)

    # single-device baseline over the identical batch sequence
    model = pt.build(net, name="rag_net_base")
    v = model.init(0, xs[:BS], ys[:BS])
    opt = pt.optimizer.SGD(1e-1)
    os_ = opt.create_state(v.params)
    step = jax.jit(opt.minimize(model))
    losses_base = []
    for _ in range(2):
        for bx, by in reader():
            out = step(v, os_, jnp.asarray(bx), jnp.asarray(by))
            v, os_ = out.variables, out.opt_state
            losses_base.append(float(out.loss))

    assert len(losses_par) == len(losses_base) == 8  # 4 batches x 2 epochs
    np.testing.assert_allclose(losses_par, losses_base, rtol=2e-5, atol=1e-6)
    for k, p in v.params.items():
        np.testing.assert_allclose(
            np.asarray(tr.variables.params[k]), np.asarray(p),
            rtol=2e-5, atol=1e-6,
        )


def test_train_allow_ragged_with_prefetch(rng):
    """code-review r5: prefetch=True must not crash on the ragged tail —
    the prefetcher's per-item placement sends it to the default device and
    step_ragged replicates it."""
    from paddle_tpu.trainer import Trainer

    xs = rng.randn(20, 4).astype(np.float32)
    ys = rng.randn(20).astype(np.float32)

    def reader():  # 16 + ragged 4
        yield xs[:16], ys[:16]
        yield xs[16:], ys[16:]

    tr = Trainer(
        lambda: pt.build(lambda x, y: pt.layers.mean(
            pt.layers.square_error_cost(pt.layers.fc(x, 1, name="w")[:, 0], y))),
        lambda: pt.optimizer.SGD(1e-1),
        parallel=True, prefetch=True,
        parallel_kwargs=dict(mesh=make_mesh(data=8), donate=False),
    )
    losses = []
    tr.train(num_epochs=2, reader=reader, allow_ragged=True,
             event_handler=lambda ev: losses.append(ev.metrics)
             if type(ev).__name__ == "EndStepEvent" else None)
    assert len(losses) == 4 and losses[-1] < losses[0]
