"""Framework core tests: param creation, naming, state threading, scopes.

Mirrors the reference's C++ framework unit tests (scope_test.cc,
operator_test.cc, var_type_inference_test.cc) at the abstraction that exists
here: the transform/param-store."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def test_param_creation_and_apply_consistency():
    def net(x):
        return layers.fc(x, 16, act="relu", name="fc1")

    model = pt.build(net)
    x = jnp.ones((4, 8))
    variables = model.init(jax.random.PRNGKey(0), x)
    assert set(variables.params) == {"fc1/w", "fc1/b"}
    assert variables.params["fc1/w"].shape == (8, 16)
    out, new_state = model.apply(variables, x)
    assert out.shape == (4, 16)
    assert new_state == {}


def test_duplicate_layer_names_uniquified():
    def net(x):
        for _ in range(3):
            x = layers.fc(x, 8)
        return x

    model = pt.build(net)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((2, 8)))
    assert {n for n in variables.params if n.endswith("/w")} == {"fc/w", "fc_1/w", "fc_2/w"}


def test_name_scope_nesting():
    def net(x):
        with pt.name_scope("block"):
            x = layers.fc(x, 8, name="inner")
        with pt.name_scope("block"):
            x = layers.fc(x, 8, name="inner")
        return x

    model = pt.build(net)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((2, 8)))
    names = sorted(variables.params)
    assert "block/inner/w" in names
    assert "block_1/inner/w" in names


def test_state_threading_batch_norm():
    def net(x):
        return layers.batch_norm(x, name="bn")

    model = pt.build(net)
    x = jnp.asarray(np.random.RandomState(0).randn(8, 4, 4, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    assert "bn/moving_mean" in variables.state
    out, new_state = model.apply(variables, x, is_train=True)
    # moving stats must move in train mode...
    assert not np.allclose(new_state["bn/moving_mean"], variables.state["bn/moving_mean"])
    # ...and stay fixed in eval mode
    out2, state2 = model.apply(variables, x, is_train=False)
    np.testing.assert_array_equal(state2["bn/moving_mean"], variables.state["bn/moving_mean"])


def test_missing_param_raises():
    def net(x):
        return layers.fc(x, 4)

    model = pt.build(net)
    variables = model.init(jax.random.PRNGKey(0), jnp.ones((2, 4)))
    bad = {k: v for k, v in variables.params.items() if not k.endswith("/b")}
    with pytest.raises(pt.EnforceError):
        model.apply((bad, {}), jnp.ones((2, 4)))


def test_apply_is_jittable_and_pure():
    def net(x):
        h = layers.fc(x, 32, act="tanh")
        return layers.fc(h, 2)

    model = pt.build(net)
    x = jnp.ones((4, 8))
    variables = model.init(jax.random.PRNGKey(0), x)

    @jax.jit
    def fwd(params, x):
        out, _ = model.apply((params, {}), x)
        return out

    out1 = fwd(variables.params, x)
    out2, _ = model.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def test_dropout_needs_rng_and_is_train_gated():
    def net(x):
        return layers.dropout(x, 0.5)

    model = pt.build(net)
    x = jnp.ones((128,))
    variables = model.init(jax.random.PRNGKey(0), x)
    out_eval, _ = model.apply(variables, x, is_train=False)
    np.testing.assert_array_equal(np.asarray(out_eval), np.ones(128))
    out_train, _ = model.apply(variables, x, rng=jax.random.PRNGKey(1), is_train=True)
    assert np.any(np.asarray(out_train) == 0.0)
    with pytest.raises(pt.EnforceError):
        model.apply(variables, x, is_train=True)  # no rng provided


def test_param_info_records_metadata():
    reg = pt.regularizer.L2Decay(1e-4)

    def net(x):
        return layers.fc(
            x, 4, param_attr=pt.framework.ParamAttr(regularizer=reg, learning_rate=0.5)
        )

    model = pt.build(net)
    model.init(jax.random.PRNGKey(0), jnp.ones((2, 4)))
    info = model.param_info["fc/w"]
    assert info.regularizer is reg
    assert info.learning_rate == 0.5
    assert model.param_info["fc/b"].regularizer is None


# -------------------------------------------------- API-parity tail


def test_weight_norm_param_attr(rng):
    """fc with WeightNormParamAttr trains through the (v, g) pair; the
    effective weight's per-output-column norm equals g."""
    def net(x, y):
        pred = pt.layers.fc(
            x, size=4, param_attr=pt.WeightNormParamAttr(dim=1), bias_attr=False)
        return pt.layers.mean((pred - y) ** 2)

    model = pt.build(net)
    x = rng.randn(8, 6).astype(np.float32)
    y = rng.randn(8, 4).astype(np.float32)
    variables = model.init(0, x, y)
    names = list(variables.params)
    assert any(n.endswith("w_v") for n in names), names
    assert any(n.endswith("w_g") for n in names), names

    opt = pt.optimizer.SGD(learning_rate=0.1)
    step = jax.jit(opt.minimize(model))
    o = step(variables, opt.create_state(variables.params), x, y)
    o2 = step(o.variables, o.opt_state, x, y)
    assert float(o2.loss) < float(o.loss)

    # effective weight column norms == g (reparameterization invariant)
    p = o2.variables.params
    v = np.asarray([p[n] for n in names if n.endswith("w_v")][0])
    g = np.asarray([p[n] for n in names if n.endswith("w_g")][0])
    w = g[None, :] * v / np.linalg.norm(v, axis=0, keepdims=True)
    np.testing.assert_allclose(np.linalg.norm(w, axis=0), np.abs(g), rtol=1e-5)


def test_create_lod_tensor_compat():
    rb = pt.create_lod_tensor([np.arange(3), np.arange(5)])
    assert rb.data.shape == (2, 5)
    assert list(rb.lengths) == [3, 5]
    assert rb.mask().sum() == 8

    flat = np.arange(8).reshape(8, 1)
    rb2 = pt.create_lod_tensor(flat, recursive_seq_lens=[[3, 5]])
    assert rb2.data.shape == (2, 5, 1)
    np.testing.assert_array_equal(rb2.data[0, :3, 0], [0, 1, 2])

    rb3 = pt.create_random_int_lodtensor([[2, 4]], base_shape=[1], high=9, seed=0)
    assert rb3.data.shape == (2, 4, 1)
    assert rb3.data.max() <= 9


def test_inferencer_round_trip(tmp_path, rng):
    def net(x, y):
        pred = pt.layers.fc(x, size=1, name="fc")
        return pt.layers.mean((pred[:, 0] - y) ** 2)

    model = pt.build(net)
    x = rng.randn(8, 4).astype(np.float32)
    y = rng.randn(8).astype(np.float32)
    variables = model.init(0, x, y)
    pt.io.save_params(str(tmp_path / "params"), variables)

    def infer_net(x):
        return pt.layers.fc(x, size=1, name="fc")

    inf = pt.Inferencer(infer_net, str(tmp_path / "params"))
    out = inf.infer([x])
    expect, _ = pt.build(infer_net).apply(variables, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5)


def test_persistent_compile_cache_flag(tmp_path, rng):
    """flags().compilation_cache_dir routes jit compiles through the
    persistent cache: artifacts appear in the directory."""
    cache_dir = str(tmp_path / "jaxcache")
    cfg_mod = pt.core.config
    prev_applied = cfg_mod._compile_cache_applied
    cfg_mod._compile_cache_applied = False
    try:
        pt.core.config.set_flags(compilation_cache_dir=cache_dir)
        exe = pt.Executor()

        def net(x):
            return pt.layers.fc(x, size=3).sum()

        model = pt.build(net)
        x = rng.randn(4, 5).astype(np.float32)
        variables = model.init(0, x)
        fn = exe.prepare(lambda v, x: model.apply(v, x)[0], key="cache_probe")
        float(fn(variables, jnp.asarray(x)))
        import os as _os

        assert _os.path.isdir(cache_dir) and len(_os.listdir(cache_dir)) >= 1
    finally:
        # restore GLOBAL jax config — later tests must not write cache
        # artifacts into this test's tmp dir
        pt.core.config.set_flags(compilation_cache_dir="")
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        cfg_mod._compile_cache_applied = prev_applied


def test_compile_cache_env_var_wins(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no directory in
    code (JAX reads the variable itself); unset, a caller falls to the
    default it passes (benchmarks/harness.py: ``.bench_cache/jax``)."""
    cfg_mod = pt.core.config
    monkeypatch.setattr(cfg_mod, "_compile_cache_applied", False)
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert cfg_mod.apply_compile_cache(default_dir=str(tmp_path / "d")) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.setattr(cfg_mod, "_compile_cache_applied", False)
        assert cfg_mod.apply_compile_cache(default_dir=str(tmp_path / "d")) == str(tmp_path / "d")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "d")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def test_place_raises_for_a_device_that_is_not_there():
    """TPUPlace on a host without that chip must not stand in the CPU (or
    another chip) and say nothing."""
    assert pt.CPUPlace().device().platform == "cpu"
    with pytest.raises(RuntimeError, match="no tpu device 0"):
        pt.TPUPlace(0).device()
    from paddle_tpu.serving.engine import _ReplicaPlace

    with pytest.raises(RuntimeError, match="no cpu device 99"):
        _ReplicaPlace("cpu", 99).device()


def test_inferencer_dict_feed_in_feed_order(tmp_path, rng):
    """Dict feeds must be unpacked in feed_order (FeedSpec order), not raw
    insertion order — clients over the wire give no ordering guarantee."""
    def net(a, b):
        return layers.fc(a, size=2, name="fa") + layers.fc(b, size=2, name="fb")

    model = pt.build(net)
    a = rng.randn(4, 3).astype(np.float32)
    b = rng.randn(4, 7).astype(np.float32)
    variables = model.init(0, a, b)
    pt.io.save_params(str(tmp_path / "p"), variables)

    inf = pt.Inferencer(
        net, str(tmp_path / "p"),
        feed_order=[pt.FeedSpec("a", (3,)), pt.FeedSpec("b", (7,))],
    )
    # feed dict built backwards: insertion order would swap the slots
    out = inf.infer({"b": b, "a": a})
    expect, _ = model.apply(variables, jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5)


def test_inferencer_reuses_executor_compile_cache(tmp_path, rng):
    """infer() compiles through the shared Executor cache (one entry,
    reused), not a private slot."""
    def net(x):
        return layers.fc(x, size=2, name="fc")

    model = pt.build(net)
    x = rng.randn(4, 5).astype(np.float32)
    variables = model.init(0, x)
    pt.io.save_params(str(tmp_path / "p"), variables)
    inf = pt.Inferencer(net, str(tmp_path / "p"))
    assert len(inf.executor._cache) == 0
    inf.infer([x])
    assert len(inf.executor._cache) == 1
    inf.infer([x])
    assert len(inf.executor._cache) == 1  # cache hit, no new entry


def test_executor_run_forwards_static_argnums():
    """run() must forward static_argnums to prepare — a python-branching
    static arg traced as a Tracer would raise."""
    exe = pt.Executor()

    def f(x, mode):
        if mode == "double":  # concretization error unless mode is static
            return x * 2
        return x

    out = exe.run(f, jnp.ones((3,)), "double", static_argnums=(1,))
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones((3,)))
    out = exe.run(f, jnp.ones((3,)), "id", static_argnums=(1,))
    np.testing.assert_allclose(np.asarray(out), np.ones((3,)))


def test_executor_cache_lru_not_fifo():
    """A cache hit refreshes recency: hot entries (serving buckets) must
    survive a burst of cold one-off functions; FIFO would evict them."""
    exe = pt.Executor(max_cache=2)
    hot = exe.prepare(lambda x: x + 1, key="hot")
    exe.prepare(lambda x: x + 2, key="cold1")
    assert exe.prepare(lambda x: x, key="hot") is hot  # hit → move to end
    exe.prepare(lambda x: x + 3, key="cold2")  # evicts cold1, NOT hot
    assert "hot" in exe._cache and "cold1" not in exe._cache
    assert exe.prepare(lambda x: x, key="hot") is hot


def test_executor_cache_eviction_bound():
    exe = pt.Executor(max_cache=4)
    for i in range(10):
        exe.prepare(lambda x, i=i: x + i, key=("k", i))
    assert len(exe._cache) == 4
    # the most recent 4 survive
    assert [k[1] for k in exe._cache] == [6, 7, 8, 9]
