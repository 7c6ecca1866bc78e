"""The latent-attention, sparse-expert LM (``models/latent_moe_lm.py``,
``ops/moe.py``, ``ops/pallas/moe.py``) at a small size on the CPU: the two
forms of the attention agree on the same cache, the YaRN tables are the
formulas', the router drops nothing, four shares of the experts add up to the
whole layer, the ``moe_gmm`` kernel (interpret mode) agrees with XLA's ragged
dot, and ``pt.Trainer`` trains the model with the loss and gradients of the
plain reference."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu.models import latent_moe_lm as L
from paddle_tpu.ops import moe
from paddle_tpu.ops.attention import rope_tables, yarn_mscale
from paddle_tpu.ops.pallas import moe as kernel

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import latent_moe_lm as ref  # noqa: E402
from benchmarks.tiny_experts import as_checkpoint  # noqa: E402

YARN = dict(type="deepseek_yarn", factor=40, original_max_position_embeddings=16,
            beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
SMALL = dict(vocab=97, d_model=64, num_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
             kv_lora_rank=32, d_inner=96, moe_d_inner=32, n_layers=3, num_experts=8,
             experts_per_token=2, rope_scaling=YARN, param_dtype="float32",
             compute_dtype="float32")


def small_model(seq_len=16, **over):
    return models.get_model("latent_moe_lm", seq_len=seq_len, **dict(SMALL, **over))


def seeded(spec, rows=2, seed=0):
    ids, labels = spec.synth_batch(rows, np.random.RandomState(seed))
    variables = spec.model.init(0, ids, labels)
    return ids, labels, {k: jnp.asarray(v) for k, v in variables.params.items()}


# -- (a) the attention's two forms, on the same cache -------------------------

@pytest.mark.parametrize("head_block", [4, 2])
def test_absorbed_and_expanded_attention_agree_on_the_same_cache(head_block, monkeypatch):
    # the scores of all four heads are 4 * 2 * 4 * 3 * 24 bytes
    monkeypatch.setattr(L, "_SCORE_BYTES", 2304 * head_block // 4)
    assert L.head_block_for(2, 4, 3, 24) == head_block
    rng = np.random.default_rng(0)
    B, H, Q, T, nope, rope, rank, v = 2, 4, 3, 24, 16, 8, 32, 16
    q = jnp.asarray(rng.normal(size=(B, H, Q, nope + rope)), jnp.float32)
    rows = np.zeros((B, T, 128), np.float32)  # latent, rotary key, the row's zeros
    rows[..., :rank + rope] = rng.normal(size=(B, T, rank + rope))
    w_kb = jnp.asarray(rng.normal(size=(rank, H, nope)) * 0.2, jnp.float32)
    w_vb = jnp.asarray(rng.normal(size=(rank, H, v)) * 0.2, jnp.float32)
    live = jnp.asarray(np.arange(T)[None, None, None, :] <= np.array([20, 21, 22])[None, None, :, None])
    cfg = dict(L.BASE_CFG, qk_nope_dim=nope, qk_rope_dim=rope, compute_dtype="float32")
    a = L._core(cfg, "absorbed")(q, jnp.asarray(rows), live, w_kb, w_vb)
    e = L._core(cfg, "expanded")(q, jnp.asarray(rows), live, w_kb, w_vb)
    assert a.shape == (B, H, Q, v)
    np.testing.assert_allclose(a, e, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("form", ["expanded", "absorbed", "absorbed_in_kernel"])
def test_chunks_then_steps_through_the_pages_give_the_full_forward_pass(form, monkeypatch):
    from paddle_tpu.core import profiler as prof
    from paddle_tpu.models import transformer_lm

    in_kernel, before = form == "absorbed_in_kernel", dict(prof.counters())
    if in_kernel:
        # the chunk and the step through latent_attend_chunk / _step, which a
        # CPU interprets, as a TPU's programs take them
        monkeypatch.setattr(transformer_lm, "step_attends_in_kernel", lambda *a: True)
        form = "absorbed"
    spec = small_model(seq_len=24)
    cfg = spec.extra["cfg"]
    ids, labels, params = seeded(spec)
    want = np.asarray(ref.logits_fn(as_checkpoint(params, (0, 8)), ids[:1], cfg,
                                    refc.mm_f32))[0]
    page, P, C = 4, 8, 8
    (spec_,) = L.latent_cache_specs(cfg, num_pages=1 + 2 * P, page_size=page, dtype=jnp.float32)
    assert spec_.shape == (3, 17, 4, 128)  # 32 + 8 numbers a row, in 128 lanes
    pages = jnp.zeros(spec_.shape, spec_.dtype)
    table = jnp.arange(1, 1 + P, dtype=jnp.int32)
    seq = ids[0]
    for c in range(0, 16, C):
        tok, pages, load = L.latent_prefill_chunk(
            params, jnp.asarray(seq[c:c + C]), jnp.int32(c), jnp.int32(C - 1), table, pages,
            cfg=cfg, page_size=page, form=form)
        assert int(tok) == want[c + C - 1].argmax() and load.shape == (2, 8)
        assert int(load.sum()) == 2 * C * 2  # every token's two experts, both expert layers
    tables = jnp.stack([table, jnp.zeros_like(table)])  # slot 1 idle: the scratch page
    for t in range(16, 24):
        nxt, pages, load = L.latent_decode_step(
            params, jnp.asarray([seq[t], 0]), jnp.asarray([t, 0]), tables, pages,
            cfg=cfg, page_size=page)
        assert int(nxt[0]) == want[t].argmax()
        assert int(load.sum()) == 2 * 2  # the idle slot's token reaches no routed expert
    # a padded last chunk: the three real positions' pairs and no others
    _, _, load = L.latent_prefill_chunk(
        params, jnp.asarray(seq[:C]), jnp.int32(0), jnp.int32(2), table, pages,
        cfg=cfg, page_size=page, form=form)
    assert int(load.sum()) == 3 * 2 * 2
    traced = {k: v - before.get(k, 0) for k, v in prof.counters().items()
              if k.startswith("mla.kernel.") and v != before.get(k, 0)}
    # a layer's call of every trace (the calls are not jitted here): three
    # chunks and eight steps through three layers, or none
    assert traced == ({"mla.kernel.chunk": 3 * 3, "mla.kernel.step": 8 * 3} if in_kernel else {})


# -- (b) YaRN -------------------------------------------------------------------

def test_yarn_tables_are_the_formulas_past_and_under_the_original_context():
    """A literal transcription of DeepSeek-V2's ``find_correction_dim``,
    ``find_correction_range`` and ``linear_ramp_mask`` at the published
    numbers (rotary 64, base 10000, factor 40, original 4096, beta 32 / 1)."""
    dim, base, factor, orig, fast, slow = 64, 10000.0, 40.0, 4096, 32, 1
    scaling = dict(type="deepseek_yarn", factor=factor, beta_fast=fast, beta_slow=slow,
                   original_max_position_embeddings=orig, mscale=1, mscale_all_dim=1)
    fcd = lambda r: dim * math.log(orig / (r * 2 * math.pi)) / (2 * math.log(base))
    low, high = max(math.floor(fcd(fast)), 0), min(math.ceil(fcd(slow)), dim - 1)
    assert (low, high) == (10, 23)
    want = []
    for j in range(dim // 2):
        extra = 1.0 / base ** (2 * j / dim)
        mask = 1.0 - min(max((j - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * (1 - mask) + extra * mask)
    want = np.array(want)
    assert want[5] == pytest.approx(base ** (-10 / 64)) and want[30] == pytest.approx(
        base ** (-60 / 64) / 40)  # fast ones kept, slow ones interpolated
    for pos0 in (100, 4000, 9000, 16000):  # under and past the original 4096
        cos, sin = rope_tables(dim, 4, base, pos0, scaling)
        angle = (pos0 + np.arange(4))[:, None] * want[None, :]
        np.testing.assert_allclose(cos, np.cos(angle), atol=2e-3)
        np.testing.assert_allclose(sin, np.sin(angle), atol=2e-3)
    np.testing.assert_allclose(ref.yarn_inv_freq(dim, base, scaling), want, rtol=1e-6)
    plain = rope_tables(dim, 4, base, 9000)
    assert not np.allclose(plain[0], rope_tables(dim, 4, base, 9000, scaling)[0], atol=1e-2)
    assert yarn_mscale(40.0, 1.0) == pytest.approx(1.368888, rel=1e-6)
    cfg = dict(L.BASE_CFG, qk_nope_dim=128, qk_rope_dim=64, rope_scaling=scaling)
    assert L.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.368888 ** 2, rel=1e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(L.softmax_scale(cfg), rel=1e-9)
    with pytest.raises(Exception, match="deepseek_yarn"):
        rope_tables(dim, 4, base, 0, {"type": "linear", "factor": 2})


# -- (c) the router and the share ----------------------------------------------

def experts_of(rng, count, d, f):
    return {"gate": jnp.asarray(rng.normal(size=(count, d, f)) * 0.1, jnp.float32),
            "fc1": jnp.asarray(rng.normal(size=(count, d, f)) * 0.1, jnp.float32),
            "fc2": jnp.asarray(rng.normal(size=(count, f, d)) * 0.1, jnp.float32)}


def whole_layer(x, route, experts):
    y = jnp.zeros_like(x)
    for e in range(experts["gate"].shape[0]):
        w = jnp.sum(jnp.where(route.experts == e, route.weights, 0.0), -1)
        y += w[:, None] * ((jax.nn.silu(x @ experts["gate"][e]) * (x @ experts["fc1"][e]))
                           @ experts["fc2"][e])
    return y


def test_the_router_drops_nothing_when_one_expert_takes_every_token():
    rng = np.random.default_rng(1)
    N, E, k, d, f = 40, 8, 2, 16, 24
    scores = jnp.asarray(rng.uniform(0.1, 0.4, size=(N, E)), jnp.float32)
    bias = jnp.zeros((E,)).at[3].set(10.0)  # the bias picks, the scores weigh
    route = moe.topk_route(scores, bias, k, scaling=2.5)
    assert bool((route.experts == 3).any(-1).all())
    np.testing.assert_allclose(route.weights.sum(-1), 2.5, rtol=1e-6)
    picked = jnp.take_along_axis(scores, route.experts, -1)
    np.testing.assert_allclose(route.weights, 2.5 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    x = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    experts = experts_of(rng, E, d, f)
    for use_kernel, tile in ((False, None), (True, 8)):
        y, load = moe.expert_share_ffn(x, route, experts, (0, E), compute_dtype=jnp.float32,
                                       kernel=use_kernel, row_tile=tile)
        assert int(load[3]) == N and int(load.sum()) == N * k  # no capacity: all 40 land
        np.testing.assert_allclose(y, whole_layer(x, route, experts), rtol=2e-4, atol=2e-5)


def test_four_shares_of_the_experts_and_the_shared_expert_once_add_up_to_the_whole_layer():
    """The uncut reference's expert layer against four programs that each
    hold two of the eight experts: their routed parts and one copy of what
    every chip computes alike (the shared expert) sum to the whole layer."""
    spec = small_model()
    cfg = spec.extra["cfg"]
    _, _, params = seeded(spec)
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.normal(size=(20, 64)), jnp.float32)
    head = "layer_1/"
    stacks = {k[len(head):]: v for k, v in params.items() if k.startswith(head)}
    lp = as_checkpoint(stacks, (0, 8))  # the reference's: a matrix an expert
    whole = (ref.expert_share(n, lp, dict(cfg, experts_held=None), refc.mm_f32)
             + ref.swiglu(n, lp, "moe/shared/ffn", refc.mm_f32))
    scores = jax.nn.sigmoid(n @ lp["moe/router/w"])
    route = moe.topk_route(scores, lp["moe/router/b"], 2, 2.5)
    parts, ref_parts, loads = [], [], []
    for first in range(0, 8, 2):
        held = {w: stacks[f"moe/experts/{w}/w"][first:first + 2] for w in ("gate", "fc1", "fc2")}
        y, load = moe.expert_share_ffn(n, route, held, (first, 2), compute_dtype=jnp.float32,
                                       kernel=False)
        parts.append(y)
        loads.append(int(load.sum()))
        # a share's checkpoint holds its own two experts' matrices and no others
        share = {k: v for k, v in lp.items() if not k.startswith("moe/experts/")
                 or int(k.split("/")[2]) in (first, first + 1)}
        ref_parts.append(ref.expert_share(n, share, dict(cfg, experts_held=(first, 2)),
                                          refc.mm_f32))
    assert sum(loads) == 20 * 2 and max(loads) < 40
    shared = ref.swiglu(n, lp, "moe/shared/ffn", refc.mm_f32)
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(sum(ref_parts) + shared, whole, rtol=2e-4, atol=2e-5)
    for mine, theirs in zip(parts, ref_parts):  # and share for share
        np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-5)


# -- (d) the kernel --------------------------------------------------------------

@pytest.mark.parametrize("tm, tn", [(8, None), (16, 128), (8, 256)])
def test_moe_gmm_in_interpret_mode_is_xlas_ragged_dot(tm, tn):
    rng = np.random.default_rng(3)
    N, k, E, K, Nout = 24, 4, 16, 128, 256
    held = (4, 6)
    experts = jnp.asarray(np.stack([rng.choice(E, k, replace=False) for _ in range(N)]), jnp.int32)
    lay = moe.share_layout(experts, held, tm)
    rows = lay.src.shape[0]
    assert rows % tm == 0 and rows >= N * k + 6 * (tm - 1) - tm
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, K, Nout)) * 0.1, jnp.float32)
    got = kernel.moe_gmm(x, w, lay.tile_expert, lay.used, tm=tm, tn=tn, interpret=True)
    want = kernel.moe_gmm_xla(x, w, lay.padded)
    used = int(lay.used[0]) * tm
    assert used == int(lay.padded.sum()) and int(lay.load.sum()) == int(lay.here.sum())
    np.testing.assert_allclose(got[:used], want[:used], rtol=1e-5, atol=1e-5)
    # a layout is sorted by expert, each expert's rows from a multiple of the tile
    starts = np.cumsum(lay.padded) - lay.padded
    assert (starts % tm == 0).all() and (np.diff(np.asarray(lay.tile_expert)) >= 0).all()
    resolved = kernel.take_resolved()
    assert resolved[f"moe_gmm_{tm}x{K}x{Nout}"].endswith("caller" if tn else "rule")


@pytest.mark.parametrize("K, Nout", [(1024, 2688), (2688, 1024)])
def test_moe_gmm_at_the_latent_experts_shapes_is_xlas_ragged_dot(K, Nout):
    """The two products of an up-relu^2-down expert in a latent of 1024 (a step
    of 64 tokens, 22 of 512 a token, on 16 of the held experts, tiles of 16):
    the whole matrix is one block (by the rule for float32 operands here, by
    the table's chip-measured row for the chip's bfloat16)."""
    rng = np.random.default_rng(5)
    N, k, E, tm = 64, 22, 512, 16
    held = (32, 16)
    experts = jnp.asarray(np.stack([rng.choice(E, k, replace=False) for _ in range(N)]), jnp.int32)
    lay = moe.share_layout(experts, held, tm)
    x = jnp.asarray(rng.normal(size=(lay.src.shape[0], K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, K, Nout)) * 0.03, jnp.float32)
    got = kernel.moe_gmm(x, w, lay.tile_expert, lay.used, tm=tm, interpret=True)
    want = kernel.moe_gmm_xla(x, w, lay.padded)
    used = int(lay.used[0]) * tm
    assert 0 < int(lay.load.sum()) == int(lay.here.sum()) <= used
    np.testing.assert_allclose(got[:used], want[:used], rtol=2e-5, atol=2e-4)
    assert kernel.take_resolved()[f"moe_gmm_{tm}x{K}x{Nout}"].split()[0] == str(Nout)
    assert kernel.resolve_tiles(tm, K, Nout, 2) == (Nout, "table")  # bfloat16 on the chip
    assert kernel.resolve_tiles(64, K, Nout, 2) == (Nout, "table")  # a chunk's tiles


@pytest.mark.parametrize("use_kernel", [False, True], ids=["ragged_dot", "kernel"])
def test_the_relu2_body_shares_the_routing_layout_and_scatter(use_kernel):
    """``expert_share_ffn`` with the up-relu^2-down body against a plain loop
    over the held experts; the SwiGLU body over the same routing differs."""
    rng = np.random.default_rng(6)
    N, k, E, d, f = 20, 3, 12, 16, 24
    held = (3, 6)
    x = jnp.asarray(rng.normal(size=(N, d)), jnp.float32)
    experts = jnp.asarray(np.stack([rng.choice(E, k, replace=False) for _ in range(N)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(N, k)), jnp.float32)
    w = {"fc1": jnp.asarray(rng.normal(size=(6, d, f)) * 0.3, jnp.float32),
         "fc2": jnp.asarray(rng.normal(size=(6, f, d)) * 0.3, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        y, load = moe.expert_share_ffn(x, moe.Route(experts, weights), w, held, body="relu2",
                                       compute_dtype=jnp.float32, kernel=use_kernel,
                                       row_tile=8 if use_kernel else None)
        want = np.zeros((N, d), np.float32)
        for i in range(N):
            for e, we in zip(np.asarray(experts[i]), np.asarray(weights[i])):
                if 3 <= e < 9:
                    h = np.maximum(np.asarray(x[i]) @ np.asarray(w["fc1"][e - 3]), 0.0) ** 2
                    want[i] += we * (h @ np.asarray(w["fc2"][e - 3]))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    assert load.tolist() == [int((np.asarray(experts) == e).sum()) for e in range(3, 9)]
    with pytest.raises(KeyError):
        moe.expert_share_ffn(x, moe.Route(experts, weights), w, held, body="swiglu",
                             compute_dtype=jnp.float32, kernel=False)


def test_no_pair_held_here_is_a_layer_that_adds_nothing():
    experts = jnp.zeros((5, 2), jnp.int32)  # every pair on expert 0, held elsewhere
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    route = moe.Route(experts, jnp.ones((5, 2), jnp.float32))
    for use_kernel, tile in ((False, None), (True, 8)):
        y, load = moe.expert_share_ffn(x, route, experts_of(rng, 2, 16, 8), (4, 2),
                                       compute_dtype=jnp.float32, kernel=use_kernel,
                                       row_tile=tile)
        assert int(load.sum()) == 0 and not np.asarray(y).any()


# -- (e) pt.Trainer: loss and gradients against the reference's ---------------

def test_trainer_loss_and_gradients_are_the_references_and_the_bias_stays():
    spec = small_model(seq_len=12, experts_held=(2, 4))
    cfg = spec.extra["cfg"]
    ids, labels, params = seeded(spec, rows=3, seed=1)
    mean_loss = lambda p: ref.loss_sum(as_checkpoint(p, (2, 4)), ids, labels, cfg,
                                       refc.mm_f32) / labels.size
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
    np.testing.assert_allclose(np.asarray(losses[0]).reshape(-1)[0], want_loss, rtol=1e-5)
    for name, g in want_grad.items():  # SGD at rate 1: the step is the gradient
        got = params[name] - trainer.variables.params[name]
        np.testing.assert_allclose(got, g, rtol=2e-3, atol=2e-6, err_msg=name)
    for i in (1, 2):  # the selection bias takes no gradient
        name = f"layer_{i}/moe/router/b"
        np.testing.assert_array_equal(trainer.variables.params[name], params[name])


def test_the_model_is_in_the_registry_and_holds_bfloat16_by_default():
    spec = models.get_model("latent_moe_lm", seq_len=8, vocab=97, d_model=32, num_heads=2,
                            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_lora_rank=16,
                            d_inner=64, moe_d_inner=16, n_layers=2, num_experts=4,
                            experts_per_token=2, experts_held=(1, 2))
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    cfg = spec.extra["cfg"]
    assert {v.dtype for v in variables.params.values()} == {jnp.dtype("bfloat16")}
    assert {k: v.shape for k, v in variables.params.items()} == L.param_shapes(cfg)
    # the two held experts' matrices stacked in their order, as the grouped matmul reads them
    assert variables.params["layer_1/moe/experts/fc1/w"].shape == (2, 32, 16)
    assert variables.params["layer_1/moe/experts/fc2/w"].shape == (2, 16, 32)
    assert variables.params["layer_1/moe/experts/gate/w"].shape == (2, 32, 16)
    assert variables.params["layer_1/moe/router/w"].shape == (32, 4)
    # initialised by one expert's own fans: the stack is as loud as the shared expert
    loud = lambda n: float(jnp.std(variables.params[n].astype(jnp.float32)))
    assert loud("layer_1/moe/experts/fc1/w") == pytest.approx(
        loud("layer_1/moe/shared/ffn/fc1/w"), rel=0.15)
    # a checkpoint holds a matrix an expert, by its index in the router's width
    held = as_checkpoint(dict(variables.params), (1, 2))
    assert held["layer_1/moe/experts/2/fc2/w"].shape == (16, 32)
    assert "layer_1/moe/experts/0/fc2/w" not in held
    loaded = L.stack_experts(held, cfg)
    assert not held and loaded.keys() == variables.params.keys()
    for name, w in variables.params.items():
        np.testing.assert_array_equal(loaded[name], w)
    (loss, _, logits), _ = spec.model.apply(variables, ids, labels)
    assert np.isfinite(float(loss)) and logits.dtype == jnp.float32
    progs = models.serving_programs(cfg)
    assert (progs.cache, progs.cache_args, progs.kv_heads, progs.extras) == (
        "pages", ("latent_pages",), None, ("expert_load",))
    with pytest.raises(Exception, match="experts_held"):
        models.get_model("latent_moe_lm", num_experts=4, experts_held=(3, 2))
