"""``models/looped_lm.py`` at a tiny size on seeded weights, on the CPU: a
decoder whose whole stack runs several passes over the same weights, each
pass with K and V pages of its own. What it computes is the plain
reference's full forward pass (``benchmarks/references/looped_lm.py``) in
training, through prefill chunks and decode steps by hand, and through
``serving.DecodeEngine``; plane ``r * L + i`` of the pages holds pass ``r``,
layer ``i``; the exit distribution is a distribution; the layers' parameters
are held stacked, and a checkpoint that holds a leaf a layer is stacked at
load; what the engine cannot do with such a model it refuses by name."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import models, tracing
from paddle_tpu.models import looped_lm as L
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.disagg import DECODE, PREFILL, DisaggRouter
from paddle_tpu.serving.shardgroup import TP_AXIS, GroupLayout, make_groups

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import check, weights  # noqa: E402
from benchmarks.families import looped_lm as family  # noqa: E402
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import looped_lm as ref  # noqa: E402

VOCAB, LAYERS, PASSES = 97, 3, 4
SMALL = dict(vocab=VOCAB, d_model=64, d_inner=128, num_heads=4, head_dim=16, n_layers=LAYERS,
             total_ut_steps=PASSES, max_len=64, param_dtype="float32", compute_dtype="float32")
DECODE_KW = dict(max_slots=3, page_size=4, max_context=64, prefill_chunk=8)


def seeded(seed=5, **overrides):
    """(spec, cfg, {name: float32 array}) of the tiny model on the benchmark's
    seeded weights, a leaf a layer as a checkpoint and the reference hold
    them: every scale, the gate and its bias away from their initial values."""
    spec = models.get_model("looped_lm", seq_len=16, **dict(SMALL, **overrides))
    cfg = spec.extra["cfg"]
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32) for n, s in L.param_shapes(cfg).items()}
    return spec, cfg, dict(weights.make_weights(family.checkpoint_shapes(None, shapes), seed))


@pytest.fixture(scope="module")
def lm():
    """``params`` a leaf a layer (the reference's); ``stacked`` and
    ``variables`` what the program holds."""
    spec, cfg, params = seeded()
    stacked = L.stack_layers(dict(params), cfg)
    return types.SimpleNamespace(spec=spec, cfg=cfg, params=params, stacked=stacked,
                                 variables=pt.framework.Variables(dict(stacked), {}))


def reference_rows(lm, prompt, tokens):
    """The reference's logits at the positions that produced ``tokens``."""
    logits, _ = ref.forward(lm.params, jnp.asarray(np.concatenate([prompt, tokens])), lm.cfg,
                            refc.mm_f32)
    return np.asarray(logits)[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


def prompts_of(rng, lengths):
    return [rng.randint(1, VOCAB, size=(n,)).astype(np.int32) for n in lengths]


# -- the programs by hand ----------------------------------------------------

def test_chunks_then_steps_through_the_pages_give_the_full_forward_pass(lm):
    cfg, params = lm.cfg, lm.stacked
    seq = np.random.RandomState(0).randint(1, VOCAB, size=(24,)).astype(np.int32)
    want, want_lam = ref.forward(lm.params, jnp.asarray(seq), cfg, refc.mm_f32)
    want, want_cdf = np.asarray(want), np.cumsum(np.asarray(ref.exit_distribution(want_lam)), 0)
    page, P, C = 4, 8, 8
    k_spec, v_spec = L.looped_cache_specs(cfg, num_pages=1 + 2 * P, page_size=page,
                                          dtype=jnp.float32)
    assert k_spec.shape == v_spec.shape == (PASSES * LAYERS, 17, 4, 64)
    k, v = jnp.zeros(k_spec.shape), jnp.zeros(v_spec.shape)
    table = jnp.arange(1, 1 + P, dtype=jnp.int32)
    for c in range(0, 16, C):
        tok, k, v, cdf, rows = L.looped_prefill_chunk(
            params, jnp.asarray(seq[c:c + C]), jnp.int32(c), jnp.int32(C - 1), table, k, v,
            cfg=cfg, page_size=page)
        assert int(tok) == want[c + C - 1].argmax()
        assert cdf.shape == (1, PASSES) and rows.tolist() == [c + C]
        np.testing.assert_allclose(cdf[0], want_cdf[:, c + C - 1], atol=1e-5)
    tables = jnp.stack([table, jnp.zeros_like(table)])  # slot 1 idle: the scratch page
    for t in range(16, 24):
        nxt, k, v, cdf, rows = L.looped_decode_step(
            params, jnp.asarray([seq[t], 0]), jnp.asarray([t, 0]), tables, k, v,
            cfg=cfg, page_size=page)
        assert int(nxt[0]) == want[t].argmax()
        assert rows.tolist() == [t + 1, 0]  # the idle slot attends nothing that counts
        np.testing.assert_allclose(cdf[0], want_cdf[:, t], atol=1e-5)
    # plane r * L + i holds the reference's rotated keys and values of pass r, layer i
    kept = {}
    ref.forward(lm.params, jnp.asarray(seq), cfg, refc.mm_f32, keep=kept)
    for r in range(PASSES):
        for i in range(LAYERS):
            for pages, which in ((k, "k"), (v, "v")):
                got = np.asarray(pages[r * LAYERS + i, 1:1 + 24 // page]).reshape(24, 4, 16)
                np.testing.assert_allclose(got.transpose(1, 0, 2), kept[(r, i)][which],
                                           atol=2e-5, err_msg=f"{which} pass {r} layer {i}")


def test_one_pass_is_the_stack_itself_and_the_passes_add_no_parameter(lm):
    _, one, _ = seeded(total_ut_steps=1)
    assert L.param_shapes(one) == L.param_shapes(lm.cfg)
    assert L.planes(one) == LAYERS and L.planes(lm.cfg) == PASSES * LAYERS
    seq = jnp.asarray(np.random.RandomState(1).randint(1, VOCAB, size=(12,)))
    got, lam = ref.forward(lm.params, seq, one, refc.mm_f32)
    # the single-pass stack spelled out: every layer once, then the final norm
    x = ref.embed(lm.params["emb/word_emb"], seq)
    for i in range(LAYERS):
        x = ref.layer(x, ref.layer_params(lm.params, i), one, refc.mm_f32)
    x = ref.rms_norm(x, lm.params["final_norm/scale"], one["rms_eps"])
    np.testing.assert_allclose(got, refc.mm_f32(x, lm.params["head/w"]), rtol=1e-5, atol=1e-5)
    model = pt.build(lambda ids, labels: L.lm_forward(ids, labels, cfg=one), name="one_pass")
    ids = np.asarray(seq)[None]
    (_, _, logits), _ = model.apply(lm.variables, ids, ids)
    np.testing.assert_allclose(logits[0], got, rtol=1e-4, atol=1e-4)
    assert np.asarray(L.exit_cdf(lam)).tolist() == [[1.0]] * 12  # one pass takes the remainder


def test_the_exit_distribution_sums_to_one_and_threshold_one_takes_the_last_pass(lm):
    seq = jnp.asarray(np.random.RandomState(2).randint(1, VOCAB, size=(20,)))
    _, lam = ref.forward(lm.params, seq, lm.cfg, refc.mm_f32)
    p = np.asarray(ref.exit_distribution(lam))
    assert p.shape == (PASSES, 20) and (p > 0).all()
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    cdf = np.asarray(L.exit_cdf(lam))
    np.testing.assert_allclose(cdf, np.cumsum(p, 0).T, atol=1e-6)
    assert (L.exit_pass(cdf, 1.0) == PASSES - 1).all()
    # a lower threshold would let some tokens leave early: not built, refused by name
    assert (L.exit_pass(cdf, 0.5) < PASSES - 1).any()
    with pytest.raises(Exception, match="differs by token.*not built"):
        models.get_model("looped_lm", **dict(SMALL, early_exit_threshold=0.5))


def test_trainer_loss_and_the_gradient_of_a_shared_weight_are_the_references(lm):
    rng = np.random.RandomState(3)
    tok = rng.randint(1, VOCAB, size=(3, 13)).astype(np.int32)
    ids, labels = tok[:, :-1], tok[:, 1:]
    mean_loss = lambda p: ref.loss_sum(p, ids, labels, lm.cfg, refc.mm_f32) / labels.size
    want_loss, want_grad = jax.value_and_grad(mean_loss)(lm.params)
    trainer = pt.Trainer(lambda: lm.spec.model, lambda: pt.optimizer.SGD(learning_rate=1.0))
    # the step consumes the state it is handed: the Trainer gets a copy
    trainer.variables = trainer.exe.put(pt.framework.Variables(
        {k: jnp.array(v) for k, v in lm.stacked.items()}, {}))
    trainer.opt_state = trainer.exe.put(trainer.optimizer.create_state(trainer.variables.params))
    losses = []
    trainer.train(num_epochs=1, reader=lambda: iter([(ids, labels)]),
                  event_handler=lambda ev: losses.append(ev.metrics)
                  if isinstance(ev, pt.trainer.EndStepEvent) else None)
    np.testing.assert_allclose(np.asarray(losses[0]).reshape(-1)[0], want_loss, rtol=1e-5)
    step = {k: lm.stacked[k] - v for k, v in trainer.variables.params.items()}
    for name, g in want_grad.items():  # SGD at rate 1: the step is the gradient
        head, _, rest = name.partition("/")
        got = (step[f"layers/{rest}"][int(head[len("layer_"):])] if head.startswith("layer_")
               else step[name])
        np.testing.assert_allclose(got, g, rtol=2e-3, atol=2e-6, err_msg=name)
    # a layer's weight is used once a pass: its gradient is the sum over the
    # passes, far from any one pass's share
    assert float(jnp.abs(want_grad["layer_1/ffn/fc2/w"]).max()) > 0
    assert not np.any(np.asarray(want_grad["exit_gate/w"]))  # the loss does not read the gate


@pytest.mark.parametrize("T", [1, 8], ids=["step", "chunk"])
def test_the_head_split_kept_off_the_weights_changes_no_logit_and_no_gradient(lm, T, monkeypatch):
    """``block`` holds the flat q, k and v behind a barrier so that the head
    split stays on the activation: at one row a slot and at a chunk's rows
    the logits through the pages are the full forward pass's, and the
    training loss's gradient with respect to the stacked q weight is the
    reference's."""
    cfg, params = lm.cfg, lm.stacked
    monkeypatch.setattr(L, "sample_logits", lambda logits, *_: logits)  # the programs' logits
    seq = np.random.RandomState(6).randint(1, VOCAB, size=(16 + T,)).astype(np.int32)
    want = np.asarray(ref.forward(lm.params, jnp.asarray(seq), cfg, refc.mm_f32)[0])
    page, P, C = 4, 8, 8
    k_spec, v_spec = L.looped_cache_specs(cfg, num_pages=1 + P, page_size=page, dtype=jnp.float32)
    k, v = jnp.zeros(k_spec.shape), jnp.zeros(v_spec.shape)
    table = jnp.arange(1, 1 + P, dtype=jnp.int32)
    for c in range(0, 16, C):
        _, k, v, _, _ = L.looped_prefill_chunk(
            params, jnp.asarray(seq[c:c + C]), jnp.int32(c), jnp.int32(C - 1), table, k, v,
            cfg=cfg, page_size=page)
    if T == 1:
        got = L.looped_decode_step(
            params, jnp.asarray(seq[16:]), jnp.asarray([16]), table[None], k, v,
            cfg=cfg, page_size=page)[0][0]
    else:
        got = L.looped_prefill_chunk(
            params, jnp.asarray(seq[16:]), jnp.int32(16), jnp.int32(T - 1), table, k, v,
            cfg=cfg, page_size=page)[0]
    np.testing.assert_allclose(got, want[-1], rtol=1e-4, atol=1e-4)

    tok = np.random.RandomState(7).randint(1, VOCAB, size=(3, T + 5)).astype(np.int32)
    ids, labels = tok[:, :-1], tok[:, 1:]
    want_grad = jax.grad(lambda p: ref.loss_sum(p, ids, labels, cfg, refc.mm_f32) / labels.size)(
        lm.params)
    name = "layers/attn/q/w"
    loss = lambda w: lm.spec.model.apply(
        pt.framework.Variables(dict(params, **{name: w}), {}), ids, labels)[0][0]
    got_grad = jax.grad(loss)(params[name])
    assert float(jnp.abs(got_grad).max()) > 0
    for i in range(LAYERS):
        np.testing.assert_allclose(got_grad[i], want_grad[f"layer_{i}/attn/q/w"],
                                   rtol=2e-3, atol=2e-6, err_msg=f"layer {i}")


def test_the_model_is_in_the_registry_and_holds_bfloat16_by_default():
    spec = models.get_model("looped_lm", seq_len=8, vocab=97, d_model=32, d_inner=64,
                            num_heads=2, head_dim=16, n_layers=2, total_ut_steps=3)
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    cfg = spec.extra["cfg"]
    assert {v.dtype for v in variables.params.values()} == {jnp.dtype("bfloat16")}
    assert {k: v.shape for k, v in variables.params.items()} == L.param_shapes(cfg)
    (loss, _, _), _ = spec.model.apply(variables, ids, labels)
    assert np.isfinite(float(loss))
    # the layers' leaves are held stacked, each layer initialised by its own fans
    fc1 = np.asarray(variables.params["layers/ffn/fc1/w"], np.float32)
    assert fc1.shape == (2, 32, 64) and not np.array_equal(fc1[0], fc1[1])
    assert abs(fc1.std() / (2.0 / (32 + 64)) ** 0.5 - 1.0) < 0.1


def test_a_checkpoint_of_a_leaf_a_layer_is_stacked_at_load_and_refused_as_it_is(lm):
    loaded = L.stack_layers(dict(lm.params), lm.cfg)
    assert {k: v.shape for k, v in loaded.items()} == L.param_shapes(lm.cfg)
    np.testing.assert_array_equal(loaded["layers/attn/out/w"][2], lm.params["layer_2/attn/out/w"])
    with pytest.raises(Exception, match="holds its layers stacked.*stack_layers"):
        DecodeEngine(pt.framework.Variables(dict(lm.params), {}), lm.cfg,
                     decode=DecodeConfig(**DECODE_KW))


# -- through the engine ------------------------------------------------------

def test_served_tokens_are_the_references_and_the_engine_names_the_planes(lm):
    rng = np.random.RandomState(5)
    # six requests on three slots: slots are freed and taken again mid-run; the
    # 30- and 27-token prompts prefill (4 chunks) while the other slots decode
    cases = list(zip(prompts_of(rng, (5, 30, 9, 27, 3, 14)), (9, 6, 12, 5, 4, 7)))
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE_KW))
    try:
        outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
        name = eng._manifest_name()
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    assert eng.decode_step_cache_size() == 1 and eng.prefill_cache_size() == 1
    for (prompt, budget), out in zip(cases, outs):
        assert out.finish_reason == "length" and len(out.tokens) == budget
        gap = check.gap_sigmas(reference_rows(lm, prompt, out.tokens), out.tokens).max()
        assert gap < 1e-3, len(prompt)
    label = {"engine": eng.metrics.engine_label}
    reg = obs_metrics.default_registry()
    # 12 planes of a K and a V row of 64 float32
    assert reg.get("serving.decode.cache_bytes_per_token", label, default=None) == 12 * 64 * 4 * 2
    assert reg.get("serving.decode.loop.passes", label, default=None) == PASSES
    assert reg.get("serving.decode.loop.planes", label, default=None) == PASSES * LAYERS
    assert reg.get("serving.decode.pages_donated", label, default=None) == 1.0
    assert reg.get("serving.decode.pages_row_major", label, default=None) == 1.0
    # the prewarm manifest is keyed by the cache's planes: another pass count, another name
    assert name.startswith("looped_lm_decode_L12_")
    _, fewer, _ = seeded(total_ut_steps=2)
    eng2 = DecodeEngine(lm.variables, fewer, decode=DecodeConfig(**DECODE_KW))
    try:
        assert eng2._manifest_name().startswith("looped_lm_decode_L6_")
    finally:
        eng2.close()


def test_every_call_lands_the_loops_counts_on_its_span(lm):
    tracing.reset_tracing()
    tracing.enable_tracing()
    rng = np.random.RandomState(3)
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE_KW))
    try:
        for p, m in zip(prompts_of(rng, (19, 6)), (5, 4)):
            eng.infer(p, m)
    finally:
        eng.close()
    loop = [s for s in tracing.spans() if s.context.trace_id == eng._loop_trace.trace_id]
    steps = [s for s in loop if s.name == "serving.decode.model_step"]
    chunks = [s for s in loop if s.name == "serving.decode.prefill"]
    assert len(steps) >= 7 and len(chunks) == 4  # 19 tokens in three chunks of 8, then one
    for s in steps + chunks:
        assert s.attrs["loop_passes"] == PASSES and s.attrs["loop_planes"] == PASSES * LAYERS
        assert 1.0 < s.attrs["exit_mean_pass"] < PASSES  # seeded gates leave mass on every pass
    # one request at a time: a step attends the positions of its one decoding slot
    # a 19-token prompt: its first step writes position 19 and attends 20
    assert [s.attrs["live_rows"] for s in steps[:4]] == [20, 21, 22, 23]
    assert [s.attrs["live_rows"] for s in chunks] == [8, 16, 19, 6]
    assert not eng._chunk_extras


def test_a_shared_prefix_the_host_tier_and_a_handoff_work_on_the_planes(lm):
    rng = np.random.RandomState(6)
    stem = prompts_of(rng, (22,))[0]  # 5 full pages, a straddled chunk
    prompts = [np.concatenate([stem, tail]) for tail in prompts_of(rng, (3, 6, 2))]

    def served(engine_or_router, closing):
        try:
            first = engine_or_router.submit(prompts[0], 5).result(timeout=300)
            rest = [h.result(timeout=300)
                    for h in [engine_or_router.submit(p, 5) for p in prompts[1:]]]
            return [np.asarray(out.tokens) for out in [first] + rest]
        finally:
            closing()

    plain = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE_KW))
    want = served(plain, plain.close)
    for p, toks in zip(prompts, want):
        assert check.gap_sigmas(reference_rows(lm, p, toks), toks).max() < 1e-3
    tier = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        prefix_cache=True, host_tier_bytes=1 << 20, **DECODE_KW))
    got = served(tier, tier.close)
    tier.kv.assert_no_leaks()
    assert tier.metrics.prefix_hit_tokens_total >= 20
    assert all(np.array_equal(a, b) for a, b in zip(want, got))
    pre, dec = (DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE_KW))
                for _ in range(2))
    router = DisaggRouter([pre, dec], [PREFILL, DECODE], transport="serialized")
    got = served(router, lambda: router.close(30))
    assert router.handoffs_total == 3 and router.handoff_rejects_total == 0
    assert all(np.array_equal(a, b) for a, b in zip(want, got))
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_a_replica_group_serves_it_under_a_layout_for_its_names(lm):
    if len(jax.devices()) < 2:
        pytest.skip("needs two virtual CPU devices")
    from jax.sharding import PartitionSpec as P

    cols, rows = P(None, None, TP_AXIS), P(None, TP_AXIS, None)  # of a stack [L, in, out]
    rules = tuple((f"*/attn/{w}/w", cols) for w in "qkv") + (
        ("*/attn/out/w", rows), ("*/ffn/fc1/w", cols), ("*/ffn/gate/w", cols),
        ("*/ffn/fc2/w", rows))
    prompts = prompts_of(np.random.RandomState(7), (11, 4, 17))
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE_KW),
                       group=make_groups(2)[0], layout=GroupLayout(rules=rules, optional=()))
    try:
        outs = [h.result(timeout=300) for h in [eng.submit(p, 6) for p in prompts]]
        assert eng.decode_step_cache_size() == 1 and eng.tp_degree == 2
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    for p, out in zip(prompts, outs):
        assert check.gap_sigmas(reference_rows(lm, p, out.tokens), out.tokens).max() < 1e-3
    # the default layout's rules are another family's names: refused before any placement
    with pytest.raises(Exception, match="shard-dead-rule"):
        DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE_KW),
                     group=make_groups(2)[0])


@pytest.mark.parametrize("role", ["target", "draft"])
def test_a_draft_model_is_refused_by_name(lm, role):
    t = models.get_model("transformer_lm", seq_len=16, vocab=VOCAB, d_model=32, d_inner=64,
                         num_heads=2, n_layers=1, max_len=64)
    ids, labels = t.synth_batch(2, np.random.RandomState(0))
    plain = (t.model.init(0, ids, labels), t.extra["cfg"])
    looped = (lm.variables, lm.cfg)
    (variables, cfg), (dvars, dcfg) = (looped, plain) if role == "target" else (plain, looped)
    with pytest.raises(Exception, match="a draft model cannot be used.*stack runs several "
                                        "passes, each with K and V pages of its own"):
        DecodeEngine(variables, cfg, decode=DecodeConfig(spec_tokens=2, **DECODE_KW),
                     draft_variables=dvars, draft_cfg=dcfg)
    assert models.serving_programs(lm.cfg).verify_step is None


@pytest.mark.parametrize("name", ["looped_lm", "transformer_lm"])
def test_a_chunk_that_does_not_divide_the_context_overhangs_onto_the_scratch_page(lm, name):
    """max_context 64 in chunks of 24: the last chunk of a 50-token prompt
    covers positions 48-71. The table row a chunk is handed is lengthened by
    scratch entries to 72 positions, so the overhang lands on the scratch
    page and the served tokens are those of chunks of 8."""
    if name == "looped_lm":
        variables, cfg = lm.variables, lm.cfg
    else:
        t = models.get_model(name, seq_len=16, vocab=VOCAB, d_model=32, d_inner=64,
                             num_heads=2, n_layers=2, max_len=64)
        ids, labels = t.synth_batch(2, np.random.RandomState(0))
        variables, cfg = t.model.init(0, ids, labels), t.extra["cfg"]
    cases = list(zip(prompts_of(np.random.RandomState(9), (50, 7, 26, 59)), (8, 6, 9, 5)))
    got = {}
    for chunk in (8, 24):
        eng = DecodeEngine(variables, cfg, decode=DecodeConfig(**dict(DECODE_KW,
                                                                      prefill_chunk=chunk)))
        try:
            got[chunk] = [np.asarray(h.result(timeout=300).tokens)
                          for h in [eng.submit(p, m) for p, m in cases]]
            assert eng.prefill_cache_size() == 1 and eng._chunk_table_pad == (chunk == 24) * 2
        finally:
            eng.close()
        eng.kv.assert_no_leaks()
    assert all(np.array_equal(a, b) for a, b in zip(got[8], got[24]))


def test_a_state_cache_still_asks_for_a_context_of_whole_chunks():
    r = models.get_model("retention_lm", seq_len=16, vocab=VOCAB, d_model=32, d_inner=64,
                         num_heads=2, head_dim=16, n_layers=1, max_len=64)
    ids, labels = r.synth_batch(2, np.random.RandomState(0))
    with pytest.raises(Exception, match=r"max_context \(64\) must be a multiple of prefill_chunk "
                                        r"\(24\).*recurrent state"):
        DecodeEngine(r.model.init(0, ids, labels), r.extra["cfg"],
                     decode=DecodeConfig(**dict(DECODE_KW, prefill_chunk=24)))
