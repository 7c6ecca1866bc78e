"""``serving.DecodeEngine`` over a model whose cache is one page array of
latent rows and whose FFNs are a share of an expert layer
(``models/latent_moe_lm.py``): what it serves is the plain reference's full
pass, through admission, chunked prefill beside decoding slots, a shared
prefix with its copy-on-write and a recovered step fault; it owns its one
page array as it owns a K and V pair; every call's expert counts land on its
span; and it refuses what names a K and a V page, by name."""

import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import models, tracing
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.resilience import faults
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.disagg import PREFILL, DisaggRouter
from paddle_tpu.serving.host_tier import HostPagePool
from paddle_tpu.serving.shardgroup import make_groups

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import check  # noqa: E402
from test_serving_decode import _ConsumedSpy  # noqa: E402  (counts cache arrays left alive)
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import latent_moe_lm as ref  # noqa: E402
from benchmarks.tiny_experts import as_checkpoint  # noqa: E402

VOCAB = 97
DECODE = dict(max_slots=3, page_size=4, max_context=64, prefill_chunk=8)
YARN = dict(type="deepseek_yarn", factor=40, original_max_position_embeddings=16,
            beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)


@pytest.fixture(scope="module")
def lm():
    spec = models.get_model(
        "latent_moe_lm", seq_len=16, vocab=VOCAB, d_model=64, num_heads=4, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32, d_inner=96, moe_d_inner=32, n_layers=3,
        num_experts=8, experts_per_token=2, experts_held=(2, 4), rope_scaling=YARN,
        param_dtype="float32", compute_dtype="float32")
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    return types.SimpleNamespace(variables=variables, cfg=spec.extra["cfg"])


def gap_to_reference(lm, prompt, tokens) -> float:
    """How far, in standard deviations of a position's logits, the served
    tokens lie below the best of the reference's one full pass over prompt
    and served tokens (``check.gap_sigmas``: what decides ``correct``)."""
    ids = np.concatenate([prompt, tokens])[None]
    params = {k: jnp.asarray(v) for k, v in lm.variables.params.items()}
    logits = np.asarray(ref.logits_fn(as_checkpoint(params, (2, 4)), ids, lm.cfg,
                                      refc.mm_f32))[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(check.gap_sigmas(rows, tokens).max())


def cases_of(rng):
    # six requests on three slots: slots are freed and taken again mid-run; the
    # 30- and 27-token prompts prefill (4 chunks) while the other slots decode,
    # past the YaRN's original 16 positions
    return [(rng.randint(1, VOCAB, size=(n,)).astype(np.int32), m)
            for n, m in [(5, 9), (30, 6), (9, 12), (27, 5), (3, 4), (14, 7)]]


def test_served_tokens_are_the_references_through_admission_prefill_and_a_step_fault(lm):
    cases = cases_of(np.random.RandomState(5))
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        with faults.injected(faults.FaultSpec(faults.DECODE_STEP, "error", after=3, times=1)):
            outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    assert snap["step_faults_total"] == 1 and snap["recovered_total"] >= 1
    assert eng.decode_step_cache_size() == 1 and eng.prefill_cache_size() == 1
    for (prompt, budget), out in zip(cases, outs):
        assert out.finish_reason == "length" and len(out.tokens) == budget
        assert gap_to_reference(lm, prompt, out.tokens) < 1e-3, len(prompt)
    label = {"engine": eng.metrics.engine_label}
    reg = obs_metrics.default_registry()
    # 3 layers of a 128-lane float32 row: the latent's 32, the rotary key's 8, zeros
    assert reg.get("serving.decode.cache_bytes_per_token", label, default=None) == 3 * 128 * 4
    assert reg.get("serving.decode.moe.experts_held", label, default=None) == 4
    assert reg.get("serving.decode.moe.router_width", label, default=None) == 8
    assert reg.get("serving.decode.pages_donated", label, default=None) == 1.0


def test_the_engine_serves_through_the_kernels_and_its_spans_say_so(lm, monkeypatch):
    """As on a TPU: the step and the chunk attend through
    ``latent_attend_step`` / ``latent_attend_chunk`` (interpreted here) over
    the pages a sequence holds. The served tokens are the reference's still,
    and the step's and the chunk's span carry what the kernel read beside
    what a gather would have."""
    from paddle_tpu.models import latent_moe_lm, transformer_lm

    for module in (latent_moe_lm, transformer_lm):  # the rule, where each asks it
        monkeypatch.setattr(module, "step_attends_in_kernel", lambda *a: True)
    tracing.enable_tracing()
    tracing.reset_tracing()
    cases = cases_of(np.random.RandomState(5))[:4]
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        outs = [h.result(timeout=300) for h in [eng.submit(p, m) for p, m in cases]]
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    for (prompt, budget), out in zip(cases, outs):
        assert len(out.tokens) == budget and gap_to_reference(lm, prompt, out.tokens) < 1e-3
    spans = tracing.spans_for_trace(eng._loop_trace.trace_id)
    steps = [s.attrs for s in spans if s.name == "serving.decode.model_step"]
    chunks = [s.attrs for s in spans if s.name == "serving.decode.prefill"]
    page_bytes = 4 * 3 * 128 * 4  # a page of 4 rows over 3 planes of 128 float32
    assert steps and {a["attend_kernel"] for a in steps} == {1}
    assert all(0 < a["attend_live_pages"] < a["attend_table_pages"] == 3 * 16 for a in steps)
    # 5-, 30-, 9- and 27-token prompts in chunks of 8: a chunk reads the pages up to its end
    assert sorted(a["attend_live_pages"] for a in chunks) == sorted(
        8 * (c + 1) // 4 for n in (5, 30, 9, 27) for c in range(-(-n // 8)))
    assert {(a["attend_kernel"], a["attend_table_pages"], a["attend_page_bytes"])
            for a in chunks} == {(1, 16, page_bytes)}
    assert {a["attend_page_bytes"] for a in steps} == {page_bytes}


def test_a_shared_prefix_is_adopted_and_copied_on_write_in_the_one_page_array(lm):
    rng = np.random.RandomState(6)
    stem = rng.randint(1, VOCAB, size=(22,)).astype(np.int32)  # 5 full pages, a straddled chunk
    prompts = [np.concatenate([stem, rng.randint(1, VOCAB, size=(n,)).astype(np.int32)])
               for n in (3, 6, 2)]
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(prefix_cache=True, **DECODE))
    try:
        first = eng.infer(prompts[0], 5)
        rest = [h.result(timeout=300) for h in [eng.submit(p, 5) for p in prompts[1:]]]
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    eng.kv.assert_no_leaks()
    assert eng.metrics.prefix_hit_tokens_total >= 2 * 20 and eng.metrics.cow_copies_total >= 1
    for p, out in zip(prompts, [first] + rest):
        assert gap_to_reference(lm, p, out.tokens) < 1e-3


@pytest.mark.parametrize("jit", ["_step", "_prefill"])
def test_every_jit_that_writes_the_latent_pages_consumes_the_array_it_is_handed(lm, jit):
    rng = np.random.RandomState(2)
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        spy = _ConsumedSpy(getattr(eng, jit))
        setattr(eng, jit, spy)
        for n, m in [(11, 4), (4, 6)]:
            eng.infer(rng.randint(1, VOCAB, size=(n,)).astype(np.int32), m)
    finally:
        eng.close()
    assert spy.calls >= 2, f"{jit} never ran"
    assert spy.kept == 0, f"{jit} left {spy.kept} page array(s) alive"
    assert len(eng._cache) == 1 and eng._cache[0].shape == (3, 1 + 3 * 16, 4, 128)


def test_every_call_lands_its_expert_counts_on_its_span_and_under_the_counter(lm):
    tracing.reset_tracing()
    tracing.enable_tracing()
    rng = np.random.RandomState(3)
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        for n, m in [(19, 5), (6, 4)]:
            eng.infer(rng.randint(1, VOCAB, size=(n,)).astype(np.int32), m)
    finally:
        eng.close()
    loop = [s for s in tracing.spans() if s.context.trace_id == eng._loop_trace.trace_id]
    steps = [s for s in loop if s.name == "serving.decode.model_step"]
    chunks = [s for s in loop if s.name == "serving.decode.prefill"]
    assert len(steps) >= 7 and len(chunks) == 4  # 19 tokens in three chunks of 8, then one
    for s in steps + chunks:
        tokens = 3 if s.name.endswith("model_step") else 8  # all slots; a whole chunk
        assert 0 <= s.attrs["moe_experts_hit"] <= 2 * 4
        assert s.attrs["moe_max_load"] <= tokens
        assert s.attrs["moe_pairs"] <= tokens * 2 * 2  # tokens x experts a token x expert layers
        assert s.attrs["moe_max_load"] <= s.attrs["moe_pairs"]
    total = sum(s.attrs["moe_pairs"] for s in steps + chunks)
    assert total > 0 and not eng._chunk_extras
    assert obs_metrics.default_registry().get(
        "serving.decode.moe.pairs_total", {"engine": eng.metrics.engine_label},
        default=None) >= total  # warm-up's two calls are counted too, on no span


# -- what names a K and a V page is refused, by name ---------------------------

@pytest.mark.parametrize("feature, kwargs", [
    ("the host tier", dict(decode=DecodeConfig(prefix_cache=True, host_tier_bytes=1 << 20,
                                               **DECODE))),
    ("the host tier", dict(decode=DecodeConfig(prefix_cache=True, **DECODE),
                           host_tier=HostPagePool(1 << 20, 4))),
    ("a draft model", dict(decode=DecodeConfig(**DECODE), draft_variables="same")),
    ("a replica group", dict(decode=DecodeConfig(**DECODE), group="two")),
])
def test_the_engine_refuses_what_names_a_k_and_a_v_page(lm, feature, kwargs):
    if kwargs.get("draft_variables") == "same":
        kwargs = dict(kwargs, draft_variables=lm.variables)
    if kwargs.get("group") == "two":
        kwargs = dict(kwargs, group=make_groups(2)[0])
    with pytest.raises(Exception, match=f"{feature} cannot be used.*latent attention.*K and a V"):
        DecodeEngine(lm.variables, lm.cfg, **kwargs)


def test_disaggregated_handoff_is_refused(lm):
    engines = [DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
               for _ in range(2)]
    try:
        with pytest.raises(Exception, match="disaggregated handoff cannot be used.*latent attention"):
            DisaggRouter(engines, [PREFILL, "decode"])
        with pytest.raises(Exception, match="disaggregated handoff cannot be used"):
            engines[1].adopt_handoff(None)
    finally:
        for e in engines:
            e.close()


def test_transformer_lm_still_has_no_experts_on_the_paged_path():
    from paddle_tpu.models import transformer_lm as t

    with pytest.raises(Exception, match="latent_moe_lm"):
        t._paged_enforce({"moe_experts": 4}, 0.0, None)
