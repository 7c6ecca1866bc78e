"""paddle_tpu.serving.decode — continuous-batching decode engine tests.

The acceptance contract from the continuous-batching PR: mixed-length
requests admitted/evicted at iteration granularity produce tokens
*exactly* equal to the static :func:`models.transformer_lm.generate`
path, and the jitted decode step compiles ONCE — the executable-cache
size stays flat as requests of different prompt lengths and budgets
enter and leave.  Also covered: preempt/resume continuation under a
starved page pool, cancel mid-generation, eos stopping, the bf16
``cache_dtype`` plumbing, and per-token deadline prediction feeding the
admission controller (satellite of PR 8's latency histograms), and the
ownership rule of ISSUE 26: every jit that writes the page arrays
consumes the arrays it is handed.
"""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import models
from paddle_tpu.models.transformer_lm import generate
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.serving import (
    AdmissionRejected,
    DecodeConfig,
    DecodeCostModel,
    DecodeEngine,
    ServingConfig,
    TenantConfig,
)

VOCAB = 97


@pytest.fixture(scope="module")
def lm():
    """Tiny LM + params + greedy reference outputs for a mixed-length
    request set sized to force page contention (the expensive part is the
    per-(Tp, N)-shape generate() compiles, done once here)."""
    spec = models.get_model("transformer_lm", seq_len=64, vocab=VOCAB,
                            d_model=32, d_inner=64, num_heads=4, n_layers=2)
    cfg = spec.extra["cfg"]
    rng = np.random.RandomState(1)
    variables = spec.model.init(0, *spec.synth_batch(2, rng))
    cases = []
    for _ in range(6):
        tp = int(rng.randint(4, 12))
        n = int(rng.randint(12, 24))
        prompt = rng.randint(1, VOCAB, size=(tp,)).astype(np.int32)
        ref = np.asarray(generate(variables, jnp.asarray(prompt[None]),
                                  n, cfg))[0]
        cases.append((prompt, n, ref))
    # the tiny random model repeats itself (case 0 starts 46, 46, 46, ...),
    # so an eos picked by position alone may already be the first token:
    # stop on the first token, past the first, that is new where it occurs
    ref0 = cases[0][2]
    eos_at = next((i for i in range(1, len(ref0)) if ref0[i] not in ref0[:i]), None)
    if eos_at is None:
        raise RuntimeError(
            f"reference {ref0.tolist()} never produces a new token after its "
            "first: test_eos_stops_early has no eos to stop on")
    return types.SimpleNamespace(cfg=cfg, variables=variables, cases=cases,
                                 eos_at=eos_at)


@pytest.fixture(scope="module")
def eng(lm):
    """One warmed engine over a starved page pool (13 usable pages vs
    ~21 needed by three fully-grown slots), shared across the tests —
    metrics/counters only ever grow, so later tests must not assert
    equality on totals."""
    engine = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        max_slots=3, page_size=4, max_context=40, prefill_chunk=8,
        num_pages=14))
    yield engine
    engine.close()
    engine.kv.assert_no_leaks()


def test_mixed_lengths_exact_and_compile_once(lm, eng):
    """The PR's acceptance criterion: continuous batching under slot and
    page contention reproduces generate() token-for-token, with the step
    executable compiled exactly once (admit/evict/preempt of requests
    with six different (prompt_len, budget) shapes adds no entries)."""
    assert eng.decode_step_cache_size() == 1  # warmup compile only
    handles = [eng.submit(p, n) for p, n, _ in lm.cases]
    outs = [h.result(timeout=300) for h in handles]
    for (prompt, n, ref), out in zip(lm.cases, outs):
        assert np.array_equal(out.tokens, ref), (
            f"tokens diverged from generate() for Tp={len(prompt)} N={n}")
        assert out.finish_reason == "length"
        assert out.prompt_len == len(prompt)
    snap = eng.metrics.snapshot()
    # the pool is starved by construction, so iteration-level eviction
    # (preempt) and resume both fired — and every resumed request above
    # still matched the reference exactly
    assert snap["preempted_total"] >= 1
    assert snap["resumed_total"] == snap["preempted_total"]
    assert eng.decode_step_cache_size() == 1
    assert eng.prefill_cache_size() == 1


def test_cancel_mid_generation(lm, eng):
    h = eng.submit(np.arange(1, 6, dtype=np.int32), 30)  # 5 + 30 <= 40
    deadline = time.monotonic() + 60
    while len(h._req.generated) < 3:
        assert time.monotonic() < deadline, "no tokens generated"
        time.sleep(0.005)
    h.cancel()
    out = h.result(timeout=60)
    assert out.finish_reason == "cancelled"
    assert 0 < len(out.tokens) < 30


def test_submit_validation(lm, eng):
    with pytest.raises(Exception):
        eng.submit(lm.cases[0][0], 1000)  # prompt + budget > max_context
    with pytest.raises(Exception):
        eng.submit(np.zeros((0,), np.int32), 4)


def test_eos_stops_early(lm):
    prompt, n, ref = lm.cases[0]
    eos = int(ref[lm.eos_at])
    engine = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        max_slots=2, page_size=8, max_context=64, prefill_chunk=8,
        eos_id=eos))
    try:
        out = engine.infer(prompt, n)
        assert out.finish_reason == "eos"
        assert np.array_equal(out.tokens, ref[:lm.eos_at + 1])  # eos included
    finally:
        engine.close()
    engine.kv.assert_no_leaks()


def test_chunks_go_out_behind_the_step_and_first_tokens_behind_the_next(lm):
    """While others decode, an iteration enqueues its step first and its
    prefill chunk behind it, before it waits for the step's tokens; a last
    chunk's token is read one iteration on, with the next step (and chunk)
    already queued behind that chunk. So no iteration that carries a chunk
    leaves the device waiting for the host. The request decodes from the
    step after, or ends there on a budget of one; every output stays
    generate()'s."""
    engine = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        max_slots=2, page_size=8, max_context=64, prefill_chunk=8))
    order = []
    step, chunk, land = engine._step, engine._prefill, engine._land_first_token

    def spy(name, fn):
        def call(*args):
            order.append(name)
            return fn(*args)
        return call

    def spy_land(req, in_step):
        order.append("first token in step" if in_step else "first token")
        return land(req, in_step)

    engine._step, engine._prefill = spy("step", step), spy("chunk", chunk)
    engine._land_first_token = spy_land
    (p0, n0, r0), (p1, _, r1) = lm.cases[:2]
    try:
        h0 = engine.submit(p0, n0)
        deadline = time.monotonic() + 60
        while len(h0._req.generated) < 2:
            assert time.monotonic() < deadline, "no tokens generated"
            time.sleep(0.002)
        h1 = engine.submit(p1, 1)  # ends on its first token, inside h0's step
        h2 = engine.submit(p1, 5)  # takes the slot h1 leaves
        outs = [h.result(timeout=300) for h in (h0, h1, h2)]
    finally:
        engine.close()
    engine.kv.assert_no_leaks()
    for out, ref in zip(outs, (r0, r1[:1], r1[:5])):
        assert np.array_equal(out.tokens, ref)
    first = order.index("step")
    # h0 had no step to hide behind: its chunks, then its token at once
    assert set(order[:first]) == {"chunk", "first token"} and order[first - 1] == "first token"
    rest = order[first:]
    assert rest.count("first token in step") == 2 and "first token" not in rest
    for i, what in enumerate(rest):
        if what != "step":  # behind a step: the chunks, then an earlier chunk's token
            assert rest[i - 1] in ("step", "chunk")
            assert what == "chunk" or rest[i + 1:i + 2] != ["chunk"]
    # the token is of a chunk that went out behind an EARLIER step
    for i in [i for i, what in enumerate(rest) if what == "first token in step"]:
        this_step = max(j for j in range(i) if rest[j] == "step")
        assert "chunk" in rest[:this_step]


@pytest.mark.parametrize("spec_tokens", [0, 3], ids=["plain", "verify"])
def test_gathered_bookings_keep_every_sample_every_token_and_every_step(lm, spec_tokens):
    """A turn books its tokens in one stretch before it appends them: each
    request's waterfall still holds every generated token once (the one it
    finishes on too), one TTFT and ``tokens - 1`` TPOT samples, the labelled
    histograms count what the waterfalls hold, a tap on the instance's
    ``record_step`` still sees every step, and the tokens are generate()'s."""
    from paddle_tpu.tracing import waterfall

    kw = dict(draft_variables=lm.variables, draft_cfg=lm.cfg) if spec_tokens else {}
    engine = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(
        max_slots=3, page_size=4, max_context=40, prefill_chunk=8, num_pages=14,
        spec_tokens=spec_tokens), **kw)
    tapped = []
    record_step = engine.metrics.record_step

    def tap(active, max_slots, seconds, new_tokens):
        tapped.append(new_tokens)
        return record_step(active, max_slots, seconds, new_tokens)

    engine.metrics.record_step = tap
    try:
        known = set(waterfall.rids())
        handles = [engine.submit(p, n) for p, n, _ in lm.cases]
        rids = [r for r in waterfall.rids() if r not in known]
        outs = [h.result(timeout=300) for h in handles]
        snap = engine.metrics.snapshot()
    finally:
        engine.close()
    engine.kv.assert_no_leaks()
    assert len(rids) == len(lm.cases)
    for (prompt, n, ref), out, rid in zip(lm.cases, outs, rids):
        assert np.array_equal(out.tokens, ref)
        doc = waterfall.doc(rid)
        assert doc["finished"] and doc["reason"] == "length" and doc["ttft_s"] >= 0.0
        assert doc["tokens"] == n and len(doc["tpot_s"]) == n - 1
        landings = [e["n"] for e in doc["events"] if e["n"] > 0]
        # the token a request finishes on is booked before the append that
        # finishes it: the last landing comes right before the finish
        assert sum(landings) == n and doc["events"][-1]["phase"] == "finish"
        assert doc["events"][-2]["n"] > 0
    n_tokens = sum(n for _, n, _ in lm.cases)
    assert snap["ttft_observed_total"] == len(lm.cases)
    assert snap["tpot_samples_total"] == n_tokens - len(lm.cases)
    reg = obs_metrics.default_registry()
    labels = {"engine": engine.metrics.engine_label, "cls": "interactive"}
    assert reg.histogram_snapshot("serving.decode.tpot_seconds", labels)["count"] == \
        n_tokens - len(lm.cases)
    assert reg.histogram_snapshot("serving.decode.ttft_seconds", labels)["count"] == len(lm.cases)
    assert len(tapped) == snap["steps_total"] and sum(tapped) == snap["tokens_total"]
    # a verify step counts the tokens it lands before it appends them
    assert snap["tokens_total"] <= n_tokens
    assert reg.histogram_snapshot("serving.decode.prefill_chunk_seconds", {
        "engine": engine.metrics.engine_label}) is None
    assert snap["prefill_chunks_total"] >= len(lm.cases)


def test_cache_dtype_bf16(lm):
    """Satellite: cache_dtype flows ServingConfig -> engine, and the
    DecodeConfig override wins; decode still runs end to end on a bf16
    cache (lower-precision KV, full-precision attention math)."""
    engine = DecodeEngine(
        lm.variables, lm.cfg,
        config=ServingConfig(cache_dtype=jnp.float32),
        decode=DecodeConfig(max_slots=2, page_size=8, max_context=64,
                            prefill_chunk=8, cache_dtype=jnp.bfloat16))
    try:
        assert engine._cache[0].dtype == jnp.bfloat16
        assert engine._cache[1].dtype == jnp.bfloat16
        out = engine.infer(lm.cases[1][0], 8)
        assert out.finish_reason == "length" and len(out.tokens) == 8
    finally:
        engine.close()
    engine.kv.assert_no_leaks()


def test_cost_model_math():
    cold = DecodeCostModel()
    assert cold.estimate(2, 10) is None  # cold -> admission falls back
    cm = DecodeCostModel(step_s=0.01, chunk_s=0.05)
    # 3 chunks + 20 steps + 4 queued iterations ahead
    assert cm.estimate(3, 20, queue_cost=4) == pytest.approx(
        3 * 0.05 + 20 * 0.01 + 4 * 0.01)
    cm2 = DecodeCostModel(alpha=0.5, step_s=0.1)
    cm2.observe_step(0.2)
    assert cm2.snapshot()["step_s"] == pytest.approx(0.15)
    # no chunk observations: chunk cost falls back to step cost
    assert cm2.estimate(1, 1) == pytest.approx(0.15 * 2)


def test_per_token_deadline_admission(lm):
    """Satellite: admission predicts service latency from per-token
    decode cost x the request's token budget (not whole-request latency
    histograms), so an infeasible (deadline, max_new_tokens) pair is
    shed at submit; a cold cost model admits everything."""
    engine = DecodeEngine(
        lm.variables, lm.cfg,
        config=ServingConfig(admission=True, tenants=[TenantConfig("t")]),
        decode=DecodeConfig(max_slots=2, page_size=8, max_context=512,
                            prefill_chunk=8, warmup=False))
    try:
        prompt = lm.cases[0][0]
        # the wiring itself: chunks * chunk_s + budget * step_s
        engine.cost = DecodeCostModel(step_s=10.0, chunk_s=10.0)
        fake = types.SimpleNamespace(prompt=prompt, mnt=30)
        assert engine._request_cost(fake) == pytest.approx(
            engine._n_chunks(len(prompt)) * 10.0 + 30 * 10.0)
        # 30 tokens x 10s/token >> 1s deadline -> shed before queueing
        with pytest.raises(AdmissionRejected) as ei:
            engine.submit(prompt, 30, deadline_s=1.0, tenant="t")
        assert ei.value.reason == "deadline_unmeetable"
        # a 4-token budget under the same per-token cost is feasible
        h = engine.submit(prompt, 4, deadline_s=3600.0, tenant="t")
        h.cancel()
        # cold model -> no prediction -> admit even tight deadlines
        engine.cost = DecodeCostModel()
        h2 = engine.submit(prompt, 30, deadline_s=3600.0, tenant="t")
        h2.cancel()
    finally:
        engine.close()


# ---- speculative decoding (ISSUE 12) ---------------------------------------


def test_speculative_self_draft_exact_and_compile_once(lm):
    """Draft-and-verify under mixed-length traffic on the starved pool:
    outputs exactly match generate() and BOTH jitted paths stay
    compile-once — ``paged_verify_step``'s [max_slots, spec_tokens + 1]
    block shape is static config, so admit/evict/preempt of requests
    with six (prompt_len, budget) shapes adds no executables."""
    engine = DecodeEngine(
        lm.variables, lm.cfg,
        decode=DecodeConfig(max_slots=3, page_size=4, max_context=40,
                            prefill_chunk=8, num_pages=14, spec_tokens=3),
        draft_variables=lm.variables, draft_cfg=lm.cfg)
    try:
        assert engine.verify_step_cache_size() == 1  # warmup compile only
        handles = [engine.submit(p, n) for p, n, _ in lm.cases]
        outs = [h.result(timeout=300) for h in handles]
        for (prompt, n, ref), out in zip(lm.cases, outs):
            assert np.array_equal(out.tokens, ref), (
                f"speculative decode diverged for Tp={len(prompt)} N={n}")
        snap = engine.metrics.snapshot()
        assert snap["verify_steps_total"] >= 1
        # self-draft: almost every in-budget draft is accepted, so each
        # verify step lands more than one token on average
        assert engine.metrics.accepted_tokens_per_verify_step() > 1.0
        assert 0.0 < snap["spec_accept_rate"] <= 1.0
        assert engine.verify_step_cache_size() == 1
        assert engine.decode_step_cache_size() == 1
    finally:
        engine.close()
    engine.kv.assert_no_leaks()


def test_speculative_divergent_draft_still_exact(lm):
    """Token-exactness must not depend on draft quality: a separately
    seeded 1-layer draft proposes mostly-wrong tokens, the acceptance
    rule rejects them, and the output still equals generate()."""
    dspec = models.get_model("transformer_lm", seq_len=64, vocab=VOCAB,
                             d_model=16, d_inner=32, num_heads=2, n_layers=1)
    drng = np.random.RandomState(99)
    draft_vars = dspec.model.init(1, *dspec.synth_batch(2, drng))
    engine = DecodeEngine(
        lm.variables, lm.cfg,
        decode=DecodeConfig(max_slots=3, page_size=4, max_context=40,
                            prefill_chunk=8, num_pages=14, spec_tokens=3),
        draft_variables=draft_vars, draft_cfg=dspec.extra["cfg"])
    try:
        handles = [engine.submit(p, n) for p, n, _ in lm.cases[:4]]
        outs = [h.result(timeout=300) for h in handles]
        for (prompt, n, ref), out in zip(lm.cases[:4], outs):
            assert np.array_equal(out.tokens, ref), (
                f"divergent-draft decode diverged for Tp={len(prompt)}")
        # rejection-heavy, but each verify step still lands its one
        # target-sampled token
        assert engine.metrics.snapshot()["verify_steps_total"] >= 1
        assert engine.metrics.accepted_tokens_per_verify_step() >= 1.0
    finally:
        engine.close()
    engine.kv.assert_no_leaks()


@pytest.mark.parametrize("path", ["verify", "step"])
@pytest.mark.parametrize("variant", [
    {},
    {"pos_encoding": "rope"},
    {"num_kv_heads": 2},
    {"attention_window": 3},
    {"num_kv_heads": 2, "pos_encoding": "rope", "ffn_activation": "swiglu",
     "attention_window": 4},
], ids=["sinusoid", "rope", "gqa", "window", "modern"])
def test_verify_step_exact_across_model_configs(variant, path):
    """The paged programs must reproduce generate() under every cache
    layout decode_block and the paged attend serve: additive sinusoid PE,
    per-position RoPE, the H_kv-head GQA cache, sliding-window masking,
    and all of them at once — paged_verify_step behind a self-draft
    (``verify``), and paged_prefill_chunk + paged_decode_step alone
    (``step``)."""
    spec = models.get_model("transformer_lm", seq_len=48, vocab=VOCAB,
                            d_model=32, d_inner=64, num_heads=4, n_layers=2,
                            **variant)
    cfg = spec.extra["cfg"]
    rng = np.random.RandomState(3)
    variables = spec.model.init(0, *spec.synth_batch(2, rng))
    cases = []
    for tp in (5, 9):
        prompt = rng.randint(1, VOCAB, size=(tp,)).astype(np.int32)
        ref = np.asarray(generate(variables, jnp.asarray(prompt[None]),
                                  10, cfg))[0]
        cases.append((prompt, ref))
    speculative = path == "verify"
    engine = DecodeEngine(
        variables, cfg,
        decode=DecodeConfig(max_slots=2, page_size=4, max_context=32,
                            prefill_chunk=8, num_pages=12, spec_tokens=3),
        **({"draft_variables": variables, "draft_cfg": cfg} if speculative else {}))
    try:
        handles = [engine.submit(p, 10) for p, _ in cases]
        outs = [h.result(timeout=300) for h in handles]
        for (prompt, ref), out in zip(cases, outs):
            assert np.array_equal(out.tokens, ref), (
                f"{path} diverged for variant={variant} "
                f"Tp={len(prompt)}")
        if speculative:
            assert engine.metrics.snapshot()["verify_steps_total"] >= 1
            assert engine.verify_step_cache_size() == 1
        else:
            assert engine.decode_step_cache_size() == 1
    finally:
        engine.close()
    engine.kv.assert_no_leaks()


def test_cost_model_speculative_math():
    """Under speculation one admission 'iteration' is a verify step
    landing accepted_per_step tokens; prefill falls back to verify cost
    when no chunk observations exist; observe_verify feeds both EMAs."""
    cm = DecodeCostModel(chunk_s=0.05, verify_s=0.01, accepted_per_step=2.0)
    assert cm.estimate(3, 20, queue_cost=4) == pytest.approx(
        3 * 0.05 + (20 / 2.0) * 0.01 + 4 * 0.01)
    # no accepted-tokens observation yet: assume 1 token/iteration;
    # no chunk observation: chunk cost falls back to verify cost
    assert DecodeCostModel(verify_s=0.1).estimate(1, 2) == pytest.approx(
        1 * 0.1 + 2 * 0.1)
    cm2 = DecodeCostModel(alpha=0.5, verify_s=0.1, accepted_per_step=1.0)
    cm2.observe_verify(0.2, 3.0)
    snap = cm2.snapshot()
    assert snap["verify_s"] == pytest.approx(0.15)
    assert snap["accepted_per_step"] == pytest.approx(2.0)
    # the non-speculative estimate path is untouched when verify_s is cold
    assert cm2.snapshot()["step_s"] is None


# ---- the engine owns its KV pages (ISSUE 26) --------------------------------

# every jit that returns a new version of a page array, by the engine that
# reaches it: the plain engine the one-token step, the self-draft engine the
# draft and verify steps; both the chunked prefill and the prefix cache's copy
_WRITE_JITS = [("plain", "_step"), ("plain", "_prefill"),
               ("plain", "_implant_page"), ("plain", "_copy_page"),
               ("spec", "_draft_step"), ("spec", "_draft_prefill"),
               ("spec", "_verify"), ("spec", "_copy_page_d")]


class _ConsumedSpy:
    """Stands in for one write-jit: runs the real call, then counts the page
    arrays ([L, pages, page_size, H_kv * dh], or a state model's 5-d states:
    the only arguments of 4 axes or more) it was handed that are still
    alive. Runs on the loop thread, so it only counts; the test asserts."""

    def __init__(self, fn):
        self.fn, self.calls, self.kept = fn, 0, 0

    def __call__(self, *args):
        out = self.fn(*args)
        pages = [a for a in args if getattr(a, "ndim", 0) >= 4]
        self.calls += bool(pages)  # a call that found none proves nothing
        self.kept += sum(not p.is_deleted() for p in pages)
        return out

    def __getattr__(self, name):  # _cache_size
        return getattr(self.fn, name)


@pytest.fixture(scope="module", params=["single", "group2"])
def owned(request, lm):
    """For one placement (one device, or a two-device tp group): a plain and
    a self-draft engine, prefix cache on, a spy on each write-jit, after
    traffic that reaches them all: mixed lengths at once, then two prompts
    sharing a 14-token prefix one after the other (3 pages hit, not
    chunk-aligned: the continuation chunk copies on write)."""
    group = None
    if request.param == "group2":
        from paddle_tpu.serving.shardgroup import make_groups

        if jax.device_count() < 2:
            pytest.skip("a tp group needs two devices")
        group = make_groups(2)[0]
    rng = np.random.RandomState(26)
    shared = rng.randint(1, VOCAB, size=(14,)).astype(np.int32)
    cases = list(lm.cases[:3])
    for _ in range(2):
        prompt = np.concatenate(
            [shared, rng.randint(1, VOCAB, size=(4,)).astype(np.int32)])
        cases.append((prompt, 8, np.asarray(generate(
            lm.variables, jnp.asarray(prompt[None]), 8, lm.cfg))[0]))
    engines, diverged = {}, []
    for kind in ("plain", "spec"):
        draft = (dict(draft_variables=lm.variables, draft_cfg=lm.cfg)
                 if kind == "spec" else {})
        eng = engines[kind] = DecodeEngine(
            lm.variables, lm.cfg, group=group, decode=DecodeConfig(
                max_slots=3, page_size=4, max_context=40, prefill_chunk=8,
                num_pages=14, prefix_cache=True,
                spec_tokens=3 if kind == "spec" else 0), **draft)
        for k, name in _WRITE_JITS:
            if k == kind:
                setattr(eng, name, _ConsumedSpy(getattr(eng, name)))
        outs = [h.result(timeout=300)
                for h in [eng.submit(p, n) for p, n, _ in cases[:3]]]
        outs += [eng.infer(p, n) for p, n, _ in cases[3:]]
        diverged += [(kind, len(p)) for (p, _, ref), out in zip(cases, outs)
                     if not np.array_equal(out.tokens, ref)]
    # no traffic implants without a second engine or a host tier: by hand,
    # into the scratch page, while the loop thread idles
    eng = engines["plain"]
    old = eng._cache[0]
    eng._cache[0] = eng._implant_page(
        old, jnp.int32(0), jnp.zeros(old.shape[:1] + old.shape[2:], old.dtype))
    yield types.SimpleNamespace(engines=engines, diverged=diverged)
    for eng in engines.values():
        eng.close()
        eng.kv.assert_no_leaks()


@pytest.mark.parametrize("kind,jit", _WRITE_JITS,
                         ids=[name.strip("_") for _, name in _WRITE_JITS])
def test_write_jit_consumes_the_pages_it_is_handed(owned, kind, jit):
    """Donation engages on every write path: each call consumed its page
    arrays, warm-up published that, the step still compiled once and the
    served tokens are generate()'s."""
    eng = owned.engines[kind]
    spy = getattr(eng, jit)
    assert spy.calls >= 1, f"{jit} never ran"
    assert spy.kept == 0, f"{jit} left {spy.kept} page array(s) alive"
    assert obs_metrics.default_registry().get(
        "serving.decode.pages_donated", {"engine": eng.metrics.engine_label},
        default=None) == 1.0
    assert eng.decode_step_cache_size() == 1
    assert eng.prefill_cache_size() == 1
    assert not owned.diverged


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_warmup_publishes_whether_the_device_holds_pages_as_spelled(owned, kind):
    """``serving.decode.pages_row_major`` is set once at warm-up beside
    ``pages_donated``: 1 when every page array (the draft's too) is held in
    the order the model spells it, which a CPU always does; 0 when the device
    reordered one (a v5e did, with heads an axis of their own and dh 64)."""
    eng = owned.engines[kind]
    read = lambda: obs_metrics.default_registry().get(
        "serving.decode.pages_row_major", {"engine": eng.metrics.engine_label},
        default=None)
    assert read() == 1.0
    arrays = list(eng._cache) + ([eng._dk_pages, eng._dv_pages] if kind == "spec" else [])
    assert all(a.ndim == 4 and a.format.layout.major_to_minor == (0, 1, 2, 3)
               for a in arrays)
    eng.metrics.set_pages_row_major(False)
    assert read() == 0.0
    eng.metrics.set_pages_row_major(True)
