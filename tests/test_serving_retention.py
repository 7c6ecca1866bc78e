"""``serving.DecodeEngine`` over a model whose cache is a fixed recurrent
state per slot (``models/retention_lm.py``): what it serves is the plain
reference's full pass, through admission, chunked prefill beside decoding
slots and a recovered step fault; it owns its state array as it owns pages;
and it refuses what needs pages, by name."""

import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import models
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.resilience import faults
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.disagg import PREFILL, DisaggRouter
from paddle_tpu.serving.host_tier import HostPagePool

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks import check  # noqa: E402
from test_serving_decode import _ConsumedSpy  # noqa: E402  (counts cache arrays left alive)
from benchmarks.references import common as refc  # noqa: E402
from benchmarks.references import retention_lm as ref  # noqa: E402

VOCAB = 97
DECODE = dict(max_slots=3, page_size=4, max_context=64, prefill_chunk=8)


def _lm(**overrides):
    spec = models.get_model(
        "retention_lm", seq_len=16, vocab=VOCAB, d_model=64, d_inner=128, num_heads=4,
        num_kv_heads=2, head_dim=16, n_layers=2, ret_tile=8, param_dtype="float32",
        compute_dtype="float32", **overrides)
    ids, labels = spec.synth_batch(2, np.random.RandomState(0))
    variables = spec.model.init(0, ids, labels)
    return types.SimpleNamespace(variables=variables, cfg=spec.extra["cfg"])


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def lm_long_memory():
    """Gates near 1 (logit 0.999 + noise): a state outlives a chunk and a
    request, so the carry across chunks and the start-over of a slot that is
    taken again both show in the served tokens."""
    return _lm(ret_gate_shift=6.906768)


def gap_to_reference(lm, prompt, tokens) -> float:
    """How far, in standard deviations of a position's logits, the served
    tokens lie below the best of the reference's one full pass over prompt
    and served tokens (``check.gap_sigmas``: what decides ``correct``)."""
    ids = np.concatenate([prompt, tokens])[None]
    params = {k: jnp.asarray(v) for k, v in lm.variables.params.items()}
    logits = np.asarray(ref.logits_fn(params, ids, lm.cfg, refc.mm_f32))[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(check.gap_sigmas(rows, tokens).max())


# -- (b) prefill then decode through the state, against the full pass ---------

@pytest.mark.parametrize("which", ["lm", "lm_long_memory"])
def test_served_tokens_are_the_references_through_admission_prefill_and_a_step_fault(
        which, request):
    lm = request.getfixturevalue(which)
    rng = np.random.RandomState(5)
    # six requests on three slots: slots are freed and taken again mid-run; the
    # 30- and 27-token prompts prefill (4 chunks) while the other slots decode
    cases = [(rng.randint(1, VOCAB, size=(n,)).astype(np.int32), m)
             for n, m in [(5, 9), (30, 6), (9, 12), (27, 5), (3, 4), (14, 7)]]
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
    assert reg.get("serving.decode.state_bytes", label, default=None) == 2 * 3 * 2 * 24 * 192 * 4
    assert reg.get("serving.decode.state_slots_in_use", label, default=None) == 0.0


# -- (e) the engine owns its state array --------------------------------------

@pytest.mark.parametrize("jit", ["_step", "_prefill"])
def test_every_state_writing_jit_consumes_the_state_it_is_handed(lm, jit):
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
    assert spy.kept == 0, f"{jit} left {spy.kept} state array(s) alive"
    assert obs_metrics.default_registry().get(
        "serving.decode.state_donated", {"engine": eng.metrics.engine_label},
        default=None) == 1.0
    assert len(eng._cache) == 1 and eng._cache[0].shape == (2, 3, 2, 24, 192)


def test_a_step_that_fails_after_consuming_the_state_is_recovered_by_re_prefill(lm):
    """The donated call dies having eaten its input: the engine rebuilds the
    array zeroed and every request prefills again, to the same tokens."""
    rng = np.random.RandomState(8)
    prompts = [rng.randint(1, VOCAB, size=(n,)).astype(np.int32) for n in (12, 6)]
    eng = DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
    try:
        real, calls = eng._step, []

        def dies_once(*args):
            out = real(*args)
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("injected: failed after the call consumed the state")
            return out

        eng._step = dies_once
        outs = [h.result(timeout=300) for h in [eng.submit(p, 8) for p in prompts]]
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    assert snap["step_faults_total"] == 1
    for p, out in zip(prompts, outs):
        assert len(out.tokens) == 8 and gap_to_reference(lm, p, out.tokens) < 1e-3


# -- (d) what needs pages is refused, by name ---------------------------------

@pytest.mark.parametrize("feature, kwargs", [
    ("the prefix cache", dict(decode=DecodeConfig(prefix_cache=True, **DECODE))),
    ("the host tier", dict(decode=DecodeConfig(host_tier_bytes=1 << 20, **DECODE))),
    ("the host tier", dict(decode=DecodeConfig(**DECODE), host_tier=HostPagePool(1 << 20, 4))),
    ("a draft model", dict(decode=DecodeConfig(**DECODE), draft_variables="same")),
])
def test_the_engine_refuses_what_needs_kv_pages(lm, feature, kwargs):
    if kwargs.get("draft_variables") == "same":
        kwargs = dict(kwargs, draft_variables=lm.variables)
    with pytest.raises(Exception, match=f"{feature} cannot be used.*power retention"):
        DecodeEngine(lm.variables, lm.cfg, **kwargs)


def test_disaggregated_handoff_is_refused(lm):
    engines = [DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**DECODE))
               for _ in range(2)]
    try:
        with pytest.raises(Exception, match="disaggregated handoff cannot be used.*power retention"):
            DisaggRouter(engines, [PREFILL, "decode"])
        with pytest.raises(Exception, match="disaggregated handoff cannot be used"):
            engines[1].adopt_handoff(None)
    finally:
        for e in engines:
            e.close()
