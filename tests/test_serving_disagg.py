"""paddle_tpu.serving.disagg — disaggregated prefill/decode acceptance.

The PR 15 contract: (a) a request submitted to a prefill-role worker is
decoded token-exactly by a decode-role worker after an explicit KV-page
handoff, on both transports ("device" gather/scatter and the CRC-checked
"serialized" wire format); (b) a torn or faulted transfer is rejected
whole and degrades to a token-exact re-prefill on the decode worker
(rung 2 of the ladder); (c) a prefill worker dying between the journaled
``hof`` record and the receiver's ``ack`` resumes via
``resume_incomplete`` with zero loss; (d) the :class:`Autoscaler`
decision core scales decode on SLO burn, prefill on queue spikes, and
converges to the configured floor when idle; (e) ``DecodeFleet._pick``
routes least-loaded so a saturated engine stops receiving new work.
"""

import os
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import models
from paddle_tpu.models.transformer_lm import generate
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (
    Autoscaler,
    AutoscalerConfig,
    DecodeConfig,
    DecodeEngine,
    DecodeFleet,
    DisaggRouter,
    HandoffCorrupt,
    HandoffPayload,
    EngineUnhealthy,
    RequestJournal,
    replay_journal,
    resume_incomplete,
)
from paddle_tpu.serving.disagg import DECODE, PREFILL

VOCAB = 97

DC = dict(max_slots=3, page_size=4, max_context=40, prefill_chunk=8,
          num_pages=14, recovery_base_delay_s=0.001,
          recovery_max_delay_s=0.005, breaker_cooldown_s=0.05,
          breaker_max_cooldown_s=0.2)


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def lm():
    spec = models.get_model("transformer_lm", seq_len=64, vocab=VOCAB,
                            d_model=32, d_inner=64, num_heads=4, n_layers=2)
    cfg = spec.extra["cfg"]
    rng = np.random.RandomState(1)
    variables = spec.model.init(0, *spec.synth_batch(2, rng))
    cases = []
    for _ in range(3):
        tp = int(rng.randint(4, 12))
        n = int(rng.randint(8, 16))
        prompt = rng.randint(1, VOCAB, size=(tp,)).astype(np.int32)
        ref = np.asarray(generate(variables, jnp.asarray(prompt[None]),
                                  n, cfg))[0]
        cases.append((prompt, n, ref))
    return types.SimpleNamespace(cfg=cfg, variables=variables, cases=cases)


def _engine(lm, **over):
    kw = dict(DC)
    kw.update(over)
    return DecodeEngine(lm.variables, lm.cfg, decode=DecodeConfig(**kw))


def _payload():
    rng = np.random.RandomState(7)
    pages = [rng.randn(2, 4, 4 * 8).astype(np.float32) for _ in range(2)]  # [L, page_size, H_kv * dh]
    return HandoffPayload(
        rid="r-1", prompt=np.array([3, 5, 8], np.int32),
        generated=[11, 13], mnt=16, cur_len=5, last_tok=13, page_size=4,
        k_pages=pages, v_pages=[p + 1.0 for p in pages],
        tenant="t0", cls="interactive", t_submit=1.5, n_preemptions=2,
        src="pre0")


# ---- wire format: CRC-checked serialize / reject-torn -----------------------


def test_handoff_payload_round_trip():
    p = _payload()
    q = HandoffPayload.from_bytes(p.to_bytes())
    assert q.rid == p.rid
    assert q.prompt.tolist() == p.prompt.tolist()
    assert q.generated == p.generated
    assert (q.mnt, q.cur_len, q.last_tok, q.page_size) == (16, 5, 13, 4)
    assert (q.tenant, q.cls, q.src) == ("t0", "interactive", "pre0")
    assert q.n_preemptions == 2 and q.t_submit == 1.5
    for a, b in zip(p.k_pages + p.v_pages, q.k_pages + q.v_pages):
        np.testing.assert_array_equal(a, b)
    # the handle is process-local and never crosses the wire; the trace
    # header does ride it, but this payload carries none
    assert q.handle is None and q.trace is None


def test_handoff_payload_rejects_torn_and_corrupt():
    blob = _payload().to_bytes()
    with pytest.raises(HandoffCorrupt, match="torn"):
        HandoffPayload.from_bytes(blob[:-5])  # truncated page bytes
    flipped = bytearray(blob)
    flipped[-10] ^= 0xFF  # bit-flip inside the last page
    with pytest.raises(HandoffCorrupt, match="CRC mismatch"):
        HandoffPayload.from_bytes(bytes(flipped))
    hdr = bytearray(blob)
    hdr[12] ^= 0xFF  # bit-flip inside the JSON header
    with pytest.raises(HandoffCorrupt, match="header CRC"):
        HandoffPayload.from_bytes(bytes(hdr))
    with pytest.raises(HandoffCorrupt, match="magic"):
        HandoffPayload.from_bytes(b"nope" + blob)


def test_handoff_payload_to_rescue_packet():
    p = _payload()
    rp = p.to_rescue_packet()
    assert rp.rid == p.rid and rp.generated == p.generated
    assert rp.prompt.tolist() == p.prompt.tolist()
    assert rp.mnt == p.mnt and rp.tenant == p.tenant


# ---- trace continuity across the handoff boundary ---------------------------


def test_handoff_payload_trace_rides_the_wire():
    """The W3C traceparent crosses the CRC'd wire and restores the same
    (trace_id, span_id) identity; absent or malformed headers decode to
    no trace — version tolerance, never a reject."""
    from paddle_tpu import tracing
    from paddle_tpu.serving.disagg import _trace_from_header

    p = _payload()
    p.trace = tracing.SpanContext.new_trace()
    q = HandoffPayload.from_bytes(p.to_bytes())
    assert q.trace is not None
    assert q.trace.trace_id == p.trace.trace_id
    assert q.trace.span_id == p.trace.span_id
    assert _trace_from_header(None) is None
    assert _trace_from_header("not-a-traceparent") is None
    assert _trace_from_header("00-zz-bad-01") is None


@pytest.mark.parametrize("transport", ["device", "serialized"])
def test_handoff_trace_one_id_no_orphans(lm, transport):
    """A request that crosses the prefill→decode boundary must leave ONE
    trace: prefill spans on the publisher, transfer/adopt spans at the
    boundary, the root recorded by the finishing engine — and
    ``validate_trace(multi_engine=True)`` finds no orphans."""
    from paddle_tpu import tracing

    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          transport=transport)
    try:
        prompt, n, ref = lm.cases[0]
        h = router.submit(prompt, n)
        out = h.result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        assert h.trace is not None
        spans = tracing.spans_for_trace(h.trace.trace_id)
        assert tracing.validate_trace(spans, multi_engine=True) == []
        names = {s.name for s in spans}
        assert {"serving.decode.queue_wait", "serving.decode.prefill",
                "serving.handoff.transfer", "serving.handoff.adopt",
                "serving.decode.request"} <= names, names
        engines = {s.attrs.get("engine") for s in spans} - {None}
        assert engines == {pre.metrics.engine_label,
                           dec.metrics.engine_label}
        # exactly one root, recorded by the engine that FINISHED the
        # request — adoption must not mint a second identity
        roots = [s for s in spans if s.context.parent_id is None]
        assert len(roots) == 1, [(s.name, s.attrs) for s in roots]
        assert roots[0].name == "serving.decode.request"
        assert roots[0].attrs["engine"] == dec.metrics.engine_label
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_faulted_transfer_keeps_trace_through_reprefill(lm):
    """Rung 2 (reject + re-prefill on the decode worker) rides the rescue
    path — the adopted request must keep the submitter's trace id."""
    from paddle_tpu import tracing

    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          transport="serialized")
    try:
        with faults.injected(
            faults.FaultSpec(faults.DISAGG_HANDOFF, "error", times=1)
        ):
            prompt, n, ref = lm.cases[0]
            h = router.submit(prompt, n)
            out = h.result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        assert h.trace is not None
        spans = tracing.spans_for_trace(h.trace.trace_id)
        assert tracing.validate_trace(spans, multi_engine=True) == []
        assert "serving.rescue" in {s.name for s in spans}
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


# ---- end-to-end handoff: both transports, token-exact -----------------------


@pytest.mark.parametrize("transport", ["device", "serialized"])
def test_disagg_handoff_token_exact(lm, transport):
    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          transport=transport)
    try:
        handles = [router.submit(p, n) for p, n, _ in lm.cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(lm.cases, outs):
            assert np.array_equal(out.tokens, ref)
        # every request crossed the boundary: prefilled on pre, decoded
        # on dec — no silent local decode on the prefill worker
        assert router.handoffs_total == len(lm.cases)
        assert pre.metrics.handoffs_out_total == len(lm.cases)
        assert dec.metrics.handoffs_in_total == len(lm.cases)
        assert router.handoff_rejects_total == 0
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_disagg_faulted_transfer_reprefills_token_exact(lm):
    """An injected transfer fault (rung 2) must degrade to re-prefill on
    the decode worker — same tokens, nothing lost."""
    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          transport="serialized")
    try:
        with faults.injected(
            faults.FaultSpec(faults.DISAGG_HANDOFF, "error", times=1)
        ) as plan:
            prompt, n, ref = lm.cases[0]
            out = router.submit(prompt, n).result(timeout=120)
            assert plan.all_fired()
        assert np.array_equal(out.tokens, ref)
        assert router.handoff_rejects_total == 1
        assert router.handoff_reprefills_total == 1
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_gathered_page_implants_and_reads_back_bit_exact(lm):
    """The handoff's two page ops spell no axis after the page's
    (``pages[:, id]``), so they hold for the form
    ``[L, num_pages, page_size, H_kv * dh]`` as they did with heads an axis
    of their own: a page gathered from one engine and implanted in another
    reads back bit for bit, and no other page of the target is touched."""
    from paddle_tpu.models.transformer_lm import paged_cache_shape

    src, dst = _engine(lm), _engine(lm)
    try:
        shape = src._cache[0].shape
        assert shape == paged_cache_shape(lm.cfg, DC["num_pages"], DC["page_size"])
        assert shape == (2, 14, 4, 4 * 8)
        page = np.random.RandomState(3).randn(2, 4, 32).astype(np.float32)
        # by hand, while the loop threads idle: the engines own their arrays,
        # so each write rebinds the result
        src._cache[0] = src._implant_page(src._cache[0], jnp.int32(9), jnp.asarray(page))
        wire = np.asarray(src._gather_page(src._cache[0], jnp.int32(9)))
        assert wire.shape == (2, 4, 32) and wire.tobytes() == page.tobytes()
        dst._cache[1] = dst._implant_page(dst._cache[1], jnp.int32(5), jnp.asarray(wire))
        back = np.asarray(dst._cache[1])
        assert back[:, 5].tobytes() == page.tobytes()
        assert not back[:, :5].any() and not back[:, 6:].any()
    finally:
        src.close(30)
        dst.close(30)


def test_handoff_of_the_old_page_form_is_refused_and_reprefilled(lm):
    """A payload gathered by an engine that still held heads as an axis
    carries pages ``[L, H_kv, page_size, dh]``: the same values, but not this
    engine's page shape. Adoption is refused and the decode worker
    re-prefills, token-exact, as for a payload of another tp degree."""
    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE], transport="serialized")
    adopt, seen = dec.adopt_handoff, []

    def adopt_old_form(payload, **kw):
        old = lambda p: np.ascontiguousarray(
            np.moveaxis(p.reshape(p.shape[0], p.shape[1], 4, 8), 2, 1))
        payload.k_pages = [old(p) for p in payload.k_pages]
        payload.v_pages = [old(p) for p in payload.v_pages]
        seen.extend(p.shape for p in payload.k_pages)
        return adopt(payload, **kw)

    dec.adopt_handoff = adopt_old_form
    try:
        handles = [router.submit(p, n) for p, n, _ in lm.cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(lm.cases, outs):
            assert np.array_equal(out.tokens, ref)
        assert seen and set(seen) == {(2, 4, 4, 8)}
        snap = dec.metrics.snapshot()
        assert snap["handoffs_in_total"] == 0, snap
        assert snap["recovered_total"] == len(lm.cases), snap
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_disagg_no_decode_worker_decodes_locally(lm):
    """Rung 3: with the decode side unavailable the publisher keeps the
    request and decodes it locally — degraded, never lost."""
    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE])
    try:
        router._draining.add(id(dec))  # decode side at a safe boundary
        prompt, n, ref = lm.cases[0]
        out = router.submit(prompt, n).result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        assert router.handoffs_total == 0
        assert pre.metrics.handoffs_out_total == 0
    finally:
        router._draining.discard(id(dec))
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


# ---- durable handoff window: hof-without-ack resumes ------------------------


def test_unacked_handoff_record_resumes_token_exact(lm, tmp_path):
    """A prefill worker dying after the journaled ``hof`` intent but
    before the receiver's ``ack`` must leave a replayable record that
    ``resume_incomplete`` completes token-exactly."""
    path = os.fspath(tmp_path / "disagg.wal")
    prompt, n, ref = lm.cases[0]
    j = RequestJournal(path, fsync_every=1)
    j.log_admit("h-1", prompt, n, [], "default", "interactive")
    j.log_token("h-1", int(ref[0]))
    j.log_handoff("h-1", prompt, n, [int(ref[0])], "default",
                  "interactive", src="pre0", dst=None)
    j.close()  # crash: no ack, no fin

    rep = replay_journal(path)
    assert rep["h-1"].handed_off and not rep["h-1"].acked
    assert not rep["h-1"].finished

    eng = _engine(lm, journal_path=path)
    try:
        resumed = resume_incomplete(eng, path)
        assert set(resumed) == {"h-1"}
        handle, n_delivered = resumed["h-1"]
        out = handle.result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        assert out.tokens[:n_delivered].tolist() == [int(ref[0])]
    finally:
        eng.close(timeout=30)
    eng.kv.assert_no_leaks()


def test_acked_handoff_is_transfer_complete(tmp_path):
    path = os.fspath(tmp_path / "j.wal")
    j = RequestJournal(path, fsync_every=1)
    j.log_handoff("r", np.array([1, 2], np.int32), 4, [9], "default",
                  "interactive", src="pre0", dst=None)
    j.log_handoff_ack("r", "dec0")
    j.close()
    rep = replay_journal(path)
    assert rep["r"].handed_off and rep["r"].acked
    assert rep["r"].generated == [9]


# ---- least-loaded routing (PR 15 satellite) ---------------------------------


def test_fleet_pick_routes_away_from_saturated_engine(lm):
    """A saturated engine (high live load) must stop receiving new work
    while a healthy peer has capacity."""
    a, b = _engine(lm), _engine(lm)
    fleet = DecodeFleet([a, b])
    try:
        a.load = lambda: 50.0  # saturated: slots + queue all busy
        for _ in range(4):
            assert fleet._pick() is b
        prompt, n, ref = lm.cases[0]
        outs = [fleet.submit(prompt, n).result(timeout=120)
                for _ in range(3)]
        for out in outs:
            assert np.array_equal(out.tokens, ref)
        assert b.metrics.snapshot()["requests_total"] == 3
        assert a.metrics.snapshot()["requests_total"] == 0
    finally:
        fleet.close(30)


def test_engine_load_tracks_live_work(lm):
    eng = _engine(lm)
    try:
        assert eng.load() == 0.0
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "stall", stall_s=0.2,
                             times=2)
        ):
            h = eng.submit(lm.cases[0][0], lm.cases[0][1])
            deadline = time.monotonic() + 10
            while eng.load() == 0.0 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert eng.load() >= 1.0
            h.result(timeout=60)
        deadline = time.monotonic() + 10
        while eng.load() > 0.0 and time.monotonic() < deadline:
            time.sleep(0.002)
        assert eng.load() == 0.0
    finally:
        eng.close(timeout=30)


# ---- drain-and-convert ------------------------------------------------------


def test_convert_drains_and_swaps_role(lm):
    built = []

    def factory(role):
        eng = _engine(lm)
        built.append((role, eng))
        return eng

    p1, p2, d1 = _engine(lm), _engine(lm), _engine(lm)
    router = DisaggRouter([p1, p2, d1], [PREFILL, PREFILL, DECODE],
                          factory=factory)
    try:
        assert (router.n_prefill, router.n_decode) == (2, 1)
        new = router.convert(p2, DECODE, timeout=10)
        assert p2.closed  # drained, not abandoned
        assert built and built[0][0] == DECODE and built[0][1] is new
        assert (router.n_prefill, router.n_decode) == (1, 2)
        assert router.role(new) == DECODE
        assert router.conversions_total == 1
        # traffic still flows end-to-end through the reshaped fleet
        prompt, n, ref = lm.cases[0]
        out = router.submit(prompt, n).result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        # converting to the role it already has is a no-op
        assert router.convert(new, DECODE) is new
    finally:
        router.close(30)
    for e in (p1, d1, new):
        e.kv.assert_no_leaks()


# ---- Autoscaler decision core (pure, every branch) --------------------------


def _scaler(**over):
    cfg = AutoscalerConfig(**over)
    router = types.SimpleNamespace()  # decide() never touches the router
    return Autoscaler(router, cfg, detector=types.SimpleNamespace(
        observe=lambda *a, **k: None))


def test_autoscaler_burn_breach_scales_decode():
    s = _scaler(burn_threshold=1.0, min_prefill=1)
    assert s.decide(burn_rate=2.5, prefill_depth=0, decode_depth=9,
                    n_prefill=3, n_decode=2) == Autoscaler.SCALE_DECODE
    # ...but never below the prefill floor
    assert s.decide(burn_rate=2.5, prefill_depth=0, decode_depth=9,
                    n_prefill=1, n_decode=2) is None
    # healthy burn rate under normal load: no action
    assert s.decide(burn_rate=0.4, prefill_depth=1, decode_depth=5,
                    n_prefill=3, n_decode=2) is None


def test_autoscaler_queue_spike_scales_prefill():
    s = _scaler(spike_depth=8.0, min_decode=1)
    assert s.decide(burn_rate=0.2, prefill_depth=20, decode_depth=3,
                    n_prefill=2, n_decode=3) == Autoscaler.SCALE_PREFILL
    # detector anomaly flag counts even under the depth threshold
    assert s.decide(burn_rate=0.2, prefill_depth=4, decode_depth=3,
                    n_prefill=2, n_decode=3,
                    queue_spike=True) == Autoscaler.SCALE_PREFILL
    # a burning decode SLO outranks the prefill backlog
    assert s.decide(burn_rate=5.0, prefill_depth=20, decode_depth=9,
                    n_prefill=2, n_decode=3) == Autoscaler.SCALE_DECODE
    # never below the decode floor
    assert s.decide(burn_rate=0.2, prefill_depth=20, decode_depth=3,
                    n_prefill=2, n_decode=1) is None


def test_autoscaler_idle_converges_to_floor():
    s = _scaler(floor_prefill=2, min_prefill=1, min_decode=1)
    # too many prefill workers for an idle fleet: give one to decode
    assert s.decide(burn_rate=0.0, prefill_depth=0, decode_depth=0,
                    n_prefill=4, n_decode=2) == Autoscaler.SCALE_DECODE
    # too few: rebuild toward the floor
    assert s.decide(burn_rate=0.0, prefill_depth=0, decode_depth=0,
                    n_prefill=1, n_decode=3) == Autoscaler.SCALE_PREFILL
    # at the floor: stable, no thrash
    assert s.decide(burn_rate=0.0, prefill_depth=0, decode_depth=0,
                    n_prefill=2, n_decode=2) is None
    # no SLO feed (burn_rate None) still converges on depth alone
    assert s.decide(burn_rate=None, prefill_depth=0, decode_depth=0,
                    n_prefill=4, n_decode=2) == Autoscaler.SCALE_DECODE


def test_autoscaler_tick_converts_and_cools_down(lm):
    built = []

    def factory(role):
        eng = _engine(lm)
        built.append(role)
        return eng

    p1, p2, d1 = _engine(lm), _engine(lm), _engine(lm)
    router = DisaggRouter([p1, p2, d1], [PREFILL, PREFILL, DECODE],
                          factory=factory)
    now = {"t": 100.0}
    slo = types.SimpleNamespace(status=lambda: [
        {"name": "decode_p99", "burn_rate": 9.0}])
    scaler = Autoscaler(
        router, AutoscalerConfig(slo_name="decode_p99", cooldown_s=30.0),
        slo_engine=slo,
        detector=types.SimpleNamespace(observe=lambda *a, **k: None),
        clock=lambda: now["t"])
    try:
        assert scaler.tick() == Autoscaler.SCALE_DECODE
        assert built == [DECODE]
        assert (router.n_prefill, router.n_decode) == (1, 2)
        # cooldown: the next tick inside the window is a no-op even
        # though the SLO still burns
        assert scaler.tick() is None
        now["t"] += 31.0
        # burn persists but the prefill floor blocks further conversion
        assert scaler.tick() is None
        assert scaler.actions_total == {Autoscaler.SCALE_DECODE: 1}
    finally:
        router.close(30)


# ---- router construction guards ---------------------------------------------


def test_router_requires_decode_role(lm):
    eng = _engine(lm)
    try:
        with pytest.raises(Exception, match="decode-role"):
            DisaggRouter([eng], [PREFILL])
    finally:
        eng.close(timeout=30)


def test_router_shares_journal_with_engines(lm, tmp_path):
    path = os.fspath(tmp_path / "fleet.wal")
    pre, dec = _engine(lm), _engine(lm)
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          journal_path=path)
    try:
        assert pre._journal is router._journal
        assert dec._journal is router._journal
        assert not pre._journal_owned and not dec._journal_owned
        prompt, n, ref = lm.cases[0]
        out = router.submit(prompt, n).result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        router._journal.flush()
        rep = replay_journal(path)
        (entry,) = rep.values()
        assert entry.finished  # one request, fully journaled + finished
        # the adopter's admit snapshot superseded the hof record; the
        # receiver's ack proves the transfer completed
        assert entry.acked and not entry.handed_off
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()
