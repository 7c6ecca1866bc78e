"""paddle_tpu.serving.recovery — zero-loss decode acceptance tests.

The acceptance contract (ISSUE 11): with mixed-length in-flight
generations, (a) a transient ``DECODE_STEP`` fault storm and (b) an
engine declared unhealthy mid-generation both end with ZERO failed
requests and token-exact outputs vs. a fault-free run; (c) a simulated
process restart replays the durable journal, resumes incomplete
requests to completion, and dedupes already-delivered tokens. The
jitted decode step must stay compile-once (``decode_step_cache_size()
== 1``) through every recovery path. Also covered: the typed
``RetriesExhausted`` outcome for deterministic poison, journal CRC /
torn-tail discipline, the enforced ``close()`` drain deadline, and
fault-during-recovery escalation to migration.
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
from paddle_tpu.resilience.circuit import CLOSED, OPEN
from paddle_tpu.serving import (
    DecodeConfig,
    DecodeEngine,
    DecodeFleet,
    EngineUnhealthy,
    RequestJournal,
    RetriesExhausted,
    replay_journal,
    resume_incomplete,
)
from paddle_tpu.serving.recovery import _decode_record, _encode_record

VOCAB = 97

# small backoffs + page-starved pool: recovery AND preemption both fire
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
    """Tiny LM + greedy fault-free references for mixed-length requests
    (same shapes as test_serving_decode so jit/persistent caches are
    shared across the files)."""
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


# ---- (a) step-fault storm: zero loss, token-exact -------------------------


def test_step_fault_storm_zero_loss_token_exact(lm):
    eng = _engine(lm)
    try:
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "error", after=2, times=3)
        ) as plan:
            handles = [eng.submit(p, n) for p, n, _ in lm.cases]
            outs = [h.result(timeout=120) for h in handles]
            assert plan.all_fired()
        for (_, _, ref), out in zip(lm.cases, outs):
            assert np.array_equal(out.tokens, ref)  # token-exact, zero lost
        snap = eng.metrics.snapshot()
        assert snap["errors_total"] == 0, snap
        assert snap["step_faults_total"] >= 3, snap
        assert snap["recovered_total"] >= 1, snap
        # the recovery path re-admits through the SAME jitted step
        assert eng.decode_step_cache_size() == 1
        assert eng.breaker.state == CLOSED  # clean steps reset health
    finally:
        eng.close(timeout=30)


def test_recovery_disabled_preserves_fail_fast(lm):
    """recovery=False pins the pre-recovery contract: one poisoned
    iteration fails its in-flight requests with the injected error."""
    eng = _engine(lm, recovery=False)
    try:
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "error", after=1)
        ):
            h = eng.submit(lm.cases[0][0], lm.cases[0][1])
            with pytest.raises(OSError):
                h.result(timeout=60)
    finally:
        eng.close(timeout=30)


def test_deterministic_poison_surfaces_retries_exhausted(lm):
    """A fault that follows the request across quarantine cycles must
    burn the per-request budget and fail TYPED — not loop forever (the
    re-prefill path makes one token of progress per cycle, which is why
    the budget never resets on progress)."""
    eng = _engine(lm, recovery_retries=3)
    try:
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "error", times=10 ** 9)
        ):
            h = eng.submit(lm.cases[0][0], lm.cases[0][1])
            with pytest.raises(RetriesExhausted) as ei:
                h.result(timeout=120)
            assert ei.value.request_id is not None
        assert eng.metrics.snapshot()["retries_exhausted_total"] == 1
    finally:
        eng.close(timeout=30)


def test_prefill_fault_recovers_single_request(lm):
    """A failed prefill chunk quarantines ONE request through the resume
    path; the others never notice and every output stays token-exact."""
    eng = _engine(lm)
    fails = {"n": 2}
    real = eng._prefill

    def flaky_prefill(*a, **kw):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("injected prefill fault")
        return real(*a, **kw)

    eng._prefill = flaky_prefill
    try:
        handles = [eng.submit(p, n) for p, n, _ in lm.cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(lm.cases, outs):
            assert np.array_equal(out.tokens, ref)
        assert eng.metrics.snapshot()["errors_total"] == 0
        assert eng.metrics.snapshot()["recovered_total"] >= 1
    finally:
        eng._prefill = real
        eng.close(timeout=30)


@pytest.mark.parametrize("where,tp", [("step", 1), ("prefill", 1), ("step", 2)],
                         ids=["step", "prefill", "step-group2"])
def test_fault_after_the_call_consumed_its_pages(lm, where, tp):
    """The write-jits take the page arrays donated, so a call that fails
    AFTER it ran leaves the engine holding deleted arrays (DECODE_STEP
    injects before the call and never reaches this). The engine rebuilds
    them, drops the radix tree that indexed the lost contents, and every
    live request still finishes token-exact inside its recovery budget;
    a prompt cached before the fault is served exact again after it,
    which adopting one of its old (now zeroed) pages would break. In a
    tp group the rebuilt arrays keep the group's sharding."""
    group = None
    if tp > 1:
        from paddle_tpu.serving.shardgroup import make_groups

        if __import__("jax").device_count() < tp:
            pytest.skip("a tp group needs two devices")
        group = make_groups(tp)[0]
    eng = DecodeEngine(lm.variables, lm.cfg, group=group,
                       decode=DecodeConfig(**DC, prefix_cache=True))
    sharding = eng._cache[0].sharding
    first, n_first, ref_first = lm.cases[0]
    assert np.array_equal(eng.infer(first, n_first).tokens, ref_first)
    assert eng.prefix.num_pages >= 1  # the tree indexes pre-fault pages
    real = getattr(eng, "_" + where)
    calls = {"n": 0}

    def consume_then_raise(*a, **kw):
        out = real(*a, **kw)  # the inputs are gone from here on
        calls["n"] += 1
        if calls["n"] in (2, 4):
            raise OSError(f"injected fault after the {where} ran")
        return out

    restored = []
    real_restore = eng._restore_lost_pages

    def restore_spy():
        lost = real_restore()
        if lost:
            restored.append(eng.prefix.num_pages)
        return lost

    setattr(eng, "_" + where, consume_then_raise)
    eng._restore_lost_pages = restore_spy
    try:
        handles = [eng.submit(p, n) for p, n, _ in lm.cases[1:]]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(lm.cases[1:], outs):
            assert np.array_equal(out.tokens, ref)
        # both faults found the arrays deleted, and left an empty tree
        assert restored == [0, 0], restored
        for a in (eng._cache[0], eng._cache[1]):
            assert not a.is_deleted() and a.sharding == sharding
        snap = eng.metrics.snapshot()
        assert snap["errors_total"] == 0, snap
        assert snap["retries_exhausted_total"] == 0, snap
        assert snap["recovered_total"] >= 1, snap
        assert np.array_equal(eng.infer(first, n_first).tokens, ref_first)
        assert real._cache_size() == 1  # recovered through the same jit
    finally:
        setattr(eng, "_" + where, real)
        eng.close(timeout=30)
    eng.kv.assert_no_leaks()


# ---- (b) cross-engine migration -------------------------------------------


def test_unhealthy_engine_migrates_token_exact_then_readmits(lm):
    """Engine A goes permanently sick mid-generation: after
    ``unhealthy_after`` consecutive faults its breaker trips and every
    live request finishes on B with exactly the fault-free tokens, on
    the client's ORIGINAL handles. When the fault clears, the fleet's
    half-open probe re-admits A."""
    ea = _engine(lm)
    eb = _engine(lm)
    fleet = DecodeFleet([ea, eb])
    try:
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "error", after=1,
                             times=10 ** 9,
                             match={"engine": ea.metrics.engine_label})
        ):
            handles = [ea.submit(p, n) for p, n, _ in lm.cases]  # pin to A
            outs = [h.result(timeout=120) for h in handles]
            for (_, _, ref), out in zip(lm.cases, outs):
                assert np.array_equal(out.tokens, ref)
            assert ea.breaker.state == OPEN
            assert ea.metrics.snapshot()["migrated_total"] == len(lm.cases)
            assert eb.metrics.snapshot()["errors_total"] == 0
            assert eb.decode_step_cache_size() == 1
        # fault gone: routed traffic spends the half-open probe on A
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and ea.breaker.state != CLOSED:
            p, n, ref = lm.cases[0]
            out = fleet.submit(p, n).result(timeout=60)
            assert np.array_equal(out.tokens, ref)
            time.sleep(0.02)
        assert ea.breaker.state == CLOSED
        assert ea.breaker.recoveries_total >= 1
    finally:
        fleet.close(timeout=30)


@pytest.mark.skipif(__import__("jax").device_count() < 4,
                    reason="needs 4 virtual devices (conftest)")
def test_group_member_fault_migrates_cross_group_token_exact(lm):
    """ISSUE 16: a tp replica group is the routing unit — ONE member's
    canary fault must eject the WHOLE group (breaker trip) and finish
    every live request token-exactly on another group, then half-open
    probing re-admits the group once the member heals."""
    from paddle_tpu.serving.shardgroup import make_groups

    fleet = DecodeFleet.from_groups(
        lm.variables, lm.cfg, make_groups(2)[:2],
        decode=DecodeConfig(group_probe_every_s=0.0, **DC))
    ga, gb = fleet.engines
    try:
        handles = [ga.submit(p, n) for p, n, _ in lm.cases]  # pin to A
        # arm the canary only once every case is live in decode (same
        # rationale as the escalation test below: a fault while some
        # cases still queue migrates just the admitted subset)
        total_chunks = sum(-(-len(p) // ga.decode_config.prefill_chunk)
                           for p, _, _ in lm.cases)
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and ga.metrics.snapshot()["prefill_chunks_total"]
               < total_chunks):
            time.sleep(0.005)
        assert ga.metrics.snapshot()["prefill_chunks_total"] == total_chunks
        with faults.injected(
            faults.FaultSpec(faults.GROUP_MEMBER, "error", times=1,
                             match={"engine": ga.metrics.engine_label,
                                    "shard": 1})
        ) as plan:
            outs = [h.result(timeout=120) for h in handles]
            assert plan.all_fired()
            for (_, _, ref), out in zip(lm.cases, outs):
                assert np.array_equal(out.tokens, ref)
            assert ga.breaker.state == OPEN
            snap = ga.metrics.snapshot()
            assert snap["group_member_faults_total"] == 1, snap
            assert snap["migrated_total"] == len(lm.cases), snap
            assert snap["errors_total"] == 0, snap
            assert gb.metrics.snapshot()["errors_total"] == 0
            assert gb.decode_step_cache_size() == 1
        # member healed: routed traffic spends the half-open probe on A
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and ga.breaker.state != CLOSED:
            p, n, ref = lm.cases[0]
            out = fleet.submit(p, n).result(timeout=60)
            assert np.array_equal(out.tokens, ref)
            time.sleep(0.02)
        assert ga.breaker.state == CLOSED
    finally:
        fleet.close(timeout=30)


def test_pick_tiebreak_is_stable_under_equal_load(lm):
    """Satellite: equal-load routing must be deterministic — repeated
    picks with identical load land on the same (lowest-index) engine
    instead of drifting with the half-open rotation counter."""
    ea = _engine(lm)
    eb = _engine(lm)
    fleet = DecodeFleet([ea, eb])
    try:
        picks = {id(fleet._pick()) for _ in range(8)}
        assert picks == {id(ea)}
    finally:
        fleet.close(timeout=30)


def test_fault_during_recovery_escalates_to_migration(lm):
    """DECODE_RECOVER firing inside the quarantine path must escalate
    one rung (migrate via the fleet) rather than lose requests."""
    ea = _engine(lm)
    eb = _engine(lm)
    fleet = DecodeFleet([ea, eb])
    try:
        handles = [ea.submit(p, n) for p, n, _ in lm.cases]
        # arm the faults only once every case is through prefill: if the
        # step fault fires while some cases still sit in the admission
        # queue, the engine (correctly) migrates just the admitted subset
        # and the count below races with the loop thread
        total_chunks = sum(-(-len(p) // ea.decode_config.prefill_chunk)
                           for p, _, _ in lm.cases)
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and ea.metrics.snapshot()["prefill_chunks_total"]
               < total_chunks):
            time.sleep(0.005)
        assert ea.metrics.snapshot()["prefill_chunks_total"] == total_chunks
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "error", after=1,
                             match={"engine": ea.metrics.engine_label}),
            faults.FaultSpec(faults.DECODE_RECOVER, "error",
                             match={"engine": ea.metrics.engine_label}),
        ) as plan:
            outs = [h.result(timeout=120) for h in handles]
            assert plan.all_fired()
        for (_, _, ref), out in zip(lm.cases, outs):
            assert np.array_equal(out.tokens, ref)
        assert ea.metrics.snapshot()["migrated_total"] == len(lm.cases)
    finally:
        fleet.close(timeout=30)


def test_fleet_no_healthy_engine_rejects_typed(lm):
    eng = _engine(lm)
    fleet = DecodeFleet([eng])
    try:
        eng.breaker.trip()
        with pytest.raises(EngineUnhealthy):
            fleet.submit(lm.cases[0][0], 4)
    finally:
        fleet.close(timeout=30)


# ---- (c) durable journal: replay after restart ----------------------------


def test_journal_records_crc_and_torn_tail(tmp_path):
    path = os.fspath(tmp_path / "j.wal")
    j = RequestJournal(path, fsync_every=2)
    j.log_admit("r1", np.array([5, 6], np.int32), 4, [], "default",
                "interactive")
    j.log_token("r1", 7)
    j.log_token("r1", 8)
    j.log_finish("r1", "length")
    j.log_admit("r2", np.array([9], np.int32), 3, [1], "default",
                "interactive")
    j.log_token("r2", 2)
    j.close()
    rep = replay_journal(path)
    assert rep["r1"].finished and rep["r1"].generated == [7, 8]
    assert not rep["r2"].finished and rep["r2"].generated == [1, 2]
    # torn tail: a partial append must not poison the prefix...
    with open(path, "ab") as f:
        f.write(b"deadbeef|{\"k\":\"tok\",\"rid\":\"r2\"")  # no newline/crc
    rep = replay_journal(path)
    assert rep["r2"].generated == [1, 2]
    # ...and a bit-flip mid-file cuts trust at that record, not before
    rec = _encode_record({"k": "tok", "rid": "r2", "t": 3})
    assert _decode_record(rec) is not None
    assert _decode_record(rec[:-5] + b"X" + rec[-4:]) is None


def test_process_restart_replays_journal_resumes_and_dedupes(lm, tmp_path):
    """Kill an engine mid-generation (no drain, no fin records — a real
    crash), then rebuild from the journal on a fresh engine: every
    incomplete request resumes to completion token-exactly, and the
    journaled prefix equals the delivered-token count for dedup."""
    path = os.fspath(tmp_path / "decode.wal")
    e1 = _engine(lm, journal_path=path, journal_fsync_every=4)
    handles = [e1.submit(p, n) for p, n, _ in lm.cases]
    deadline = time.monotonic() + 60
    while (e1.metrics.snapshot()["tokens_total"] < 6
           and time.monotonic() < deadline):
        time.sleep(0.005)
    e1.kill()
    for h in handles:  # the crashed process's futures die typed, not hang
        with pytest.raises(Exception):
            h.result(timeout=10)

    rep = replay_journal(path)
    assert len(rep) == len(lm.cases)
    assert not any(r.finished for r in rep.values())  # crash wrote no fins

    e2 = _engine(lm, journal_path=path)
    try:
        resumed = resume_incomplete(e2, path)
        assert len(resumed) == len(lm.cases)
        by_prompt = {tuple(p.tolist()): ref for p, _, ref in lm.cases}
        for rid, (handle, n_delivered) in resumed.items():
            out = handle.result(timeout=120)
            ref = by_prompt[tuple(rep[rid].prompt.tolist())]
            assert np.array_equal(out.tokens, ref)  # token-exact resume
            # idempotent-id dedup: the first n_delivered tokens are
            # exactly what the journal proves was already produced
            assert out.tokens[:n_delivered].tolist() == \
                rep[rid].generated[:n_delivered]
        assert e2.metrics.snapshot()["journal_replayed_total"] == \
            len(lm.cases)
        # a second replay over the now-finished journal resumes nothing
        e2._journal.flush()  # a restart-reader only runs post-writer
        rep2 = replay_journal(path)
        assert all(r.finished for r in rep2.values())
        assert resume_incomplete(e2, path) == {}
        assert e2.decode_step_cache_size() == 1
    finally:
        e2.close(timeout=30)


# ---- journal compaction (PR 15 satellite) ----------------------------------


def test_journal_size_triggered_compaction_keeps_incomplete(tmp_path):
    """Crossing compact_bytes rewrites the WAL: finished requests drop,
    incomplete ones survive as full snapshots, and replay over the
    compacted file equals replay over the uncompacted history."""
    path = os.fspath(tmp_path / "j.wal")
    j = RequestJournal(path, fsync_every=1, compact_bytes=2048)
    j.log_admit("keep", np.array([3, 4], np.int32), 8, [], "default",
                "interactive")
    j.log_token("keep", 11)
    j.log_token("keep", 12)
    # churn finished requests until the size trigger fires
    i = 0
    while j.compactions_total == 0:
        rid = f"done{i}"
        j.log_admit(rid, np.array([1, 2], np.int32), 4, [], "default",
                    "interactive")
        j.log_token(rid, 5)
        j.log_finish(rid, "length")
        i += 1
        assert i < 10_000, "compaction never triggered"
    assert os.path.getsize(path) < 2048  # rewritten, not just rotated
    rep = replay_journal(path)
    # only the incomplete request survives, with its token prefix intact
    incomplete = {r for r, v in rep.items() if not v.finished}
    assert incomplete == {"keep"}
    assert rep["keep"].generated == [11, 12]
    assert rep["keep"].prompt.tolist() == [3, 4]
    assert rep["keep"].mnt == 8
    # ...and the journal keeps accepting appends after the swap
    j.log_token("keep", 13)
    j.close()
    assert replay_journal(path)["keep"].generated == [11, 12, 13]


def test_journal_replay_over_compacted_plus_torn_tail(tmp_path):
    """The two defenses compose: compaction's atomic publish, then a torn
    append on the NEW segment — replay trusts the compacted snapshot and
    ignores the torn tail."""
    path = os.fspath(tmp_path / "j.wal")
    j = RequestJournal(path, fsync_every=1)
    j.log_admit("a", np.array([5], np.int32), 6, [], "default",
                "interactive")
    j.log_token("a", 9)
    j.log_admit("b", np.array([6], np.int32), 6, [], "default", "batch")
    j.log_finish("b", "eos")
    stats = j.compact()
    assert stats["kept"] == 1 and stats["dropped"] == 1
    j.log_token("a", 10)  # post-compaction append lands in the new segment
    j.close()
    with open(path, "ab") as f:
        f.write(b"deadbeef|{\"k\":\"tok\",\"rid\":\"a\"")  # torn, no newline
    rep = replay_journal(path)
    assert set(rep) == {"a"}
    assert not rep["a"].finished
    assert rep["a"].generated == [9, 10]


def test_journal_compaction_under_live_engine(lm, tmp_path):
    """An engine journaling through a tiny compact_bytes budget compacts
    mid-traffic without losing replayability or corrupting results."""
    path = os.fspath(tmp_path / "decode.wal")
    eng = _engine(lm, journal_path=path, journal_fsync_every=1,
                  journal_compact_bytes=1024)
    try:
        for _ in range(2):  # several generations of churn
            handles = [eng.submit(p, n) for p, n, _ in lm.cases]
            for (_, _, ref), h in zip(lm.cases, handles):
                assert np.array_equal(h.result(timeout=120).tokens, ref)
        assert eng._journal.compactions_total >= 1
        eng._journal.flush()
        rep = replay_journal(path)
        assert all(r.finished for r in rep.values())
    finally:
        eng.close(timeout=30)
    eng.kv.assert_no_leaks()


# ---- close() drain deadline (satellite) ------------------------------------


def test_close_enforces_drain_deadline_force_finishes(lm):
    """A drain that cannot complete within close(timeout) must not hang
    the handles: stragglers complete with finish_reason="drain_timeout"
    and the page-leak invariant still holds."""
    eng = _engine(lm)
    with faults.injected(
        faults.FaultSpec(faults.DECODE_STEP, "stall", stall_s=0.4,
                         times=10 ** 9)
    ):
        h = eng.submit(lm.cases[0][0], lm.cases[0][1])
        time.sleep(0.05)  # let it admit and start stepping
        unjoined = eng.close(timeout=0.05)
        assert unjoined == []  # the deadline was ENFORCED, not just logged
        out = h.result(timeout=10)
        assert out.finish_reason == "drain_timeout"
        assert len(out.tokens) < lm.cases[0][1]  # partial, not hung
    eng.kv.assert_no_leaks()


# ---- trace continuity: rescue, restart replay, compaction (fleet obs) ------


def test_migration_keeps_one_trace_across_engines(lm):
    """A breaker-trip migration must CONTINUE the submitter's trace on
    the rescuing engine: one trace id, a ``serving.rescue`` span naming
    both engines, zero orphans, and the root recorded by the engine that
    finished the request."""
    from paddle_tpu import tracing

    ea, eb = _engine(lm), _engine(lm)
    fleet = DecodeFleet([ea, eb])
    try:
        with faults.injected(
            faults.FaultSpec(faults.DECODE_STEP, "error", after=1,
                             times=10 ** 9,
                             match={"engine": ea.metrics.engine_label})
        ):
            p, n, ref = lm.cases[0]
            h = ea.submit(p, n)  # pin to A; A's breaker will trip
            out = h.result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        assert h.trace is not None
        spans = tracing.spans_for_trace(h.trace.trace_id)
        assert tracing.validate_trace(spans, multi_engine=True) == []
        assert "serving.rescue" in {s.name for s in spans}
        engines = {s.attrs.get("engine") for s in spans} - {None}
        assert engines == {ea.metrics.engine_label,
                           eb.metrics.engine_label}
        roots = [s for s in spans if s.context.parent_id is None]
        assert len(roots) == 1, [(s.name, s.attrs) for s in roots]
        assert roots[0].attrs["engine"] == eb.metrics.engine_label
    finally:
        fleet.close(timeout=30)


def test_journal_replay_restores_trace_ids(tmp_path):
    """Admit/handoff records carry the W3C traceparent ("tp"); replay
    surfaces it, pre-trace records replay as trace-less, and compaction
    keeps it in the rewritten snapshot."""
    from paddle_tpu import tracing

    path = os.fspath(tmp_path / "j.wal")
    ctx = tracing.SpanContext.new_trace()
    j = RequestJournal(path, fsync_every=1)
    j.log_admit("r1", np.array([5, 6], np.int32), 4, [], "default",
                "interactive", trace=ctx.to_traceparent())
    j.log_token("r1", 7)
    j.log_admit("r2", np.array([9], np.int32), 3, [], "default",
                "interactive")  # a pre-trace writer's record
    rep = replay_journal(path)
    assert rep["r1"].trace == ctx.to_traceparent()
    assert rep["r2"].trace is None
    # compaction rewrites snapshots: the traceparent must survive it
    j.compact()
    j.close()
    rep2 = replay_journal(path)
    assert rep2["r1"].trace == ctx.to_traceparent()
    assert rep2["r1"].generated == [7]
    assert rep2["r2"].trace is None


def test_restart_resume_continues_original_trace(lm, tmp_path):
    """Crash → journal replay: the resumed request decodes under the
    ORIGINAL trace id (restored from the journaled traceparent), not a
    freshly minted one — the fleet trace survives the process."""
    from paddle_tpu import tracing

    path = os.fspath(tmp_path / "decode.wal")
    e1 = _engine(lm, journal_path=path, journal_fsync_every=1)
    p, n, ref = lm.cases[0]
    h1 = e1.submit(p, n)
    assert h1.trace is not None
    deadline = time.monotonic() + 60
    while (e1.metrics.snapshot()["tokens_total"] < 2
           and time.monotonic() < deadline):
        time.sleep(0.005)
    e1.kill()
    with pytest.raises(Exception):
        h1.result(timeout=10)

    e2 = _engine(lm, journal_path=path)
    try:
        resumed = resume_incomplete(e2, path)
        assert len(resumed) == 1
        (handle, _n_delivered), = resumed.values()
        out = handle.result(timeout=120)
        assert np.array_equal(out.tokens, ref)
        assert handle.trace is not None
        assert handle.trace.trace_id == h1.trace.trace_id  # SAME trace
        spans = tracing.spans_for_trace(h1.trace.trace_id)
        assert tracing.validate_trace(spans, multi_engine=True) == []
        # the killed engine never finished the request, so exactly one
        # root exists: the resuming engine's
        roots = [s for s in spans if s.context.parent_id is None]
        assert len(roots) == 1
        assert roots[0].attrs["engine"] == e2.metrics.engine_label
    finally:
        e2.close(timeout=30)
