"""paddle_tpu.serving.shardgroup — tp replica-group acceptance tests.

The acceptance contract (ISSUE 16): a tp=2 replica group — params and
paged KV sharded over its submesh, one pjit'd step per group — serves
token-exactly vs the single-device ``generate()`` reference across GQA /
RoPE / sliding-window model variants under mixed traffic, with the
compile-once invariant intact (``decode_step_cache_size() == 1``).
Also covered here: the :func:`spec_for` rule-table API (first-match,
fallback, rank enforcement), non-divisible-dim degradation, placement
assertions (params and KV pages actually span the group's devices),
same-degree group→group handoff adoption vs cross-degree re-prefill
degradation, and per-shard straggler localization. The group-kill →
cross-group migration leg lives in ``test_serving_recovery.py`` next to
the single-device migration contract it extends.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu import models
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.models.transformer_lm import generate
from paddle_tpu.parallel.mesh import TP_AXIS, partition_devices, tp_submesh
from paddle_tpu.parallel.sharding import degrade_spec, spec_for
from paddle_tpu.resilience import faults
from paddle_tpu.serving import DecodeConfig, DecodeEngine
from paddle_tpu.serving.disagg import DECODE, PREFILL, DisaggRouter, HandoffPayload
from paddle_tpu.serving.engine import ServingConfig
from paddle_tpu.serving.shardgroup import (
    KV_HEAD_DIM,
    GroupLayout,
    GroupStragglerWatch,
    ReplicaGroup,
    default_layout,
    make_groups,
    probe_members,
)

VOCAB = 97

DC = dict(max_slots=3, page_size=4, max_context=40, prefill_chunk=8,
          num_pages=14)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 virtual devices (conftest)")


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    yield
    faults.clear()


def _build(**overrides):
    spec = models.get_model("transformer_lm", seq_len=64, vocab=VOCAB,
                            d_model=32, d_inner=64, num_heads=4, n_layers=2,
                            **overrides)
    cfg = spec.extra["cfg"]
    rng = np.random.RandomState(1)
    variables = spec.model.init(0, *spec.synth_batch(2, rng))
    cases = []
    for _ in range(3):
        t = int(rng.randint(4, 12))
        n = int(rng.randint(8, 16))
        prompt = rng.randint(1, VOCAB, size=(t,)).astype(np.int32)
        ref = np.asarray(generate(variables, jnp.asarray(prompt[None]),
                                  n, cfg))[0]
        cases.append((prompt, n, ref))
    return cfg, variables, cases


def _engine(variables, cfg, group=None, label=None, **over):
    kw = dict(DC)
    kw.update(over)
    return DecodeEngine(variables, cfg, decode=DecodeConfig(**kw),
                        group=group,
                        config=ServingConfig(engine_label=label))


# ---- spec_for rule table (satellite: parallel.sharding API) ----------------


def test_spec_for_first_match_and_fallback():
    rules = (("*/q/w", P(None, "tp")), ("*/q/*", P("tp")))
    assert spec_for("layer_0/self_attn/q/w", rules) == P(None, "tp")
    assert spec_for("layer_0/self_attn/q/b", rules) == P("tp")
    assert spec_for("layer_norm/scale", rules) == P()
    assert spec_for("emb/word_emb", rules, fallback=P("x")) == P("x")


def test_spec_for_rank_mismatch_enforces():
    rules = (("*/q/w", P(None, "tp")),)
    with pytest.raises(EnforceError):
        spec_for("layer_0/self_attn/q/w", rules, ndim=1)


def test_degrade_spec_drops_non_divisible_dims():
    mesh = tp_submesh(jax.devices()[:2])
    # 64 divides by tp=2, 97 (vocab) does not, bare dims pad to None
    assert degrade_spec(mesh, P(None, TP_AXIS), (32, 64)) == P(None, TP_AXIS)
    assert degrade_spec(mesh, P(TP_AXIS), (97,)) == P(None)
    assert degrade_spec(mesh, P(TP_AXIS), (64, 32)) == P(TP_AXIS, None)


def test_spec_for_overlapping_rules_earlier_shadows_later():
    # the general rule first: the specific one below it can never win
    shadowed = (("*/q/*", P("tp")), ("*/q/w", P(None, "tp")))
    assert spec_for("layer_0/self_attn/q/w", shadowed) == P("tp")
    # specific-before-general is the intended ordering
    ordered = (("*/q/w", P(None, "tp")), ("*/q/*", P("tp")))
    assert spec_for("layer_0/self_attn/q/w", ordered) == P(None, "tp")
    assert spec_for("layer_0/self_attn/q/b", ordered) == P("tp")


def test_group_layout_with_zero_matches_replicates_everything():
    mesh = tp_submesh(jax.devices()[:2])
    layout = GroupLayout(rules=(("other_model/*", P(None, "tp")),),
                         optional=())
    assert layout.param_spec("layer_0/self_attn/q/w", (32, 32), mesh) == \
        P(None, None)
    assert layout.param_spec("emb/embedding/word_emb", (97, 32), mesh) == \
        P(None, None)


# ---- layout lint at engine init (analysis.shard_analysis wiring) -----------


def test_engine_init_rejects_bad_layout_before_placement():
    cfg, variables, _ = _build()
    group = make_groups(2)[0]
    bad = GroupLayout(rules=(("*/self_attn/qq/w", P(None, TP_AXIS)),),
                      optional=())
    with pytest.raises(EnforceError, match="shard-dead-rule"):
        DecodeEngine(variables, cfg, decode=DecodeConfig(**DC),
                     group=group, layout=bad)


def test_engine_init_lint_layout_off_places_anyway():
    cfg, variables, _ = _build()
    group = make_groups(2)[0]
    bad = GroupLayout(rules=(("*/self_attn/qq/w", P(None, TP_AXIS)),),
                      optional=())
    eng = DecodeEngine(variables, cfg,
                       decode=DecodeConfig(lint_layout=False, **DC),
                       group=group, layout=bad)
    try:
        # dead rule means no param matched: everything degraded/replicated
        assert eng._params is not None
    finally:
        eng.close()


def test_engine_init_accepts_default_layout():
    # the lint is ON by default and the shipped layout must be clean for
    # the swiglu variant too (gate rules are load-bearing there)
    cfg, variables, _ = _build(ffn_activation="swiglu")
    eng = _engine(variables, cfg, group=make_groups(2)[0])
    eng.close()


# ---- group construction ----------------------------------------------------


def test_make_groups_slices_devices_in_order():
    groups = make_groups(2, jax.devices()[:4])
    assert [g.tp for g in groups] == [2, 2]
    assert groups[0].devices == tuple(jax.devices()[:2])
    assert groups[1].devices == tuple(jax.devices()[2:4])
    assert groups[0].name == "group0" and groups[1].name == "group1"
    assert set(groups[0].mesh.axis_names) == {TP_AXIS}


def test_partition_devices_drops_ragged_tail():
    devs = jax.devices()[:3]
    assert partition_devices(2, devs) == [tuple(devs[:2])]
    with pytest.raises(EnforceError):
        partition_devices(0, devs)
    with pytest.raises(EnforceError):
        ReplicaGroup(())


def test_layout_shards_params_and_kv_across_members():
    """The layout must actually spread bytes: column/row-parallel weights
    and the KV head dim land distributed over the group's devices;
    non-divisible dims (vocab=97) stay replicated."""
    cfg, variables, _ = _build()
    group = make_groups(2)[0]
    layout = default_layout()
    sharded = layout.shard_params(group, dict(variables.params.items()))
    qw = sharded["layer_0/self_attn/q/w"]
    assert qw.sharding.spec == P(None, TP_AXIS)
    assert len(qw.sharding.device_set) == 2
    ow = sharded["layer_0/self_attn/out/w"]
    assert ow.sharding.spec == P(TP_AXIS, None)
    logits = sharded["project/logits/w"]  # 32x97: vocab not divisible
    assert logits.sharding.spec in (P(), P(None), P(None, None))
    # KV pages [L, num_pages, page_size, H_kv * dh] shard on the heads,
    # the major part of the last dim
    kv_spec = layout.kv_page_spec((2, 14, 4, 4 * 8), group.mesh, 4)
    assert kv_spec == P(None, None, None, TP_AXIS)
    assert kv_spec[KV_HEAD_DIM] == TP_AXIS
    # GQA with H_kv=1 < tp: degrade to replicated, never a crash
    assert layout.kv_page_spec((2, 14, 4, 1 * 8), group.mesh, 1) == P(
        *([None] * 4))


@pytest.mark.parametrize("kv_heads,dh,tp,sharded", [
    (4, 8, 4, True),    # a head a shard
    (8, 8, 4, True),    # two heads a shard
    (2, 8, 4, False),   # the width 16 divides by 4, the 2 heads do not
    (3, 8, 2, False),   # the width 24 divides by 2, the 3 heads do not
    (1, 64, 2, False),  # multi-query: one head cannot be split
], ids=["4x8_tp4", "8x8_tp4", "2x8_tp4", "3x8_tp2", "1x64_tp2"])
def test_kv_page_spec_shards_whole_heads_or_replicates(kv_heads, dh, tp, sharded):
    """The merged last axis of a page array shards only when the head COUNT
    divides tp, so a shard always holds whole heads: the width ``H_kv * dh``
    may divide where the count does not, and a split there would cut a head
    in two. The placed array's shards are then ``H_kv / tp`` heads wide."""
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices")
    group = make_groups(tp)[0]
    shape = (2, 14, 4, kv_heads * dh)
    spec = default_layout().kv_page_spec(shape, group.mesh, kv_heads)
    assert spec == (P(None, None, None, TP_AXIS) if sharded else P(*([None] * 4)))
    pages = jax.device_put(
        np.zeros(shape, np.float32),
        default_layout().kv_page_sharding(group, shape, kv_heads))
    widths = {s.data.shape[-1] for s in pages.addressable_shards}
    assert widths == {kv_heads * dh // tp if sharded else kv_heads * dh}


# ---- tentpole acceptance: tp=2 token-exact vs generate() -------------------


@pytest.mark.parametrize("variant", [
    {},                               # MHA baseline
    dict(num_kv_heads=2),             # GQA: KV heads == tp, pages shard
    dict(pos_encoding="rope"),        # rotary path
    dict(attention_window=8),         # sliding window
], ids=["mha", "gqa", "rope", "window"])
def test_group_token_exact_vs_generate(variant):
    """One pjit'd step over a tp=2 submesh must reproduce the greedy
    single-device reference bit-for-token under mixed in-flight traffic,
    compiling exactly once."""
    cfg, variables, cases = _build(**variant)
    eng = _engine(variables, cfg, group=make_groups(2)[0], label="tp2")
    try:
        handles = [eng.submit(p, n) for p, n, _ in cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(cases, outs):
            assert np.array_equal(out.tokens, ref)
        assert eng.decode_step_cache_size() == 1
        assert eng.tp_degree == 2
        snap = eng.metrics.snapshot()
        assert snap["errors_total"] == 0, snap
    finally:
        eng.close(timeout=30)
    eng.kv.assert_no_leaks()


def test_group_speculative_decode_token_exact():
    """Draft-and-verify under a group: the draft's page arrays shard over
    the same submesh and ``paged_verify_step`` stays compile-once."""
    cfg, variables, cases = _build()
    dspec = models.get_model("transformer_lm", seq_len=64, vocab=VOCAB,
                             d_model=32, d_inner=64, num_heads=4, n_layers=1)
    dvars = dspec.model.init(0, *dspec.synth_batch(2, np.random.RandomState(2)))
    eng = DecodeEngine(variables, cfg,
                       decode=DecodeConfig(spec_tokens=3, **DC),
                       draft_variables=dvars, draft_cfg=dspec.extra["cfg"],
                       group=make_groups(2)[0])
    try:
        handles = [eng.submit(p, n) for p, n, _ in cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(cases, outs):
            assert np.array_equal(out.tokens, ref)
        assert eng.decode_step_cache_size() == 1
        assert eng.verify_step_cache_size() == 1
    finally:
        eng.close(timeout=30)


# ---- handoff across groups -------------------------------------------------


def test_same_degree_handoff_adopts_pages():
    """tp=2 prefill group → tp=2 decode group: the gathered wire pages
    (full logical pages) implant directly — no re-prefill."""
    cfg, variables, cases = _build()
    g0, g1 = make_groups(2)[:2]
    pre = _engine(variables, cfg, group=g0, label="pre-g0")
    dec = _engine(variables, cfg, group=g1, label="dec-g1")
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          transport="serialized")
    try:
        from paddle_tpu import tracing

        handles = [router.submit(p, n) for p, n, _ in cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(cases, outs):
            assert np.array_equal(out.tokens, ref)
        snap = dec.metrics.snapshot()
        assert snap["handoffs_in_total"] == len(cases), snap
        assert snap["recovered_total"] == 0, snap
        # adoption continues the submitter's trace across the groups
        for h in handles:
            assert h.trace is not None
            spans = tracing.spans_for_trace(h.trace.trace_id)
            assert tracing.validate_trace(spans, multi_engine=True) == []
            assert "serving.handoff.adopt" in {s.name for s in spans}
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_cross_degree_handoff_degrades_to_reprefill():
    """tp=2 prefill → tp=1 decode: adopting another degree's pages would
    splice two partitioned programs' numerics mid-sequence, so adoption
    is refused and the decode worker re-prefills — token-exact, never
    lost."""
    cfg, variables, cases = _build()
    pre = _engine(variables, cfg, group=make_groups(2)[0], label="pre-tp2")
    dec = _engine(variables, cfg, group=None, label="dec-tp1")
    router = DisaggRouter([pre, dec], [PREFILL, DECODE],
                          transport="serialized")
    try:
        from paddle_tpu import tracing

        handles = [router.submit(p, n) for p, n, _ in cases]
        outs = [h.result(timeout=120) for h in handles]
        for (_, _, ref), out in zip(cases, outs):
            assert np.array_equal(out.tokens, ref)
        snap = dec.metrics.snapshot()
        assert snap["handoffs_in_total"] == 0, snap
        assert snap["recovered_total"] == len(cases), snap
        # the refused adoption re-prefills on the decode worker — still
        # ONE trace per request, with the root on the finishing engine
        # and no adopt span (the pages never implanted)
        for h in handles:
            assert h.trace is not None
            spans = tracing.spans_for_trace(h.trace.trace_id)
            assert tracing.validate_trace(spans, multi_engine=True) == []
            names = {s.name for s in spans}
            assert "serving.handoff.adopt" not in names
            roots = [s for s in spans if s.context.parent_id is None]
            assert len(roots) == 1
            assert roots[0].attrs["engine"] == dec.metrics.engine_label
    finally:
        router.close(30)
    pre.kv.assert_no_leaks()
    dec.kv.assert_no_leaks()


def test_handoff_wire_format_backward_compatible():
    """Blobs written before the ``tp_degree`` header parse as degree 1,
    and the field round-trips when present."""
    p = HandoffPayload(rid="r0", prompt=np.arange(1, 6, dtype=np.int32),
                       generated=[7], mnt=8, cur_len=8, last_tok=7,
                       page_size=4, k_pages=[], v_pages=[], tp_degree=2)
    q = HandoffPayload.from_bytes(p.to_bytes())
    assert q.tp_degree == 2
    legacy = HandoffPayload(rid="r1", prompt=np.arange(1, 6, dtype=np.int32),
                            generated=[7], mnt=8, cur_len=8, last_tok=7,
                            page_size=4, k_pages=[], v_pages=[])
    assert HandoffPayload.from_bytes(legacy.to_bytes()).tp_degree == 1


# ---- per-member canary + straggler localization ----------------------------


def test_probe_members_times_every_shard():
    group = make_groups(2)[0]
    times = probe_members(group, engine_label="probe-test")
    assert sorted(times) == [0, 1]
    assert all(t >= 0.0 for t in times.values())


def test_probe_members_fault_targets_one_shard():
    group = make_groups(2)[0]
    with faults.injected(
        faults.FaultSpec(faults.GROUP_MEMBER, "error",
                         match={"shard": 1})
    ) as plan:
        with pytest.raises(OSError):
            probe_members(group, engine_label="probe-test")
        assert plan.all_fired()


def test_straggler_watch_localizes_slow_shard():
    group = make_groups(2)[0]
    watch = GroupStragglerWatch(group, ratio=4.0, min_samples=3)
    flagged = None
    for _ in range(8):
        skew, shard = watch.observe({0: 0.001, 1: 0.050})
        if shard is not None:
            flagged = shard
    assert flagged == 1
    assert skew > 4.0


def test_straggler_watch_quiet_when_balanced():
    group = make_groups(2)[0]
    watch = GroupStragglerWatch(group, ratio=4.0, min_samples=3)
    for _ in range(8):
        skew, shard = watch.observe({0: 0.002, 1: 0.002})
        assert shard is None
    assert skew == pytest.approx(1.0, abs=0.5)
