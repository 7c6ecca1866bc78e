"""Static sharding-layout analyzer (``paddle_tpu/analysis/shard_analysis.py``):
zero-FLOP PartitionSpec propagation over eval_shape param trees — dead
rules, rank mismatches, silently-degrading dims (with HBM cost),
cross-layout conflicts, KV-geometry violations, the tp comm report, and
the DecodeEngine init hook. Everything here runs off plain ``{axis: size}``
dicts — no mesh, no devices — except the engine-hook tests at the bottom.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_tpu.analysis.shard_analysis import (
    analyze_layout,
    analyze_model,
    compare_layouts,
    eval_param_shapes,
    lint_group_layout_or_raise,
    tp_comm_report,
)
from paddle_tpu.core import logging as ptlog
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import EnforceError
from paddle_tpu.serving.shardgroup import GroupLayout, default_layout

TP4 = {"tp": 4}

PARAMS = {
    "layer_0/self_attn/q/w": (512, 512),
    "layer_0/self_attn/q/b": (512,),
    "layer_0/self_attn/out/w": (512, 512),
    "layer_0/ffn/fc1/w": (512, 2048),
    "layer_0/ffn/fc2/w": (2048, 512),
    "emb/embedding/word_emb": (97, 512),
}


def _codes(diags):
    return sorted(d.code for d in diags)


# ---- per-finding fixtures ------------------------------------------------


def test_clean_layout_has_no_findings():
    layout = GroupLayout(rules=(
        ("*/self_attn/q/w", P(None, "tp")),
        ("*/self_attn/out/w", P("tp", None)),
    ), optional=())
    assert analyze_layout(PARAMS, layout, TP4) == []


def test_dead_rule_is_an_error_with_rule_index():
    layout = GroupLayout(rules=(
        ("*/self_attn/qq/w", P(None, "tp")),   # typo: matches nothing
        ("*/self_attn/q/w", P(None, "tp")),
    ), optional=())
    diags = analyze_layout(PARAMS, layout, TP4, where="lay")
    assert _codes(diags) == ["shard-dead-rule"]
    assert diags[0].severity == "error"
    assert diags[0].where == "lay:rule[0]"


def test_optional_rules_are_exempt_from_dead_rule():
    layout = GroupLayout(rules=(
        ("*/ffn/gate/w", P(None, "tp")),       # swiglu-only family
        ("*/self_attn/q/w", P(None, "tp")),
    ), optional=("*/ffn/gate/w",))
    assert analyze_layout(PARAMS, layout, TP4) == []


def test_rank_mismatch_is_an_error():
    layout = GroupLayout(rules=(
        ("*/self_attn/q/b", P(None, "tp")),    # 2-dim spec on a 1-d bias
    ), optional=())
    diags = analyze_layout(PARAMS, layout, TP4)
    assert _codes(diags) == ["shard-rank-mismatch"]
    assert diags[0].where == "layer_0/self_attn/q/b"


def test_silent_degrade_warns_with_hbm_cost():
    layout = GroupLayout(rules=(
        ("emb/*", P("tp", None)),              # 97 % 4 != 0
    ), optional=())
    diags = analyze_layout(PARAMS, layout, TP4)
    assert _codes(diags) == ["shard-silent-degrade"]
    d = diags[0]
    assert d.severity == "warning"
    # full param stays resident: cost = total*(1 - 1/4) = 97*512*4*3/4
    assert "145.5KiB" in d.message


def test_unknown_axis_warns():
    layout = GroupLayout(rules=(
        ("*/self_attn/q/w", P(None, "model")),  # training-axis leak
    ), optional=())
    diags = analyze_layout(PARAMS, layout, TP4)
    assert _codes(diags) == ["shard-unknown-axis"]
    assert diags[0].severity == "warning"


def test_bare_rule_table_is_accepted():
    # rule tables without a GroupLayout wrapper analyze too (spec_for users)
    diags = analyze_layout(PARAMS, (("*/nope", P("tp")),), TP4)
    assert _codes(diags) == ["shard-dead-rule"]


def test_one_run_lists_every_offender():
    layout = GroupLayout(rules=(
        ("*/self_attn/qq/w", P(None, "tp")),
        ("*/self_attn/q/b", P(None, "tp")),
        ("emb/*", P("tp", None)),
        ("*/self_attn/q/w", P(None, "mp")),
    ), optional=())
    assert _codes(analyze_layout(PARAMS, layout, TP4)) == [
        "shard-dead-rule", "shard-rank-mismatch",
        "shard-silent-degrade", "shard-unknown-axis",
    ]


# ---- cross-layout conflicts ----------------------------------------------


def test_conflicting_layouts_flag_each_param():
    serving = GroupLayout(rules=(("*/q/w", P(None, "tp")),), optional=())
    training = GroupLayout(rules=(("*/q/w", P("tp", None)),), optional=())
    diags = compare_layouts(
        {"serving": serving, "training": training}, PARAMS, TP4)
    assert _codes(diags) == ["shard-conflict"]
    assert diags[0].where == "layer_0/self_attn/q/w"
    assert "serving" in diags[0].message and "training" in diags[0].message


def test_identical_effective_specs_do_not_conflict():
    # textually different rules, same effective spec after degrade:
    # 97-row embedding degrades to replicated either way
    a = GroupLayout(rules=(("emb/*", P("tp", None)),), optional=())
    b = GroupLayout(rules=(), optional=())
    assert compare_layouts({"a": a, "b": b},
                           {"emb/embedding/word_emb": (97, 512)}, TP4) == []


# ---- KV-page geometry ----------------------------------------------------


KV_SHAPE = (2, 14, 4, 4 * 8)  # [L, num_pages, page_size, H_kv * dh]
KV_GEO = {"num_pages": 14, "page_size": 4, "max_slots": 3, "pages_per_slot": 10,
          "kv_heads": 4}


def test_default_kv_rule_passes_geometry():
    diags = analyze_layout({}, GroupLayout(rules=(), optional=()), {"tp": 2},
                           kv_page_shape=KV_SHAPE, kv_geometry=KV_GEO)
    assert diags == []


def test_kv_rule_sharding_page_ids_is_an_error():
    layout = GroupLayout(rules=(), optional=(),
                         kv_rule=P(None, "tp", None, None))
    diags = analyze_layout({}, layout, {"tp": 2},
                           kv_page_shape=KV_SHAPE, kv_geometry=KV_GEO)
    assert _codes(diags) == ["shard-kv-geometry"]
    assert "page ids" in diags[0].message


def test_kv_rule_sharding_page_offsets_is_an_error():
    layout = GroupLayout(rules=(), optional=(),
                         kv_rule=P(None, None, "tp", None))
    diags = analyze_layout({}, layout, {"tp": 2},
                           kv_page_shape=KV_SHAPE, kv_geometry=KV_GEO)
    assert _codes(diags) == ["shard-kv-geometry"]
    assert "page offsets" in diags[0].message


def test_kv_shape_disagreeing_with_geometry_is_an_error():
    diags = analyze_layout({}, GroupLayout(rules=(), optional=()), {"tp": 2},
                           kv_page_shape=(2, 99, 4, 32), kv_geometry=KV_GEO)
    assert _codes(diags) == ["shard-kv-geometry"]
    assert "num_pages" in diags[0].message


def test_kv_head_non_divisible_warns_about_lost_memory_win():
    diags = analyze_layout({}, GroupLayout(rules=(), optional=()), {"tp": 3},
                           kv_page_shape=KV_SHAPE, kv_geometry=KV_GEO)
    assert _codes(diags) == ["shard-silent-degrade"]
    assert diags[0].severity == "warning"


@pytest.mark.parametrize("kv_heads,tp,degrades", [
    (4, 4, False), (2, 4, True), (1, 2, True), (None, 4, False)],
    ids=["4_heads_tp4", "2_heads_tp4", "1_head_tp2", "heads_unknown_tp4"])
def test_kv_degrade_is_decided_by_the_head_count_not_the_width(kv_heads, tp, degrades):
    """As ``GroupLayout.kv_page_spec``: the merged axis (32 wide here) divides
    by 2 and by 4 whatever the heads, but a shard must hold whole heads, so 2
    heads over tp 4 replicate and the analyzer says so. A geometry that does
    not say how many heads the row holds is judged by the width alone."""
    geo = {k: v for k, v in KV_GEO.items() if k != "kv_heads"}
    if kv_heads is not None:
        geo["kv_heads"] = kv_heads
    diags = analyze_layout({}, GroupLayout(rules=(), optional=()), {"tp": tp},
                           kv_page_shape=KV_SHAPE, kv_geometry=geo)
    assert _codes(diags) == (["shard-silent-degrade"] if degrades else [])
    if degrades:
        assert f"KV head count {kv_heads} " in diags[0].message


def test_analyze_model_hands_the_head_count_to_the_kv_check():
    """The one-call analysis of a GQA model whose 2 KV heads of 8 make a row
    of 16: over tp 4 the row divides and the heads do not."""
    diags, _ = analyze_model("transformer_lm", tp=4, vocab=64, d_model=32,
                             d_inner=64, num_heads=4, num_kv_heads=2, n_layers=1)
    kv = [d for d in diags if d.where == "kv_pages"]
    assert _codes(kv) == ["shard-silent-degrade"] and "head count 2" in kv[0].message


# ---- tp comm report ------------------------------------------------------


def test_comm_report_counts_row_parallel_boundaries():
    report = tp_comm_report(PARAMS, default_layout(), TP4)
    names = [b.param for b in report.boundaries]
    assert names == ["layer_0/ffn/fc2/w", "layer_0/self_attn/out/w"]
    out = next(b for b in report.boundaries
               if b.param == "layer_0/self_attn/out/w")
    assert out.payload_bytes == 512 * 4
    assert out.wire_bytes == int(512 * 4 * 2 * 3 / 4)  # ring: 2(n-1)/n
    assert report.total_payload_bytes == (512 + 512) * 4
    assert "wire/device" in report.format()


def test_comm_report_tp1_has_zero_wire_bytes():
    report = tp_comm_report(PARAMS, default_layout(), {"tp": 1})
    assert report.boundaries  # boundaries exist, they just cost nothing
    assert report.total_wire_bytes == 0


def test_degraded_boundary_drops_out_of_comm_report():
    # a row-parallel weight whose dim 0 doesn't divide tp never all-reduces
    layout = GroupLayout(rules=(("emb/*", P("tp", None)),), optional=())
    report = tp_comm_report({"emb/embedding/word_emb": (97, 512)}, layout, TP4)
    assert report.boundaries == ()


# ---- whole-model analysis (jax.eval_shape path) --------------------------


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_default_layout_is_clean_on_transformer_lm(tp):
    # the ISSUE's acceptance bar: zero findings on the shipped layout
    diags, report = analyze_model(tp=tp)
    assert diags == []
    assert len(report.boundaries) == 12  # 2 row-parallel weights × 6 layers


def test_eval_param_shapes_matches_real_init():
    shapes, cfg = eval_param_shapes(
        d_model=32, d_inner=64, num_heads=4, n_layers=2, vocab=97, max_len=64)
    assert shapes["layer_0/self_attn/q/w"].shape == (32, 32)
    assert shapes["layer_0/ffn/fc1/w"].shape == (32, 64)
    assert cfg["d_model"] == 32


def test_analyze_model_flags_seeded_bad_layout():
    bad = GroupLayout(rules=(
        ("*/self_attn/qq/w", P(None, "tp")),
        ("*/self_attn/q/b", P(None, "tp")),
    ), optional=())
    diags, _ = analyze_model(tp=2, layout=bad)
    # one rank-mismatch per matching layer bias, one dead rule
    assert set(_codes(diags)) == {"shard-dead-rule", "shard-rank-mismatch"}
    assert sum(1 for d in diags if d.code == "shard-rank-mismatch") == 6


# ---- engine hook + runtime counter agreement -----------------------------


def test_lint_group_layout_or_raise_raises_on_errors():
    mesh = jax.make_mesh((1,), ("tp",))
    bad = GroupLayout(rules=(("*/nope", P("tp")),), optional=())
    with pytest.raises(EnforceError, match="shard-dead-rule"):
        lint_group_layout_or_raise(PARAMS, bad, mesh, where="test")


def test_lint_group_layout_or_raise_warns_but_returns_on_warnings():
    ptlog.reset_warn_once()
    mesh = jax.make_mesh((1,), ("tp",))
    # axis size 1 divides everything; unknown axis is warning-only
    warn = GroupLayout(rules=(("*/q/w", P(None, "model")),), optional=())
    diags = lint_group_layout_or_raise(PARAMS, warn, mesh, where="test")
    assert _codes(diags) == ["shard-unknown-axis"]


def test_runtime_degrade_counter_agrees_with_static_report():
    """The satellite contract: what the analyzer reports as
    shard-silent-degrade is exactly what degrade_spec counts at runtime."""
    from paddle_tpu.parallel.sharding import degrade_spec

    ptlog.reset_warn_once()
    prof.reset_metrics()
    mesh = jax.make_mesh((jax.device_count(),), ("tp",))
    tp = jax.device_count()
    assert tp > 1, "conftest forces 8 virtual CPU devices"

    spec = degrade_spec(mesh, P("tp", None), (97, 512), name="emb")
    assert spec == P(None, None)
    assert prof.counters().get("sharding.degraded_total") == 1.0

    # repeat: counter increments, warn_once stays quiet after the first
    degrade_spec(mesh, P("tp", None), (97, 512), name="emb")
    assert prof.counters().get("sharding.degraded_total") == 2.0

    static = analyze_layout(
        {"emb": (97, 512)},
        GroupLayout(rules=(("emb", P("tp", None)),), optional=()),
        {"tp": tp})
    assert _codes(static) == ["shard-silent-degrade"]


def test_missing_axis_degrade_stays_silent_at_runtime():
    # the documented any-mesh fallback must NOT count or warn
    from paddle_tpu.parallel.sharding import degrade_spec

    prof.reset_metrics()
    mesh = jax.make_mesh((1,), ("data",))
    assert degrade_spec(mesh, P("tp", None), (8, 8), name="w") == P(None, None)
    assert "sharding.degraded_total" not in prof.counters()
