#!/usr/bin/env python
"""Static-analysis CI gate: shard-layout analyzer + retrace lint.

Three legs, all zero-FLOP (no devices are touched anywhere):

1. **Shipped layout is clean** — ``analysis.shard_analysis.analyze_model``
   runs the ``default_layout()`` over ``transformer_lm``'s
   ``jax.eval_shape`` param tree at tp ∈ {1, 2, 4}: ZERO findings
   allowed, and the comm report must show exactly the Megatron boundary
   set (one all-reduce per row-parallel weight — 2 × n_layers).
2. **Seeded violations are caught** — a deliberately broken layout (dead
   rule, rank mismatch, silent degrade, cross-layout conflict, sharded
   KV page ids) must produce EXACTLY the expected stable diagnostic
   codes; a gate that cannot see a planted bug proves nothing.
3. **Tree is retrace-clean** — ``analysis.retrace_lint`` over the whole
   package reports no errors, and a reconstructed dynamic-closure
   retrace bug (the trap the compile-once invariant exists to stop) is
   caught in a fixture.

Exit code 0 = every leg held; 1 = anything less. CI-registered next to
``tools/chaos_smoke.py`` (README "Static analysis").
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_FAILURES = []


def _check(ok: bool, label: str, detail: str = "") -> None:
    status = "ok" if ok else "FAIL"
    print(f"[analysis_gate] {status:4s} {label}" + (f" — {detail}" if detail and not ok else ""))
    if not ok:
        _FAILURES.append(label)


def leg_shipped_layout_clean() -> None:
    from paddle_tpu.analysis.shard_analysis import analyze_model

    for tp in (1, 2, 4):
        diags, report = analyze_model(tp=tp)
        _check(diags == [],
               f"default_layout() clean on transformer_lm @ tp={tp}",
               "; ".join(str(d) for d in diags))
        n_layers = 6  # transformer_lm BASE_CFG
        _check(len(report.boundaries) == 2 * n_layers,
               f"comm report has {2 * n_layers} row-parallel boundaries @ tp={tp}",
               f"got {len(report.boundaries)}")
        if tp == 4:
            print(report.format())


def leg_seeded_violations_caught() -> None:
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.analysis.shard_analysis import (
        analyze_layout,
        compare_layouts,
    )
    from paddle_tpu.serving.shardgroup import GroupLayout

    params = {
        "layer_0/self_attn/q/w": (512, 512),
        "layer_0/self_attn/q/b": (512,),
        "emb/embedding/word_emb": (97, 512),
    }
    axes = {"tp": 4}

    bad = GroupLayout(rules=(
        ("*/self_attn/qq/w", P(None, "tp")),   # dead rule (typo)
        ("*/self_attn/q/b", P(None, "tp")),    # rank mismatch on 1-d bias
        ("emb/*", P("tp", None)),              # 97 % 4: silent degrade
    ), optional=())
    got = sorted(d.code for d in analyze_layout(params, bad, axes))
    want = ["shard-dead-rule", "shard-rank-mismatch", "shard-silent-degrade"]
    _check(got == want, "seeded bad layout yields exact codes",
           f"want {want}, got {got}")

    serving = GroupLayout(rules=(("*/q/w", P(None, "tp")),), optional=())
    training = GroupLayout(rules=(("*/q/w", P("tp", None)),), optional=())
    conf = compare_layouts({"serving": serving, "training": training},
                           params, axes)
    _check([d.code for d in conf] == ["shard-conflict"],
           "cross-layout conflict detected",
           f"got {[d.code for d in conf]}")

    kv_bad = GroupLayout(rules=(), optional=(),
                         kv_rule=P(None, "tp", None, None))
    kv = analyze_layout(
        {}, kv_bad, {"tp": 2}, kv_page_shape=(2, 14, 4, 32),
        kv_geometry={"num_pages": 14, "page_size": 4})
    _check([d.code for d in kv] == ["shard-kv-geometry"],
           "sharded KV page ids rejected",
           f"got {[d.code for d in kv]}")


def leg_tree_retrace_clean() -> None:
    from paddle_tpu.analysis.retrace_lint import lint_file, lint_retrace

    diags = [d for d in lint_retrace() if d.severity == "error"]
    _check(diags == [], "whole tree retrace-lints clean",
           "; ".join(str(d) for d in diags))

    fixture = (
        "import jax\n"
        "pending = []\n"
        "def step(params, tokens):\n"
        "    return params, tokens[: len(pending)]\n"
        "def serve(params, reqs):\n"
        "    for r in reqs:\n"
        "        f = jax.jit(step)\n"
        "        params, _ = f(params, r)\n"
    )
    codes = sorted(d.code for d in lint_file("fixture.py", fixture))
    want = ["retrace-dynamic-len", "retrace-jit-in-loop"]
    _check(codes == want, "dynamic-closure retrace bug caught in fixture",
           f"want {want}, got {codes}")


def main(argv=None) -> int:
    leg_shipped_layout_clean()
    leg_seeded_violations_caught()
    leg_tree_retrace_clean()
    if _FAILURES:
        print(f"[analysis_gate] FAILED: {len(_FAILURES)} check(s): "
              + ", ".join(_FAILURES))
        return 1
    print("[analysis_gate] all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
