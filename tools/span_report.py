"""One benchmark run with the program's spans read out of the profiler trace.

    python tools/span_report.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--tracing on|off] [--inside <span>] [--out chiprun_out/span_report]

Runs ``benchmarks/run.py``'s harness in this process. With ``--trace 1`` it
reads the ``.xplane.pb`` the harness captured, before the harness deletes it:

* the device's idle gaps shared out to the program's spans, through
  ``trace_reduce.load_xplane(path, host_prefix=...)`` and
  ``trace_reduce.idle_gaps`` as they are. Nested spans would be counted
  twice, so each moment goes to the innermost span open on it: a span that
  holds children keeps only the time no child covers (``<name> (self)``).
* per span name: count, total and own seconds, and the part of the traced
  window on the spans' thread that no span covers at all.
* where the device names landed: the lines of the device plane, and the top
  operations with their raw event name and stats (``jax.named_scope`` and the
  ``pallas_call`` names are looked for there, by hand).
* with ``--inside <span>``: the heaviest host events inside that span, the
  runtime's own among them (what an enqueue is made of).

``--tracing off`` calls ``tracing.disable_tracing()`` first: the untraced
run then measures the program without its spans (the cost of tracing is the
difference; ``PERF.md`` has the runs). No option of the program is involved.

After any run it prints ``loop:`` (:func:`loop_turns`): the serving loop's
turn from the program's spans, phase by phase, with the thread's CPU time, its
time off the CPU and the bookings' own share. ``--trace 0`` reads the true
sizes; ``--trace 1`` reads what the ledger's traced runs read (the profiler's
Python tracer inflates the host's part several times).

    python tools/span_report.py --span-cost

runs no model and needs no chip: it times a scoped span with tracing on and
off and a labelled counter, gauge and histogram write (:func:`span_cost`).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PROGRAM_PREFIXES = ("trainer.", "serving.", "executor.")
# how a ``jax.named_scope`` reads in an HLO op's ``op_name``, forward or under grad
SCOPE_MARKS = [m % s for s in ("embed", "attention", "ffn", "head", "loss", "optimizer_update",
                               "page_write", "sampling") for m in ("/%s/", "(%s)/")]


def innermost(events):
    """Disjoint (name, start_ns, duration_ns) pieces: every moment of the
    nested ``events`` under the innermost one open on it. The part of a span
    that its children leave is named ``<name> (self)``; a span without
    children keeps its name."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [name, end, cursor, had_child]

    def close(top):
        name, end, cursor, had_child = top
        if end > cursor:
            out.append((name + " (self)" if had_child else name, cursor, end - cursor))

    for name, start, dur in order:
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            top = stack[-1]
            if start > top[2]:
                out.append((top[0] + " (self)", top[2], start - top[2]))
            top[2], top[3] = max(top[2], start + dur), True
        stack.append([name, start + dur, start, False])
    while stack:
        close(stack.pop())
    return out


def span_table(events, pieces):
    total, own, count = {}, {}, {}
    for name, _, dur in events:
        total[name] = total.get(name, 0.0) + dur / 1e9
        count[name] = count.get(name, 0) + 1
    for name, _, dur in pieces:
        base = name[:-len(" (self)")] if name.endswith(" (self)") else name
        own[base] = own.get(base, 0.0) + dur / 1e9
    return [{"name": n, "count": count[n], "total_s": total[n], "own_s": own.get(n, 0.0)}
            for n in sorted(total, key=lambda n: -total[n])]


def device_names(path, top=12):
    """What a reader sees on the device planes: line names, and the heaviest
    operations with their raw names and stats."""
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce

    # the op events' names and stats (below) carry no op metadata: say whether
    # the file holds the scope names at all, for whoever parses it further
    with open(path, "rb") as f:
        raw = f.read()
    out = {"scope_marks_in_the_file": {m: raw.count(m.encode()) for m in SCOPE_MARKS}}
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            by_name = {}
            for ev in line.events:
                rec = by_name.setdefault(ev.name, {"count": 0, "seconds": 0.0, "stats": None})
                rec["count"] += 1
                rec["seconds"] += ev.duration_ns / 1e9
                if rec["stats"] is None:
                    rec["stats"] = {str(k): str(v)[:300] for k, v in ev.stats}
            heavy = sorted(by_name.items(), key=lambda kv: -kv[1]["seconds"])[:top]
            # an HLO instruction's metadata (op_name: the named scopes) is at its end
            lines[line.name] = [{"name": n[:300], "tail": n[-400:] if len(n) > 300 else "", **rec}
                                for n, rec in heavy]
        out[plane.name] = lines
    return out


def inside(host, span_name, top=15):
    """The heaviest host events of any writer (the runtime's own among them)
    that lie inside an instance of ``span_name``: what that span's time is
    made of. Threads are not told apart: ``load_xplane`` merges them."""
    spans = [(s, s + d) for n, s, d in host if n == span_name]
    by_name = {}
    for n, s, d in host:
        if n != span_name and any(a <= s and s + d <= b for a, b in spans):
            rec = by_name.setdefault(n, [0, 0.0])
            rec[0] += 1
            rec[1] += d / 1e9
    heavy = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:top]
    return {"instances": len(spans), "events": [[n[:120], c, sec] for n, (c, sec) in heavy]}


def report(path: str, cell: str, out_dir: str, load_xplane, look_inside=()) -> None:
    from benchmarks import trace_reduce

    host = []
    for prefix in PROGRAM_PREFIXES:
        host += load_xplane(path, host_prefix=prefix)["host"]
    trace = load_xplane(path)  # the devices, and the benchmark's own annotations
    pieces = innermost(host)
    devs = trace["devices"]
    first = devs[sorted(devs)[0]] if devs else []
    doc = {
        "cell": cell,
        "window_s": trace_reduce.window_ns(trace) / 1e9,
        "busy_s": trace_reduce.busy_ns(first) / 1e9,
        "idle_gaps_by_program_span": trace_reduce.idle_gaps(first, pieces, top=40),
        "idle_gaps_by_bench_annotation": trace_reduce.idle_gaps(first, trace["host"], top=10),
        "spans": span_table(host, pieces),
        "device_names": device_names(path),
    }
    if look_inside:
        everything = load_xplane(path, host_prefix="")["host"]
        doc["inside"] = {name: inside(everything, name) for name in look_inside}
    if host:
        t0 = min(s for _, s, _ in host)
        t1 = max(s + d for _, s, d in host)
        doc["span_thread_s"] = (t1 - t0) / 1e9
        doc["outside_any_span_s"] = (t1 - t0 - trace_reduce.busy_ns(host)) / 1e9
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell + ".json"), "w") as f:
        json.dump(doc, f, indent=1)
    print("span report:", json.dumps({k: doc[k] for k in doc if k != "device_names"}), flush=True)


def print_set_once() -> None:
    """What the program says once about its own programs: the gauges that
    tell whether a donation engaged, each compile span's attributes (the
    kernels' blocks, the bytes a program aliases), over the paged steps still
    in the span store the share of their tables' pages that were live, and
    the loop's turns (:func:`loop_turns`)."""
    from paddle_tpu import tracing
    from paddle_tpu.core import profiler as prof

    said = {k: v for k, v in prof.gauges().items()
            if k.endswith(("_donated", "_row_major"))}
    print("program gauges:", json.dumps(said), flush=True)
    for span in tracing.spans():
        if span.name == "executor.compile":
            print("executor.compile:", json.dumps(span.attrs, default=str), flush=True)
    steps = [s.attrs for s in tracing.spans()
             if s.name == "serving.decode.model_step" and "attend_live_pages" in s.attrs]
    if steps:
        live, table = (sum(a[f"attend_{w}_pages"] for a in steps) for w in ("live", "table"))
        print("attend:", json.dumps({
            "steps": len(steps), "live_pages": live, "table_pages": table,
            "live_share": live / table, "kernel": steps[-1]["attend_kernel"]}), flush=True)
    loop = loop_turns(tracing.spans())
    if loop:
        print("loop:", json.dumps(loop), flush=True)


# a turn's phases: the name the ``loop:`` line gives each, and how its spans
# are told (a name, or for the device waits a suffix)
LOOP_PHASES = (
    ("publish", "serving.decode.publish"), ("admit", "serving.decode.admit"),
    ("pack", "serving.decode.model_step.pack"),
    ("dispatch", "serving.decode.model_step.dispatch"),
    ("chunk_enqueue", "serving.decode.prefill"), ("wait", ".wait"),
    ("land", "serving.decode.model_step.land"),
    ("model_step_self", "serving.decode.model_step (self)"),
    ("step_self", "serving.decode.step (self)"),
    ("between_spans", None))  # the loop's own lines from one pass's span to the next


def loop_turns(spans) -> dict:
    """Over the serving loop's turns still in the span store that held a model
    step, each from the end of the turn before to the end of this one (the
    drain after the window is in it: medians and 95th percentiles, not sums),
    in milliseconds:

    * ``turn_ms`` and ``host_ms`` (each ``_p50`` and ``_p95``, as all but the
      phases), the turn and the turn less the ``.wait`` spans inside it: what
      the benchmark's ``loop_iteration_ms`` and ``loop_host_ms`` read in a
      traced window;
    * ``phase_ms``, ``{phase: [median, 95th percentile]}``: every phase of
      the turn, each moment under the innermost
      span open on it (:func:`innermost`), so the phases add up to the turn:
      the last turn's ``publish``, ``admit``, ``pack``, ``dispatch``, the
      chunk's enqueue, the device waits, ``land``, what ``model_step`` and
      ``step`` hold outside their children, and what lies between the passes'
      spans;
    * ``telemetry_ms`` from ``telemetry_seconds`` on the turn's
      ``serving.decode.step`` span, the bookings' stretches: the benchmark's
      ``loop_telemetry_ms``; ``cpu_ms``, ``cpu_share`` and ``offcpu_ms`` from
      ``cpu_seconds`` beside it (all absent for a program from before them):
      the loop thread's CPU time a turn as a mean (the chip's host ticks
      ``time.thread_time()`` in steps of 10 ms, so one turn reads 0 or 10 and
      only the sum over the turns is a measurement), that sum over the sum of
      the host parts, and the median host part times what is left of the
      share (floored at 0: the thread runnable and not running, or parked on
      a lock), which is the benchmark's ``loop_offcpu_ms``;
    * ``dispatch_ms``: ``dispatch`` plus ``chunk_enqueue``, the benchmark's
      ``loop_dispatch_ms``; ``spans``: spans the turn committed."""
    import statistics

    from benchmarks import loop_spans

    rows = []
    for found in loop_spans.turns(spans):
        turn, t0, t1, inside = found.step, found.t0_us, found.step.t1_us, found.inside
        pieces = innermost([(s.name, s.t0_us, s.t1_us - s.t0_us) for s in inside])
        phase = {name: sum(d for n, _, d in pieces
                           if n == told or (told[0] == "." and n.endswith(told))) / 1e3
                 for name, told in LOOP_PHASES if told}
        row = {"turn_ms": (t1 - t0) / 1e3, "phase_ms": phase, "spans": float(len(inside))}
        phase["between_spans"] = row["turn_ms"] - sum(phase.values())
        row["host_ms"] = row["turn_ms"] - phase["wait"]
        row["dispatch_ms"] = phase["dispatch"] + phase["chunk_enqueue"]
        if "cpu_seconds" in turn.attrs:
            row["cpu_ms"] = 1e3 * turn.attrs["cpu_seconds"]
            row["telemetry_ms"] = 1e3 * turn.attrs["telemetry_seconds"]
        rows.append(row)
    if not rows:
        return {}
    p95 = lambda v: sorted(v)[int(0.95 * (len(v) - 1))]
    out = {"turns": len(rows)}
    for key in ("turn_ms", "host_ms", "dispatch_ms", "telemetry_ms", "spans"):
        values = [r[key] for r in rows if key in r]  # an engine's first turn has no account
        if values:
            out[key + "_p50"], out[key + "_p95"] = statistics.median(values), p95(values)
    timed = [r for r in rows if "cpu_ms" in r]
    if timed:
        cpu, host = (sum(r[key] for r in timed) for key in ("cpu_ms", "host_ms"))
        out["cpu_ms"], out["cpu_share"] = cpu / len(timed), cpu / host
        out["offcpu_ms"] = out["host_ms_p50"] * max(0.0, 1.0 - cpu / host)
    out["phase_ms"] = {name: [f([r["phase_ms"][name] for r in rows])
                              for f in (statistics.median, p95)] for name, _ in LOOP_PHASES}
    return out


def span_cost(calls: int = 20000, rounds: int = 5) -> dict:
    """Microseconds a call, the best of ``rounds`` rounds of ``calls`` calls
    each, on this host: a scoped child span with tracing on and with tracing
    off, and a counter, gauge and histogram write with one label as an engine
    hands it (the same dict every call). No model, no device."""
    from paddle_tpu import tracing
    from paddle_tpu.core import profiler as prof

    labels = {"engine": "span_cost"}

    def span():
        with tracing.start_span("tools.span_cost.child"):
            pass

    cases = {"span_on_us": span, "span_off_us": span,
             "counter_us": lambda: prof.inc_counter("tools.span_cost.calls_total", labels=labels),
             "gauge_us": lambda: prof.set_gauge("tools.span_cost.level", 1.0, labels=labels),
             "histogram_us": lambda: prof.observe("tools.span_cost.seconds", 0.01, labels=labels)}
    out = {"calls": calls, "rounds": rounds}
    was_on = tracing.tracing_enabled()
    try:
        for name, call in cases.items():
            (tracing.disable_tracing if name == "span_off_us" else tracing.enable_tracing)()
            best = float("inf")
            for _ in range(rounds):
                tracing.reset_tracing()  # a store that is not full: no eviction is timed
                with tracing.start_span("tools.span_cost"):
                    t0 = time.perf_counter()
                    for _ in range(calls):
                        call()
                    best = min(best, (time.perf_counter() - t0) / calls * 1e6)
            out[name] = best
    finally:
        (tracing.enable_tracing if was_on else tracing.disable_tracing)()
        tracing.reset_tracing()
    return out


def main(argv) -> int:
    if argv == ["--span-cost"]:
        print("span cost:", json.dumps(span_cost()), flush=True)
        return 0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", choices=("0", "1"), default="1")
    ap.add_argument("--tracing", choices=("on", "off"), default="on")
    ap.add_argument("--inside", action="append", default=[],
                    help="a span whose inner host events to list (may repeat)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "span_report"))
    args = ap.parse_args(argv)

    from benchmarks import harness, trace_reduce
    from paddle_tpu import tracing

    if args.tracing == "off":
        tracing.disable_tracing()
    load = trace_reduce.load_xplane

    def load_and_report(path, *a, **kw):
        # the harness reads the trace once, then deletes it: read it here too
        try:
            report(path, args.workload, args.out, load, args.inside)
        except Exception as e:  # the run's own result must still come out
            print(f"span report failed: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return load(path, *a, **kw)

    trace_reduce.load_xplane = load_and_report
    try:
        return harness.main(["--workload", args.workload, "--seed", args.seed,
                             "--seconds", args.seconds, "--trace", args.trace], T_START)
    finally:
        trace_reduce.load_xplane = load
        print_set_once()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
