"""Driver ``serve_closed``: a closed loop of as many clients as the mix says
against one ``serving.DecodeEngine``. Each client sends its next request when
the last one returned. The window opens when every client's first request has
its first token, and lasts ``--seconds``. After it the engine is freed and the
plain reference runs once over a seeded sample of the finished requests.

The one end-to-end metric is the gap between a request's output tokens: it is
one iteration of the engine and does not depend on which requests the window
happens to hold. The window's tokens per second and time to first token do (a
window holds about one request's life today), so they are counters for two
per-layer readers and carry no bound."""

from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import check, stats, traffic, weights
from benchmarks.families import _common
from benchmarks.references import common as refc

POLL_S = 0.002


class _Client:
    def __init__(self, requests):
        self.requests = requests
        self.sent = 0
        self.handle = None
        self.rid = None
        self.due = None
        self.current = None


def _tap(metrics, log: dict) -> None:
    """Keep the seconds the engine hands ``record_step`` and
    ``record_prefill_chunk`` (it sums them into histograms only)."""
    step, chunk = metrics.record_step, metrics.record_prefill_chunk

    def record_step(active, max_slots, seconds, new_tokens):
        log["steps"].append((time.perf_counter(), seconds, active / max(max_slots, 1)))
        return step(active, max_slots, seconds, new_tokens)

    def record_prefill_chunk(seconds):
        log["chunks"].append((time.perf_counter(), seconds))
        return chunk(seconds)

    metrics.record_step, metrics.record_prefill_chunk = record_step, record_prefill_chunk


def serve(ctx, family, per_client, shapes):
    """Everything that touches the program. Returns what was observed."""
    from paddle_tpu.tracing import waterfall

    mix = ctx.mix
    w = weights.make_weights(shapes, ctx.seed)
    engine = family.make_engine(ctx.config, w, mix["engine"])
    del w
    log = {"steps": [], "chunks": []}
    _tap(engine.metrics, log)
    clients = [_Client(r) for r in per_client]
    finished, sent, failed = [], [], 0
    first_rids = {}
    t_open = t_close = None

    def submit(c: _Client) -> None:
        prompt, budget = c.requests[c.sent % len(c.requests)]
        c.sent += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            c.due = time.perf_counter()
            c.handle = engine.submit(prompt, budget)
            c.rid = waterfall.rids()[-1]  # this thread alone submits
        c.current = {"rid": c.rid, "due": c.due, "prompt": prompt, "budget": budget}
        sent.append(c.current)

    try:
        for i, c in enumerate(clients):
            submit(c)
            first_rids[i] = c.rid
        while True:
            now = time.perf_counter()
            if t_open is None:
                docs = [waterfall.doc(r) for r in first_rids.values()]
                if all(d is not None and d["t_first_token_pc"] is not None for d in docs):
                    ctx.open_window()
                    t_open = time.perf_counter()
            elif now - t_open >= ctx.seconds:
                t_close = now
                break
            if t_open is None and now - ctx.t_start > mix["request_timeout_s"]:
                raise TimeoutError("the first requests did not reach a first token")
            with jax.profiler.TraceAnnotation("bench.client_poll"):
                for c in clients:
                    if not c.handle.done():
                        if now - c.due > mix["request_timeout_s"]:
                            c.handle.cancel()
                        continue
                    try:
                        out = c.handle.result(timeout=0)
                        finished.append(dict(c.current, tokens=np.asarray(out.tokens),
                                             reason=out.finish_reason))
                    except Exception as e:  # a failed request is counted, not fatal
                        print(f"request failed: {type(e).__name__}: {e}", flush=True)
                        failed += 1
                    submit(c)
            time.sleep(POLL_S)
        ctx.close_window()
        for c in clients:
            c.handle.cancel()
        for c in clients:
            try:
                c.handle.result(timeout=mix["request_timeout_s"])
            except Exception as e:
                print(f"cancelled request ended with {type(e).__name__}: {e}", flush=True)
        docs = {s["rid"]: waterfall.doc(s["rid"]) for s in sent}
        snap = engine.metrics.snapshot()
        step_cache = engine.decode_step_cache_size()
        peak = ctx.memory_peak()
    finally:
        engine.close(timeout=mix["request_timeout_s"])
    leaks = 0
    try:
        engine.kv.assert_no_leaks()
    except Exception as e:
        print(f"page leak: {e}", flush=True)
        leaks = 1
    del engine
    gc.collect()
    return dict(finished=finished, sent=sent, failed=failed, docs=docs, snap=snap, log=log,
                t_open=t_open, t_close=t_close, step_cache=step_cache, leaks=leaks, peak=peak)


def sample_requests(finished, n: int, seed: int):
    """A seeded sample of the finished requests with the longest in it."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rest = order[1:]
    pick = np.random.default_rng([int(seed), 9]).permutation(len(rest))[:max(n - 1, 0)]
    return [finished[order[0]]] + [finished[rest[i]] for i in pick]


def reference_rows(logits_fn, params, request, pad_to: int):
    """Reference logits at the positions that produced the served tokens:
    one teacher-forced pass over prompt + served tokens."""
    prompt, toks = request["prompt"], request["tokens"]
    n = len(prompt) + len(toks)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :n] = np.concatenate([prompt, toks])
    at = len(prompt) - 1 + np.arange(len(toks))
    return logits_fn(params, jnp.asarray(ids))[0, at]


def served_gaps(ctx, family, shapes, sample, mm_names=("f32",)):
    """{mm: [gap in sigmas per served token]}: for "f32" the served token's
    gap below the reference's best; for a control precision the gap of the
    token that precision puts first."""
    if not sample:
        return {m: [] for m in mm_names}
    params = weights.make_weights(weights.as_float32(shapes), ctx.seed)
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    pad_to = -(-longest // 128) * 128
    fns = {m: jax.jit(lambda p, ids, m=m: family.reference_logits(ctx.config, refc.MATMULS[m])(p, ids))
           for m in mm_names}
    out = {m: [] for m in mm_names}
    for r in sample:
        rows = np.asarray(reference_rows(fns["f32"], params, r, pad_to))
        out["f32"].extend(check.gap_sigmas(rows, r["tokens"]).tolist())
        for m in mm_names:
            if m != "f32":
                low = np.asarray(reference_rows(fns[m], params, r, pad_to))
                out[m].extend(check.gap_sigmas(rows, low.argmax(-1)).tolist())
    return out


def prepare(ctx):
    """(family module, each client's requests, parameter shapes) of the cell."""
    family = importlib.import_module(f"benchmarks.families.{ctx.config['family']}")
    per_client = traffic.closed_loop_requests(ctx.mix, ctx.config["model"]["vocab"], ctx.seed)
    model, _ = family.build_model(ctx.config, 8, "serve")
    shapes = _common.param_shapes(model, (np.zeros((1, 8), np.int32),) * 2)
    return family, per_client, shapes


def run(ctx) -> dict:
    mix = ctx.mix
    family, per_client, shapes = prepare(ctx)
    seen = serve(ctx, family, per_client, shapes)
    t0, t1 = seen["t_open"], seen["t_close"]
    window = t1 - t0
    landings = {rid: [(e["t_pc"], e["n"]) for e in d["events"] if e["n"] > 0]
                for rid, d in seen["docs"].items() if d is not None}
    out_tokens = sum(stats.tokens_in_window(l, t0, t1) for l in landings.values())
    gaps = [g for l in landings.values() for g in stats.gaps_in_window(l, t0, t1)]
    ttft = []
    for s in seen["sent"]:
        first = (seen["docs"].get(s["rid"]) or {}).get("t_first_token_pc")
        if first is not None and t0 <= first <= t1:
            ttft.append(first - s["due"])  # from when the client sent it
    t_ref = time.perf_counter()
    sample = sample_requests(seen["finished"], mix["check_requests"], ctx.seed)
    gap = served_gaps(ctx, family, shapes, sample)["f32"]
    n_checked = len(gap)
    short = [f for f in seen["finished"]
             if f["reason"] != "length" or len(f["tokens"]) != f["budget"]]
    snap = seen["snap"]
    faults = snap["step_faults_total"] + snap["recovered_total"] + snap["errors_total"]
    checks = [
        check.compared("served_gap_sigmas", max(gap) if gap else float("inf"),
                       ctx.limits["served_gap_sigmas"],
                       f"{n_checked} served tokens of {len(sample)} requests"),
        check.compared("requests_short_of_budget", float(len(short)), 0.0,
                       f"of {len(seen['finished'])} finished"),
        check.compared("engine_faults", float(faults), 0.0, "step faults + recoveries + errors"),
        check.compared("leaked_pages", float(seen["leaks"]), 0.0),
    ]
    print(f"reference check took {time.perf_counter() - t_ref:.1f} s; {len(seen['finished'])} "
          f"requests finished, {out_tokens} tokens and {len(gaps)} gaps in the window, "
          f"{len(ttft)} first tokens (median {stats.median(ttft) if ttft else float('nan'):.3f} s "
          f"from sending)", flush=True)
    steps = [(s, o) for t, s, o in seen["log"]["steps"] if t0 <= t <= t1]
    chunks = [s for t, s in seen["log"]["chunks"] if t0 <= t <= t1]
    return {
        "end_to_end": {"tpot_p95_ms": 1e3 * stats.percentile(gaps, 95)},
        "counters": {"window_s": window, "step_seconds": [s for s, _ in steps],
                     "step_occupancy": [o for _, o in steps], "chunk_seconds": chunks,
                     "decode_step_cache_size": seen["step_cache"],
                     "requests_finished": len(seen["finished"]), "gaps": len(gaps),
                     "out_tokens": out_tokens, "ttft_seconds": ttft},
        "checks": checks, "attempted": len(seen["sent"]), "failed": seen["failed"] + len(short),
        "memory_peak_bytes": seen["peak"],
    }
