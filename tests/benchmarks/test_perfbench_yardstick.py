"""Self-tests of the benchmark's yardstick: traffic, window arithmetic, FLOPs
functions, the trace reduction and the loader. CPU only; nothing here is a
device number."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import check, flops, harness, stats, traffic, trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _mix(name):
    return harness.load_json(ROOT, "benchmarks", "traffic", name + ".json")


# -- traffic ---------------------------------------------------------------

def test_closed_loop_requests_are_a_function_of_the_seed():
    mix = _mix("serve_docs16")
    a = traffic.closed_loop_requests(mix, 32000, 2**31 + 5)
    b = traffic.closed_loop_requests(mix, 32000, 2**31 + 5)
    c = traffic.closed_loop_requests(mix, 32000, 2**31 + 6)
    same = lambda x, y: all(np.array_equal(p, q) and m == n for cx, cy in zip(x, y)
                            for (p, m), (q, n) in zip(cx, cy))
    assert same(a, b)
    assert not same(a, c)


def test_every_round_of_every_seed_holds_the_same_sizes_in_another_order():
    mix = _mix("serve_long")
    mix.pop("deal_seed")  # the generator's default: the seed deals (next test: this mix is dealt once)
    a = traffic.closed_loop_requests(mix, 32000, 1)
    b = traffic.closed_loop_requests(mix, 32000, 99)
    assert len(a) == mix["clients"] == mix["engine"]["max_slots"] == 48
    assert all(len(c) == mix["rounds"] for c in a)
    rounds = lambda reqs, i: [[(len(c[r][0]), c[r][1])[i] for c in reqs] for r in range(mix["rounds"])]
    for i, want in ((0, traffic.lognormal_quantiles(48, 1020, 0.6, 128, 1792)),
                    (1, traffic.lognormal_quantiles(48, 129, 0.6, 16, 256))):
        assert all(sorted(r) == sorted(want) for reqs in (a, b) for r in rounds(reqs, i))
        assert rounds(a, i) != rounds(b, i) and rounds(a, i)[0] != rounds(a, i)[1]
    # the numbers the traffic file, the cell's `why` and PERF.md section 4 give
    assert sum(len(c[0][0]) for c in a) == 52055 and sum(c[0][1] for c in a) == 6795
    chunk = mix["engine"]["prefill_chunk"]
    assert sum(-(-len(c[0][0]) // chunk) for c in a) == 126
    assert all(len(q) + n <= mix["engine"]["max_context"] for c in a for q, n in c)
    assert max(len(q) for c in a for q, _ in c) + max(n for c in a for _, n in c) == 2048
    assert set(mix["assumed"]) >= {"prompt_len.sigma", "output_len.sigma"}


def test_a_mix_with_a_deal_seed_is_dealt_the_same_for_every_seed():
    """``lm_big.serve_long``: which lengths meet in the slots is part of the
    work there, so the seed fills in the tokens and deals nothing."""
    mix = _mix("serve_long")
    assert mix["deal_seed"] == 50
    a = traffic.closed_loop_requests(mix, 32000, 2**31 + 5)
    b = traffic.closed_loop_requests(mix, 32000, 2**31 + 6)
    sizes = lambda reqs: [[(len(q), n) for q, n in c] for c in reqs]
    assert sizes(a) == sizes(b)
    assert not any(np.array_equal(p, q) for ca, cb in zip(a, b) for (p, _), (q, _) in zip(ca, cb))
    again = traffic.closed_loop_requests(mix, 32000, 2**31 + 5)
    assert all(np.array_equal(p, q) for ca, cb in zip(a, again) for (p, _), (q, _) in zip(ca, cb))
    other = sizes(traffic.closed_loop_requests(dict(mix, deal_seed=51), 32000, 2**31 + 5))
    assert other != sizes(a)  # another dealing of the same lengths
    assert sorted(len(q) for c in a for q, _ in c) == sorted(n for c in other for n, _ in c)
    # the mixes that name no deal_seed are dealt by the seed, draw for draw as before PR 41
    docs = _mix("serve_docs16")
    assert "deal_seed" not in docs
    rng = traffic.rng_of(7, 3)
    p_len = traffic.lognormal_quantiles(16, **{k: docs["prompt_len"][k] for k in ("median", "sigma", "lo", "hi")})
    first = traffic.closed_loop_requests(docs, 1000, 7)
    assert [len(c[0][0]) for c in first] == list(rng.permutation(p_len))


def test_lognormal_quantiles_are_the_mid_quantiles_clipped():
    q = traffic.lognormal_quantiles(16, 96, 0.7, 32, 1024)
    assert list(q[[0, 7, 8, 15]]) == [32, 91, 101, 354] and (np.diff(q) > 0).all()
    q = traffic.lognormal_quantiles(48, 1020, 0.6, 128, 1792)
    assert list(q[[0, 23, 24, 39, 40, 47]]) == [255, 1004, 1036, 1778, 1792, 1792]
    assert list(traffic.lognormal_quantiles(3, 10, 0.0, 1, 100)) == [10, 10, 10]


@pytest.mark.parametrize("kind, family", [("train_2k", "decoder_lm"), ("train_wmt", "encdec_nmt")])
def test_training_pools_hold_the_same_work_for_every_seed(kind, family):
    import importlib

    fam = importlib.import_module(f"benchmarks.families.{family}")
    mix = dict(_mix(kind), pool=2)
    config = {"model": {"vocab": 32000, "src_vocab": 37000, "trg_vocab": 37000}}
    a, b = fam.training_pool(mix, config, 7), fam.training_pool(mix, config, 8)
    again = fam.training_pool(mix, config, 7)
    assert all(np.array_equal(x, y) for p, q in zip(a, again) for x, y in zip(p, q))
    assert not np.array_equal(a[0][0], b[0][0])
    counts = {fam.real_target_tokens(batch) for batch in a + b}
    assert counts == ({8192} if kind == "train_2k" else {3072})
    assert not np.array_equal(a[0][0], a[1][0])  # the pool's batches differ


def test_nmt_padding_is_a_suffix_and_labels_follow_targets():
    from benchmarks.families import encdec_nmt

    config = {"model": {"src_vocab": 37000, "trg_vocab": 37000}}
    src, src_pad, trg, trg_pad, labels, label_pad = encdec_nmt.training_pool(
        dict(_mix("train_wmt"), pool=1), config, 3)[0]
    assert (np.diff(src_pad.astype(int), axis=1) >= 0).all()
    assert (src[src_pad] == 0).all() and (src[~src_pad] > 0).all()
    assert np.array_equal(trg_pad, label_pad)
    assert np.array_equal(trg[:, 1:][~trg_pad[:, 1:]], labels[:, :-1][~trg_pad[:, 1:]])


# -- window arithmetic -----------------------------------------------------

def test_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_token_landing_outside_the_window_is_not_counted():
    landings = [(0.5, 1), (1.0, 1), (1.5, 4), (2.5, 1)]
    assert stats.tokens_in_window(landings, 1.0, 2.0) == 5
    gaps = stats.gaps_in_window(landings, 1.0, 2.0)
    # one gap of 0.5 (0.5 -> 1.0), then four tokens sharing the next 0.5 s
    assert gaps == pytest.approx([0.5] + [0.125] * 4)
    assert stats.gaps_in_window([(1.2, 1)], 1.0, 2.0) == []  # a first token has no gap


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    assert stats.spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


def test_a_failed_comparison_or_a_nan_is_not_ok():
    assert check.compared("x", 0.5, 1.0)["ok"]
    assert not check.compared("x", 1.5, 1.0)["ok"]
    assert not check.compared("x", float("nan"), 1.0)["ok"]
    gap, leaf = check.worst_leaf_gap({"a": 1.0, "b": 0.0, "c": 2.2}, {"a": 1.0, "b": 1e-9, "c": 2.0})
    assert leaf == "c" and gap == pytest.approx(0.1)  # b is held against the median leaf


# -- FLOPs and bytes, hand-worked ------------------------------------------

def test_lm_big_step_flops_by_hand():
    cfg = harness.load_json(ROOT, "benchmarks", "configs", "lm_big.json")["model"]
    # per token, forward: 12 layers x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 32000
    # = 183,762,944 multiply-adds; attention 12 x 4 x 2048 x 1024 = 100,663,296
    # operations over the full square, half of it under the causal mask
    matmul = 2 * (12 * (4 * 1024**2 + 2 * 1024 * 4096) + 1024 * 32000)
    assert flops.decoder_lm_fwd_flops_per_token(cfg, 2048, False) == matmul + 100_663_296
    full = flops.decoder_lm_train_flops(cfg, 4, 2048, causal_half=False)
    assert full == pytest.approx(1.15e13, rel=0.01)  # "about 1.2e13" a step
    assert flops.decoder_lm_train_flops(cfg, 4, 2048) == pytest.approx(1.027e13, rel=0.01)


def test_flash_kernel_flops_and_bytes_by_hand():
    # batch 4, 16 heads, T 2048, head size 64, causal, bf16
    assert flops.flash_fwd_flops(4, 16, 2048, 64) == 4 * 4 * 16 * 2048 * 2048 * 64 / 2
    assert flops.flash_bwd_flops(4, 16, 2048, 64) == 2.5 * flops.flash_fwd_flops(4, 16, 2048, 64)
    tensor = 4 * 16 * 2048 * 64 * 2
    assert flops.flash_fwd_bytes(4, 16, 2048, 64) == 4 * tensor + 4 * 4 * 16 * 2048
    assert flops.flash_bwd_bytes(4, 16, 2048, 64) == 8 * tensor + 8 * 4 * 16 * 2048


def test_nmt_step_flops_by_hand():
    cfg = dict(d_model=4, d_inner=8, n_layers=1, trg_vocab=10)
    # one pair, 2 source and 3 target tokens. encoder 2 x (4x16 + 2x32) = 256 madds;
    # decoder self q,k,v,out 3 x 64 + cross q,out 3 x 32 + cross k,v 2 x 32 + ffn 3 x 64
    # + logits 3 x 40 = 664 madds; attention 4 x d x (2x2 + 3x3/2 + 3x2) = 232 operations
    want = 3 * (2 * (256 + 664) + 4 * 4 * (4 + 4.5 + 6))
    assert flops.encdec_nmt_train_flops(cfg, [2], [3]) == pytest.approx(want)


# -- the trace reduction on the small recorded trace -----------------------

def test_trace_reduction_gives_the_numbers_written_beside_the_recorded_trace():
    trace = json.load(open(os.path.join(DATA, "recorded_trace.json")))
    want = json.load(open(os.path.join(DATA, "recorded_trace.expected.json")))
    events = [tuple(e) for e in trace["devices"]["/device:TPU:0"]]
    trace = {"devices": {"/device:TPU:0": events}, "host": [tuple(e) for e in trace["host"]]}
    assert len(events) == want["events"]
    assert trace_reduce.busy_ns(events) == want["busy_ns"]
    assert trace_reduce.window_ns(trace) == want["window_ns"]
    s = trace_reduce.summarize(trace)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(want["idle_share"])
    kernels = trace_reduce.op_seconds(events, "tpu_custom_call")
    assert sum(kernels.values()) * 1e9 == pytest.approx(want["tpu_custom_call_ns"])
    assert sum(s["ops"].values()) == pytest.approx(s["busy_s"])  # self times add up to busy
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert sum(gaps.values()) * 1e9 == pytest.approx(want["window_ns"] - want["busy_ns"])
    assert gaps["bench.step_end"] * 1e9 == pytest.approx(5162070)  # the handler's whole span
    assert gaps["unattributed"] > gaps["bench.step_end"]  # the Trainer's own host code


def test_nested_events_count_once():
    events = [("while", 0, 100), ("a", 10, 20), ("b", 40, 50), ("c", 200, 10)]
    assert trace_reduce.busy_ns(events) == 110
    assert trace_reduce.op_seconds(events) == pytest.approx(
        {"while": 30e-9, "a": 20e-9, "b": 50e-9, "c": 10e-9})


def plain_idle_gaps(events, host, top=10):
    """``trace_reduce.idle_gaps`` as it stood until PR 41, kept as the plain
    form: every host event is held against every gap."""
    merged = trace_reduce.merge_intervals(events)
    by_name = {}
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        left = s1 - e0
        for name, hs, hd in host:
            cover = min(s1, hs + hd) - max(e0, hs)
            if cover > 0:
                by_name[name] = by_name.get(name, 0.0) + cover / 1e9
                left -= cover
        if left > 0:
            by_name["unattributed"] = by_name.get("unattributed", 0.0) + left / 1e9
    return [[n, s] for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def _random_trace(rng, n_ops, n_host, long_event):
    """Device operations with gaps of 1 ns to 40 us between them (some
    overlapping or nested), and host events of a few names that overlap each
    other, start inside operations and gaps alike and leave many gaps with no
    event over them; ``long_event`` adds one that outlasts every gap."""
    starts = np.cumsum(rng.integers(1, 60_000, n_ops))
    events = [(f"op{i % 7}", int(s), int(rng.integers(1, 50_000))) for i, s in enumerate(starts)]
    span = int(starts[-1]) + 50_000
    host = [(f"bench.h{rng.integers(0, 4)}", int(rng.integers(-10_000, span)),
             int(rng.integers(0, 90_000))) for _ in range(n_host)]
    if long_event:
        host.append(("bench.long", span // 5, span // 2))
    return events, sorted(host, key=lambda e: e[1])


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("long_event", [False, True])
def test_idle_gaps_by_the_sweep_are_the_plain_walks(seed, long_event):
    rng = np.random.default_rng([41, seed])
    events, host = _random_trace(rng, 400, int(rng.integers(0, 120)), long_event)
    got, want = trace_reduce.idle_gaps(events, host, top=10), plain_idle_gaps(events, host, top=10)
    assert got == want  # name for name, and the seconds to the last bit
    if long_event:
        assert dict(map(tuple, got))["bench.long"] > 0
    assert trace_reduce.idle_gaps(events, []) == plain_idle_gaps(events, [])  # all unattributed
    # handed the host events in any order, the same to a nanosecond
    shuffled = [host[i] for i in rng.permutation(len(host))]
    assert dict(map(tuple, trace_reduce.idle_gaps(events, shuffled))) == pytest.approx(
        dict(map(tuple, want)), abs=1e-9)


def test_idle_gaps_of_a_million_gaps_reduce_in_seconds():
    """A traced serve window as PERF.md's PR 36 found it: a million device
    operations a nanosecond or a few apart, three thousand annotations. The
    plain walk is three thousand million comparisons; the limit is generous so
    that a slow worker passes and a walk that came back does not."""
    import time

    n, n_host = 1_000_001, 3_000
    events = [("op", 10 * i, 7 + i % 3) for i in range(n)]  # gaps of 1-3 ns
    host = [(f"bench.h{i % 3}", 3_333 * i, 2_000) for i in range(n_host)]
    host.append(("bench.long", 1_000_000, 5_000_000))
    t0 = time.perf_counter()
    got = dict(map(tuple, trace_reduce.idle_gaps(events, host)))
    took = time.perf_counter() - t0
    assert took < 60.0, took
    gaps_ns = sum(10 - (7 + i % 3) for i in range(n - 1))
    assert sum(got.values()) * 1e9 >= gaps_ns  # overlapping events cover a gap twice
    assert got["unattributed"] > 0 and got["bench.long"] == pytest.approx(
        sum(10 - (7 + i % 3) for i in range(100_000, 600_000)) / 1e9)


def test_short_name_keeps_what_identifies_an_op():
    text = ('%closed_call.75 = (bf16[64,2048,64]{2,1,0}) custom-call(bf16[64,2048,64] %x), '
            'custom_call_target="tpu_custom_call"')
    assert trace_reduce.short_name(text) == "closed_call(tpu_custom_call)"
    assert trace_reduce.short_name("%convolution_add_fusion.9 = f32[8] fusion(...)") == \
        "convolution_add_fusion"


# -- the manifest and the loader -------------------------------------------

def test_manifest_names_resolve_to_files():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        loaded = harness.load_cell(w["name"])
        assert loaded["config"]["name"] == w["config"]
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "drivers",
                                           loaded["mix"]["driver"] + ".py"))
        changed = next(c["reduced"] for c in manifest["configs"] if c["name"] == w["config"])
        assert set(changed) == set(loaded["config"]["reduced"]) | (
            {"dropout"} & set(loaded["config"]["departs"]))
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert hasattr(harness.load_reader(m["name"]), "read")


def test_the_retired_cell_is_an_unknown_workload_and_none_of_its_files_is_left():
    with pytest.raises(harness.BenchError, match="unknown workload 'lm_big.serve_closed16'"):
        harness.load_cell("lm_big.serve_closed16")
    bench = os.path.join(ROOT, "benchmarks")
    left = [os.path.join(d, f) for d, _, files in os.walk(bench) for f in files
            if "serve_closed16" in f]
    assert not left
    assert harness.main(["--workload", "lm_big.serve_closed16", "--seed", "1", "--seconds", "1"],
                        0.0) == harness.EXIT_NO_DEVICE  # said on stderr, no result line


def test_the_command_fails_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "lm_big.train_2k", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert "{" not in proc.stdout and "no TPU" in proc.stderr
