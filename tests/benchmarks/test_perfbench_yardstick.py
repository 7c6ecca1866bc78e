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
    mix = _mix("serve_closed16")
    a = traffic.closed_loop_requests(mix, 32000, 2**31 + 5)
    b = traffic.closed_loop_requests(mix, 32000, 2**31 + 5)
    c = traffic.closed_loop_requests(mix, 32000, 2**31 + 6)
    same = lambda x, y: all(np.array_equal(p, q) and m == n for cx, cy in zip(x, y)
                            for (p, m), (q, n) in zip(cx, cy))
    assert same(a, b)
    assert not same(a, c)


def test_every_round_of_every_seed_holds_the_same_sizes_in_another_order():
    mix = _mix("serve_closed16")
    a = traffic.closed_loop_requests(mix, 32000, 1)
    b = traffic.closed_loop_requests(mix, 32000, 99)
    assert len(a) == mix["clients"] and all(len(c) == mix["rounds"] for c in a)
    rounds = lambda reqs, i: [[(len(c[r][0]), c[r][1])[i] for c in reqs] for r in range(mix["rounds"])]
    for i, want in ((0, traffic.lognormal_quantiles(16, 96, 0.7, 32, 1024)),
                    (1, traffic.lognormal_quantiles(16, 48, 0.5, 16, 256))):
        assert all(sorted(r) == sorted(want) for reqs in (a, b) for r in rounds(reqs, i))
        assert rounds(a, i) != rounds(b, i) and rounds(a, i)[0] != rounds(a, i)[1]
    assert sum(len(c[0][0]) for c in a) == 1922 and sum(c[0][1] for c in a) == 863
    assert all(len(q) + n <= mix["engine"]["max_context"] for c in a for q, n in c)


def test_lognormal_quantiles_are_the_mid_quantiles_clipped():
    q = traffic.lognormal_quantiles(16, 96, 0.7, 32, 1024)
    assert list(q[[0, 7, 8, 15]]) == [32, 91, 101, 354] and (np.diff(q) > 0).all()
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


def test_the_command_fails_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "lm_big.train_2k", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == harness.EXIT_NO_DEVICE
    assert "{" not in proc.stdout and "no TPU" in proc.stderr
