"""Self-tests that drive whole runs of the benchmark at a tiny size on the
CPU, through the same harness, drivers, references and comparison as a run on
the chip, skipping only the harness's look for a chip. They are rehearsals:
no number from them is a device metric."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from benchmarks import check, harness, tiny  # noqa: E402
from benchmarks.drivers import serve_closed, train_pool  # noqa: E402
from benchmarks.references import common as refc  # noqa: E402

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


def drive(root, cell, seed=2**31 + 11, seconds=0.5, trace=False, devices=None):
    loaded = harness.load_cell(cell, root)
    devices = devices or jax.devices()[:1]
    return harness.execute(loaded, devices, seed, seconds, trace, time.perf_counter())


# -- each reference agrees with the program, float32, tiny -----------------

@pytest.mark.parametrize("cell, metric", [
    ("lm_tiny.train_rows", "train_tok_s"),
    ("nmt_tiny.train_pairs", "train_tok_s"),
    ("lm_tiny.serve_closed", "tpot_p95_ms"),
])
def test_reference_agrees_with_the_program_and_the_line_has_the_contracts_keys(root, cell, metric):
    line = drive(root, cell)
    assert line["correct"] is True
    assert set(line) == LINE_KEYS
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert {metric, "setup_s"} <= set(line["metrics"])
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    json.dumps(line)


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(root):
    line = drive(root, "lm_tiny.train_rows", trace=True)
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["metrics"]["compiles_in_window.train"]["value"] == 0
    assert "train_tok_s" not in line["metrics"]
    # readers that find nothing to read (no TPU plane, no peak for a CPU) are left out
    assert "train_mfu" not in line["metrics"] and "flash_roofline" not in line["metrics"]


def test_a_traced_serve_run_reports_the_windows_rate_and_first_tokens_without_a_bound(root):
    line = drive(root, "lm_tiny.serve_closed", trace=True)
    assert line["correct"] is True
    assert {"window_out_tok_s", "decode_step_ms", "decode_occupancy"} <= set(line["metrics"])
    assert line["metrics"]["window_out_tok_s"]["value"] > 0
    assert "tpot_p95_ms" not in line["metrics"]
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bounded = {m["name"] for m in json.load(f)["end_to_end"]}
    assert not bounded & {"window_out_tok_s", "window_ttft_p50_ms", "serve_out_tok_s", "ttft_p50_ms"}


def test_chips_is_data_four_virtual_devices(root):
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    four = os.path.join(os.path.dirname(root), "four")
    shutil.copytree(root, four)
    for w in manifest["workloads"]:
        w["chips"] = 4
    with open(os.path.join(four, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    loaded = harness.load_cell("lm_tiny.train_rows", four)
    line = harness.execute(loaded, jax.devices()[:4], 5, 0.5, False, time.perf_counter())
    assert line["correct"] is True and line["device"]["count"] == 4


# -- the comparison has been shown to fail ---------------------------------

def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    import paddle_tpu as pt

    real = pt.Trainer._run_step

    def frozen(self, batch):
        # the step consumes the state it is handed (PR 32): what it is frozen
        # at is a copy taken before it, as tests/test_trainer_donation.py does
        kept = jax.tree_util.tree_map(jax.numpy.array, self.variables)
        return real(self, batch)._replace(variables=kept)

    monkeypatch.setattr(pt.Trainer, "_run_step", frozen)
    line = drive(root, "lm_tiny.train_rows")
    assert line["correct"] is False


def test_a_part_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    import paddle_tpu as pt

    real = pt.Trainer._run_step

    def half(self, batch):
        return real(self, tuple(np.concatenate([b[:2], b[:2]]) for b in batch))

    monkeypatch.setattr(pt.Trainer, "_run_step", half)
    assert drive(root, "lm_tiny.train_rows")["correct"] is False


def test_a_served_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    from paddle_tpu.serving import decode

    real = decode.DecodeHandle.result

    def altered(self, timeout=None):
        out = real(self, timeout)
        out.tokens = np.asarray(out.tokens).copy()
        out.tokens[-1] = (out.tokens[-1] + 1) % 97
        return out

    monkeypatch.setattr(decode.DecodeHandle, "result", altered)
    assert drive(root, "lm_tiny.serve_closed")["correct"] is False


# -- the control: the reference one precision lower fails ------------------

def _walks(root, cell, seed):
    loaded = harness.load_cell(cell, root)
    run = harness.Run(loaded, jax.devices()[:1], seed, 0.0, False, time.perf_counter())
    family, pool, _, shapes = train_pool.prepare(run)
    ref = train_pool.reference_walk(run, family, pool, shapes, keep_first_grad=True)
    first = ref.pop("first_grad")
    low, diff = train_pool.control_walk(run, family, pool, shapes, first)
    same = {k: 0.0 for k in diff}
    return run, dict(ref, grad_diff_norms=same), low, dict(ref, grad_diff_norms=diff)


@pytest.mark.parametrize("cell", ["lm_tiny.train_rows", "nmt_tiny.train_pairs"])
def test_the_fp8_control_fails_a_training_cell(root, cell):
    run, ref, low, ref_vs_low = _walks(root, cell, 2**31 + 3)
    failed = [c["name"] for c in check.train_checks(low, ref_vs_low, run.limits, [1.0])
              if not c["ok"]]
    assert "grad_diff_gap" in failed
    assert all(c["ok"] for c in check.train_checks(ref, ref, run.limits, [1.0]))


def test_the_fp8_control_puts_other_tokens_first(root):
    loaded = harness.load_cell("lm_tiny.serve_closed", root)
    run = harness.Run(loaded, jax.devices()[:1], 17, 0.0, False, time.perf_counter())
    family, _, shapes = serve_closed.prepare(run)
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(1, 97, 40, dtype=np.int32),
               "tokens": rng.integers(1, 97, 24, dtype=np.int32)} for _ in range(4)]
    gaps = serve_closed.served_gaps(run, family, shapes, sample, ("f32", "fp8"))
    assert max(gaps["fp8"]) > run.limits["served_gap_sigmas"]
    assert min(gaps["f32"]) >= 0 and len(gaps["f32"]) == 96


def test_fp8_matmul_rounds_and_differentiates():
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    exact, low = refc.mm_f32(a, b), refc.mm_fp8(a, b)
    err = float(np.abs(exact - low).max() / np.abs(exact).max())
    assert 1e-3 < err < 0.2
    ga, gb = jax.grad(lambda x, y: refc.mm_fp8(x, y).sum(), (0, 1))(a, b)
    assert ga.shape == a.shape and gb.shape == b.shape


# -- driven by data: everything is added as files --------------------------

def test_a_configuration_a_cell_a_driver_and_a_metric_are_added_as_files_only(tmp_path):
    copy = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    bench = copy / "benchmarks"
    (bench / "configs" / "new_model.json").write_text(json.dumps({"name": "new_model", "x": 3}))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps({"driver": "new_driver", "n": 4}))
    (bench / "workloads" / "new_model.new_mix.json").write_text(
        json.dumps({"name": "new_model.new_mix", "limits": {"answer": 0.0}}))
    (bench / "drivers" / "new_driver.py").write_text(
        "from benchmarks import check\n"
        "def run(ctx):\n"
        "    ctx.open_window(); ctx.close_window()\n"
        "    n = ctx.mix['n'] * ctx.config['x']\n"
        "    return {'end_to_end': {'new_rate': float(n)}, 'counters': {'n': n},\n"
        "            'checks': [check.compared('answer', 0.0, ctx.limits['answer'])],\n"
        "            'attempted': n, 'failed': 0, 'memory_peak_bytes': 0}\n")
    (bench / "layer_metrics" / "new_count.py").write_text(
        "def read(view):\n    return view['counters']['n']\n")
    manifest["configs"].append({"name": "new_model", "source": "a test", "reduced": [],
                                "file": "benchmarks/configs/new_model.json", "why": "a test"})
    manifest["workloads"].append({"name": "new_model.new_mix", "config": "new_model",
                                  "traffic": "new_mix", "chips": 1, "why": "a test"})
    manifest["end_to_end"].append({"name": "new_rate", "unit": "1/s", "better": "higher",
                                   "bound": 0.01, "source": "host_clock",
                                   "workloads": ["new_model.new_mix"]})
    manifest["per_layer"].append({"name": "new_count", "unit": "count", "better": "higher",
                                  "source": "program_counter", "layer": "a test",
                                  "moves": "new_rate", "workloads": ["new_model.new_mix"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    script = (
        "import sys, json, time; sys.path.insert(0, %r)\n"
        "import jax\n"
        "from benchmarks import harness\n"
        "assert harness.ROOT == %r\n"
        "loaded = harness.load_cell('new_model.new_mix')\n"
        "for trace in (False, True):\n"
        "    line = harness.execute(loaded, jax.devices()[:1], 1, 1.0, trace, time.perf_counter())\n"
        "    print(json.dumps(line['metrics']))\n" % (str(copy), str(copy)))
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                          env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
                          cwd=str(copy), timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    plain, traced = (json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{"))
    assert plain["new_rate"]["value"] == 12.0 and "setup_s" in plain
    assert traced == {"new_count": {"value": 12.0, "unit": "count"}}
