"""The harness: finds everything by the names in ``BENCHMARK.json``, refuses
to run without the chips a cell asks for, keeps the compile cache inside the
checkout, opens and closes the measured window for the driver, captures the
trace, and prints the result line."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time
import traceback
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
EXIT_NO_DEVICE = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(Exception):
    """The benchmark cannot run as asked; no result line is printed."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything a run needs, found by name: the manifest's entry, the
    configuration, the traffic mix and the cell's own file of limits."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    here = os.path.join(root, "benchmarks")
    return {
        "manifest": manifest, "cell": cell,
        "config": load_json(root, configs[cell["config"]]["file"]),
        "mix": load_json(here, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(here, "workloads", name + ".json")["limits"],
    }


def require_devices(chips: int):
    """The first ``chips`` TPU devices, or BenchError: there is no fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax.devices() is {devs}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, jax sees {len(devs)}")
    peaks = load_json(HERE, "peaks.json")
    if devs[0].device_kind not in peaks:
        raise BenchError(f"no peaks on record for device kind {devs[0].device_kind!r}")
    return devs[:chips]


class Run:
    """What a driver is handed: the cell's data, the devices, and the window."""

    def __init__(self, loaded: dict, devices, seed: int, seconds: float, trace: bool,
                 t_start: float, scratch: str = CACHE_DIR):
        self.cell, self.config = loaded["cell"], loaded["config"]
        self.mix, self.limits = loaded["mix"], loaded["limits"]
        self.devices, self.seed, self.trace = devices, int(seed), trace
        # a traced run measures only the few seconds it traces
        self.seconds = float(seconds)
        if trace:
            self.seconds = min(self.seconds, self.mix.get("trace_seconds", self.seconds))
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        # a directory a process: two runs of one cell at once (the self-tests'
        # workers) would each delete the other's trace
        self.trace_dir = os.path.join(scratch, "trace", f"{self.cell['name']}.{os.getpid()}")
        self.trace_summary: Optional[dict] = None
        self._compiles = 0
        self.compiles_in_window: Optional[int] = None
        self._compiles_at_open = 0

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self._compiles += 1

    def listen_for_compiles(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_compile)

    def open_window(self) -> None:
        """Set-up ends here. With ``--trace 1`` the profiler starts, without
        its Python tracer: that hooks every Python call of every thread, which
        made the host's part of a serving turn four times its untraced size
        and left the device idle while it waited (PERF.md, PR 37 and 41). The
        annotations (``bench.*``, the program's spans) are the runtime's own
        trace events and stay on the host plane."""
        self.setup_s = time.perf_counter() - self.t_start
        self._compiles_at_open = self._compiles
        if self.trace:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def close_window(self) -> None:
        """Counts the compiles the window saw; reduces the trace, if any."""
        self.compiles_in_window = self._compiles - self._compiles_at_open
        if self.trace:
            import jax

            from benchmarks import trace_reduce

            jax.profiler.stop_trace()
            trace = trace_reduce.load_xplane(trace_reduce.find_xplane(self.trace_dir))
            self.trace_summary = trace_reduce.summarize(trace)
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    def memory_peak(self) -> int:
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return max(peaks)


def load_reader(name: str, here: str = HERE):
    """The per-layer metric's own file, ``layer_metrics/<name>.py`` (a name may
    hold dots, so it is loaded by path)."""
    import importlib.util

    path = os.path.join(here, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_metrics(manifest: dict, cell: str, result: dict, run: Run) -> dict:
    """Each per-layer metric the manifest lists for this cell, from its own
    reader; a reader that finds nothing to read returns None and is left out."""
    out = {}
    peaks = load_json(HERE, "peaks.json").get(run.devices[0].device_kind)
    view = {"counters": result["counters"], "end_to_end": result["end_to_end"],
            "trace": run.trace_summary, "peaks": peaks, "chips": len(run.devices),
            "compiles_in_window": run.compiles_in_window}
    for m in manifest["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = load_reader(m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(loaded: dict, run: Run, result: dict) -> dict:
    manifest, cell = loaded["manifest"], loaded["cell"]["name"]
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(run.devices),
              "memory_peak_bytes": int(result["memory_peak_bytes"])}
    line = {"correct": all(c["ok"] for c in result["checks"]),
            "attempted": int(result["attempted"]), "failed": int(result["failed"])}
    if run.trace:
        line["metrics"] = layer_metrics(manifest, cell, result, run)
        s = run.trace_summary or {}
        device["busy_s"], device["window_s"] = s.get("busy_s", 0.0), s.get("window_s", 0.0)
        line["breakdown"] = {"device_ops": s.get("device_ops", []),
                             "idle_gaps": s.get("idle_gaps", [])}
    else:
        values = dict(result["end_to_end"], setup_s=run.setup_s)
        line["metrics"] = {}
        for m in manifest["end_to_end"]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            line["metrics"][m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    line["device"] = device
    return line


def execute(loaded: dict, devices, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    """Drive one run on ``devices`` and return its result line. The look for
    a chip is the caller's, so a test can drive the rest on what it has."""
    from benchmarks import check

    run = Run(loaded, devices, seed, seconds, trace, t_start)
    run.listen_for_compiles()
    driver = importlib.import_module(f"benchmarks.drivers.{loaded['mix']['driver']}")
    result = driver.run(run)
    check.print_checks(result["checks"])
    return result_line(loaded, run, result)


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="paddle_tpu benchmark: one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        loaded = load_cell(args.workload)
        devices = require_devices(loaded["cell"]["chips"])
        from paddle_tpu.core.config import apply_compile_cache

        print(f"compile cache: {apply_compile_cache(default_dir=os.path.join(CACHE_DIR, 'jax'))}",
              flush=True)
        line = execute(loaded, devices, args.seed, args.seconds, bool(args.trace), t_start)
    except BenchError as e:
        print(f"benchmark cannot run: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_DEVICE
    except Exception:  # the command's one boundary: say why, print no result
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0
