"""From a profiler trace to numbers. ``load_xplane`` turns the ``.xplane.pb``
that ``jax.profiler`` wrote into a plain form (planes -> lines -> events of
name, start and duration in nanoseconds); every reduction works on that form,
so it is checked on the small recorded trace in ``tests/data``."""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def short_name(name: str) -> str:
    """The HLO instruction's own name without its number, from the whole
    instruction text XLA prints for an op; a Mosaic kernel keeps its call
    target, which is all the trace says about it."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    head = re.sub(r"[.\d]+$", "", head) or head
    if "tpu_custom_call" in name:
        head += "(tpu_custom_call)"
    return head


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str, host_prefix: str = "bench.") -> dict:
    """{"devices": {plane: [Event]}, "host": [Event]}: the op events of each
    device plane and the host events whose name starts with ``host_prefix``
    (the benchmark's own ``TraceAnnotation``s)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (short_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        host.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def merge_intervals(events: Sequence[Event]) -> List[Tuple[int, int]]:
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event]) -> int:
    """Length of the union of the intervals in which an operation ran."""
    return sum(e - s for s, e in merge_intervals(events))


def window_ns(trace: dict) -> int:
    """From the first device operation's start to the last one's end."""
    evs = [e for d in trace["devices"].values() for e in d]
    if not evs:
        return 0
    return max(s + d for _, s, d in evs) - min(s for _, s, _ in evs)


def self_times(events: Sequence[Event]) -> List[Tuple[str, int]]:
    """(name, self nanoseconds) per event: its duration less the events
    nested in it (a ``while`` op spans the ops of its body on the same line)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [e[2] for e in events]
    stack: List[int] = []
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], max(self_ns[i], 0)) for i in range(len(events))]


def op_seconds(events: Sequence[Event], pattern: Optional[str] = None) -> Dict[str, float]:
    """Summed self seconds by operation name, optionally only names that
    match ``pattern``; nested time is counted once, in the innermost op."""
    rx = re.compile(pattern) if pattern else None
    out: Dict[str, float] = {}
    for name, ns in self_times(events):
        if rx is None or rx.search(name):
            out[name] = out.get(name, 0.0) + ns / 1e9
    return out


def idle_gaps(events: Sequence[Event], host: Sequence[Event], top: int = 10):
    """Device idle seconds by what the host was doing: each gap between
    device operations is shared out to the benchmark's host annotations by
    the time they cover of it; what none covers is the program's own host
    code. Returns [[name, seconds], ...], largest first.

    The gaps come in order of time, so the host events are sorted once and
    swept beside them: ``reach`` holds, in order of their starts, the events
    that began before the gap's end and have not ended by its start, which are
    the only ones that can cover any of it. A window of a million gaps and
    some thousand annotations reduces in a second; walking every host event
    for every gap took nine minutes of a traced serve run (PERF.md, PR 36)."""
    merged = merge_intervals(events)
    host = sorted(host, key=lambda e: e[1])
    reach: List[Event] = []
    taken = 0
    by_name: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        while taken < len(host) and host[taken][1] < s1:
            reach.append(host[taken])
            taken += 1
        if reach:
            reach = [h for h in reach if h[1] + h[2] > e0]
        left = s1 - e0
        for name, hs, hd in reach:
            cover = min(s1, hs + hd) - max(e0, hs)
            if cover > 0:
                by_name[name] = by_name.get(name, 0.0) + cover / 1e9
                left -= cover
        if left > 0:
            by_name["unattributed"] = by_name.get("unattributed", 0.0) + left / 1e9
    return [[n, s] for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def summarize(trace: dict, top: int = 10) -> dict:
    """What the per-layer readers and the result line take from a trace:
    per-device busy seconds averaged over the devices, the traced window,
    summed seconds per op name (over all devices, divided by their number),
    the top operations and the attributed idle gaps of the first device."""
    devs = trace["devices"]
    if not devs:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "device_ops": [], "idle_gaps": []}
    n = len(devs)
    ops: Dict[str, float] = {}
    for evs in devs.values():
        for name, sec in op_seconds(evs).items():
            ops[name] = ops.get(name, 0.0) + sec / n
    first = devs[sorted(devs)[0]]
    return {
        "busy_s": sum(busy_ns(e) for e in devs.values()) / n / 1e9,
        "window_s": window_ns(trace) / 1e9,
        "ops": ops,
        "device_ops": [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle_gaps(first, trace["host"], top),
        "n_devices": n,
    }
