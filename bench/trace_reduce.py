"""From a profiler trace to the device's busy time, its top operations and
its idle gaps, each gap named by the host span that covers it.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain record: the device operations (the ``XLA Ops`` line of each device
plane) and the benchmark's own host spans (``TraceAnnotation`` events
whose names start with one of ``SPAN_PREFIXES``), all on the trace's clock
in nanoseconds.  ``reduce`` works on that record alone, so it is tested on
a small recorded one.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

#: host spans the benchmark writes; ``bench.trace_window`` marks the
#: traced window itself
SPAN_PREFIXES = ("serve.", "train.", "bench.")
WINDOW_SPAN = "bench.trace_window"
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def extract(path: str) -> dict:
    """Device operations and host spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns, ev.duration_ns))
    return {"devices": devices, "host_spans": spans}


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _covering_span(spans: list[tuple[str, float, float]], t: float) -> str:
    """The innermost host span that covers time ``t``."""
    best, best_len = "no_span", float("inf")
    for name, a, b in spans:
        if a <= t <= b and b - a < best_len and name != WINDOW_SPAN:
            best, best_len = name, b - a
    return best


def op_name(name: str) -> str:
    """An HLO op's short name: ``%fusion.12 = bf16[...] fusion(...)`` gives
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Nanoseconds per op name, each op less the ops nested inside it (a
    while loop holds the ops of its body on the same line)."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []                  # [name, end]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][1]:
            stack.pop()
        if stack and s + d <= stack[-1][1]:
            out[stack[-1][0]] -= d
        out[name] += d
        stack.append([name, s + d])
    return out


def reduce(record: dict) -> dict:
    """Busy and window seconds (averaged over the devices), the top device
    operations by self time, and idle time by the host span covering it."""
    spans = [(n, s, s + d) for n, s, d in record["host_spans"]]
    marks = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not marks:
        raise ValueError("the trace holds no window span")
    w0, w1 = marks[0]
    busy_total = 0.0
    op_time: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    devices = record["devices"]
    for ops in devices.values():
        inside = [(op_name(n), max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in ops if min(s + d, w1) > max(s, w0)]
        for name, t in self_times(inside).items():
            op_time[name] += t * 1e-9
        busy = _union([(s, s + d) for _, s, d in inside])
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [t for ab in busy for t in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[_covering_span(spans, (a + b) / 2)] += (b - a) * 1e-9
    n = max(len(devices), 1)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_total * 1e-9 / n, "window_s": (w1 - w0) * 1e-9,
            "device_ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": [[k, v / n] for k, v in gaps]}
