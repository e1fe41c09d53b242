"""What every cell shares: finding a cell's files by name, the device
check, the compile clock, host spans, and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  — model sizes, the cut, limits;
* ``bench/traffic/<traffic>.json`` — the generator's kind and parameters;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchError(RuntimeError):
    """The benchmark cannot run this cell as asked."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, traffic and the
    metrics ``BENCHMARK.json`` asks of it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    peaks_path: Path = BENCH / "peaks.json"
    metrics_dir: Path = BENCH / "metrics"


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, bench_root: Path = CHECKOUT) -> Cell:
    """Find a cell and everything it names, by name alone."""
    spec = load_json(bench_root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(bench_root / cfg_entry["file"])
    traffic = load_json(bench_root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a workloads key is read wherever the
    # end-to-end metric it moves is reported
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                metrics_dir=bench_root / "bench" / "metrics")


def load_reader(metrics_dir: Path, metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = metrics_dir / f"{metric}.py"
    if not path.exists():
        raise BenchError(f"no reader {path} for per-layer metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])          # field 22 of stat(5)
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def require_chips(chips: int) -> Any:
    """The TPU devices this cell needs; raises on any other backend."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_info(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def load_peaks(device_kind: str, path: Path = BENCH / "peaks.json") -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``."""
    table = load_json(path)["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


class CompileClock:
    """Counts backend compiles (or loads from the persistent cache) and
    their seconds, with the time each ended."""

    def __init__(self) -> None:
        self.events: list[tuple[float, float]] = []   # (end time, seconds)

    def _on_duration(self, event: str, secs: float, **_: object) -> None:
        if event == _COMPILE_EVENT:
            self.events.append((time.perf_counter(), secs))

    def __enter__(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc: object) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def seconds_before(self, t: float) -> float:
        return sum(s for end, s in self.events if end <= t)

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for end, _ in self.events if t0 < end <= t1)


@dataclass
class Spans:
    """Host spans of the benchmark's own calls into the program.  With
    ``annotate`` each span is also a profiler ``TraceAnnotation``, on the
    same clock as the device trace."""

    annotate: bool = False
    records: list[tuple[str, float, float]] = field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile by nearest rank, over every value; an
    infinite value (a failed request) counts as a miss."""
    if not values:
        raise BenchError("percentile of no values")
    vals = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def check(value, limit, ok) -> dict:
    """One number compared, beside its limit."""
    return {"value": value, "limit": limit, "ok": bool(ok)}


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    out: dict[str, Any] = {"correct": correct, "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks          # last: each number compared, beside its limit
    return json.dumps(out)


def print_checks(checks: dict, stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=stream)
