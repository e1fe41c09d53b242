"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload granite-serve-gen --seed 7 --seconds 40 --trace 0

The cell, its configuration, its traffic and its per-layer metrics are
all found by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The
run loads, warms up every shape the cell uses, measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON object as the last line of standard output.  With
``--trace 0`` its metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler trace covers the start of the window and the
metrics are the cell's per-layer ones.

It runs only on a TPU: on any other backend, or with fewer chips than the
cell asks for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import (BenchError, CompileClock,  # noqa: E402
                           load_peaks, load_reader, print_checks,
                           require_chips, resolve_cell, result_line)


class Tracer:
    """A profiler trace over part of the window, reduced to device busy
    time, top operations and idle gaps."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled else None
        self._mark = None
        self.running = False
        #: seconds the trace took to stop and write, inside the window
        self.stop_s = 0.0

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        jax.profiler.start_trace(self.dir)
        self._mark = jax.profiler.TraceAnnotation("bench.trace_window")
        self._mark.__enter__()
        self.running = True

    def stop(self) -> None:
        if not self.running:
            return
        import time

        import jax

        t0 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False
        self.stop_s = time.perf_counter() - t0

    def reduce(self) -> dict | None:
        if not self.enabled:
            return None
        from bench import trace_reduce

        try:
            return trace_reduce.reduce(
                trace_reduce.extract(trace_reduce.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    args = parse(argv)
    try:
        cell = resolve_cell(args.workload)
        devices = require_chips(cell.chips)
    except (BenchError, FileNotFoundError) as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes to the persistent cache, so a second run of a
    # cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = load_peaks(devices[0].device_kind, cell.peaks_path)
    plane = cell.config["plane"]
    with CompileClock() as clock:
        tracer = Tracer(bool(args.trace))
        if plane == "serve":
            from bench.cells import run_serve as run_plane
        elif plane == "train":
            from bench.cells import run_train as run_plane
        else:
            raise BenchError(f"unknown plane {plane!r}")
        out = run_plane(cell, seed=args.seed, seconds=args.seconds,
                        devices=devices, clock=clock, tracer=tracer,
                        peaks=peaks)
    trace = out["run"].get("trace")
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(cell.metrics_dir, m["name"])(out["run"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    device = out["device"]
    breakdown = None
    if trace is not None:
        device = {**device, "busy_s": trace["busy_s"],
                  "window_s": trace["window_s"]}
        breakdown = {"device_ops": trace["device_ops"],
                     "idle_gaps": trace["idle_gaps"]}
    checks = out["checks"]
    correct = all(c["ok"] for c in checks.values())
    sys.stdout.flush()
    print_checks(checks)
    sys.stderr.flush()
    print(result_line(correct=correct, attempted=out["attempted"],
                      failed=out["failed"], metrics=metrics, device=device,
                      checks=checks, breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
