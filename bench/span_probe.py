"""The program's spans and scopes on a serve cell's rig, read by the
readers that wait for the harness to hand them over.

    python bench/span_probe.py --workload granite-serve-gen --seed 7 \
        --batches 4 --seconds 40 --out span_probe.json

``bench/run.py`` does not yet enable the serve plane's span log or map
the decode program's ops to the model's scopes, so the four readers of
the program's spans and scopes (``step_host_ms.serve``,
``plane_host_ms.serve``, ``queue_wait_ms.serve``,
``decode_state_share.serve``) have no ``--trace 1`` run to read.  This
script builds the same rig (``bench/serve_phase.py``), warms it up, and:

1. serves ``--batches`` whole batches with the profiler off, the span log
   alternately off and on (a fresh log each, annotated as a traced run
   enables it): the log's cost on ``decode_step_ms``,
   ``host_gap_ms.serve`` and each batch's rate and tails, and with the log
   on the span readers over the whole batch (the program's own regime);
2. runs a window of ``--seconds`` as ``bench/run.py --trace 1`` does, the
   log on and the profiler over the first batch, notes ``trace_stop``
   when the trace begins to stop, and reads every per-layer metric of the
   cell and the four new ones from that run, with the cross-checks
   between them;
3. splits the traced batch's device idle over the innermost host span
   (program or benchmark) that covers each part of it, with the device
   clock as the trace gives it and shifted to each end of the offset
   that the decode step's own spans allow.

It runs only on a TPU: elsewhere it exits with code 2.  It writes one
JSON object to ``--out`` and prints a summary as its last line.
"""
from __future__ import annotations

import argparse
import heapq
import json
import shutil
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.trace_reduce import WINDOW_SPAN  # noqa: E402

#: the readers of the program's spans and scopes
NEW_METRICS = ("step_host_ms.serve", "plane_host_ms.serve",
               "queue_wait_ms.serve", "decode_state_share.serve")
#: the compiler's names for the decode cache's slices and writes in the
#: granite-serve-gen program, as the cell's traces list its top ops
NAMED_CACHE_OPS = ("dynamic_update_slice.31", "dynamic_update_slice.32",
                   "bitcast_dynamic-update-slice_fusion.4",
                   "bitcast_dynamic-update-slice_fusion.5",
                   "dynamic-slice_bitcast_fusion.4",
                   "dynamic-slice_bitcast_fusion.5")


# ---------------------------------------------------------------------------
# the traced batch's idle, by host span (works on a trace record alone)
# ---------------------------------------------------------------------------


def innermost_segments(spans: list[tuple[str, float, float]], w0: float,
                       w1: float) -> list[tuple[float, float, str]]:
    """``[w0, w1]`` cut at every span boundary, each piece with the name of
    the shortest span that covers it (``no_span`` where none does)."""
    cuts = sorted({w0, w1} | {t for _, a, b in spans for t in (a, b)
                              if w0 < t < w1})
    by_start = sorted(spans, key=lambda s: s[1])
    active: list[tuple[float, float, str]] = []      # (length, end, name)
    out, i = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            name, s, e = by_start[i]
            heapq.heappush(active, (e - s, e, name))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        out.append((a, b, active[0][2] if active else "no_span"))
    return out


def idle_by_span(record: dict, offset_ns: float = 0.0) -> dict[str, float]:
    """Seconds of device idle inside the traced window under each host
    span, each part of a gap given to the innermost span over it, with
    the device's times moved later by ``offset_ns``."""
    spans = [(n, s, s + d) for n, s, d in record["host_spans"]
             if n != WINDOW_SPAN]
    marks = [(s, s + d) for n, s, d in record["host_spans"] if n == WINDOW_SPAN]
    if not marks:
        raise ValueError("the trace holds no window span")
    w0, w1 = marks[0]
    out: dict[str, float] = defaultdict(float)
    segments = innermost_segments(spans, w0, w1)
    devices = record["devices"]
    for ops in devices.values():
        busy = sorted((max(s + offset_ns, w0), min(s + d + offset_ns, w1))
                      for _, s, d in ops)
        idle, t = [], w0
        for a, b in busy:
            if b <= a:
                continue
            if a > t:
                idle.append((t, a))
            t = max(t, b)
        if t < w1:
            idle.append((t, w1))
        j = 0
        for a, b in idle:
            while j < len(segments) and segments[j][1] <= a:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < b:
                s0, s1, name = segments[k]
                out[name] += (min(b, s1) - max(a, s0)) * 1e-9
                k += 1
    n = max(len(devices), 1)
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def clock_offset(record: dict, module: str) -> tuple[float, float] | None:
    """Bounds in ns on how far the trace puts the device's clock early
    against the host's: a decode program cannot start before its
    ``serve.step.launch`` span begins, and the step's last program (the
    argmax) cannot end after its ``serve.step.wait`` span ends.  None
    where the decode runs and the step spans do not pair up."""
    from bench.scope_reduce import module_runs

    launches = sorted(s for n, s, _ in record["host_spans"]
                      if n == "serve.step.launch")
    waits = sorted(s + d for n, s, d in record["host_spans"]
                   if n == "serve.step.wait")
    runs = next(iter(record["modules"].values()), [])
    starts, _ = module_runs(module, runs)
    if not starts or not len(starts) == len(launches) == len(waits):
        return None
    every = sorted((s, s + d) for _, s, d in runs)
    lo = max(a - s for a, s in zip(launches, starts))
    hi = float("inf")
    for i, s in enumerate(starts):
        nxt = starts[i + 1] if i + 1 < len(starts) else float("inf")
        last = max(e for a, e in every if s <= a < nxt)
        hi = min(hi, waits[i] - last)
    return lo, hi


# ---------------------------------------------------------------------------
# on the chip
# ---------------------------------------------------------------------------


def use_log(rig, annotate: bool | None):
    """A fresh span log for the rig's plane and decode backend: off where
    ``annotate`` is None, else on."""
    from repro.core.monitoring import SpanLog

    log = SpanLog()
    if annotate is not None:
        log.enable(annotate=annotate)
    rig.driver.monitor.spans = log
    rig.backend.span_log = log
    return log


def span_stats(spans: list[dict], t0: float, t1: float) -> dict:
    """Count, mean ms and total s of each span name inside ``[t0, t1]``."""
    by: dict[str, list[float]] = defaultdict(list)
    for s in spans:
        if t0 <= s["start"] and s["end"] <= t1:
            by[s["name"]].append(s["end"] - s["start"])
    return {k: {"n": len(v), "mean_ms": 1e3 * statistics.fmean(v),
                "total_s": sum(v)} for k, v in sorted(by.items())}


def read_all(cell, names, run: dict) -> dict:
    from bench.harness import load_reader

    return {n: load_reader(cell.metrics_dir, n)(run) for n in names}


def log_cost(rig, cell, seed: int, batches: int) -> list[dict]:
    """One whole batch at a time, the log off and on in turn."""
    from bench import serve_phase as sp
    from bench.traffic_gen import closed_batch

    n = int(cell.traffic["batch"])
    vocab = rig.model["vocab_size"]
    out = []
    for i in range(batches):
        on = i % 2 == 1
        log = use_log(rig, True if on else None)
        rig.batches.clear()
        b = sp.run_batch(rig, closed_batch(cell.traffic, seed=seed, index=i,
                                           vocab=vocab, first_rid=i * n))
        window = (b.send, time.perf_counter())
        run = {"plane": "serve", "window": window,
               "steps": sp.step_log(rig, window),
               # no profiler: the whole batch is read, as the program runs
               "program_spans": log.export(), "trace_stop": window[1]}
        row = {"log_on": on, "n_spans": len(run["program_spans"]),
               **sp.end_to_end(rig, window),
               **read_all(cell, ("decode_step_ms", "host_gap_ms.serve")
                          + NEW_METRICS[:3], run)}
        if on:
            row["span_ms"] = span_stats(run["program_spans"], *window)
        out.append(row)
    return out


def traced_run(rig, cell, seed: int, seconds: float, peaks: dict,
               clock) -> dict:
    """A window as ``bench/run.py --trace 1`` runs it, with the log on."""
    import jax
    import numpy as np

    from bench import scope_reduce, trace_reduce
    from bench import serve_phase as sp
    from bench.run import Tracer

    log = use_log(rig, True)
    rig.batches.clear()
    tracer = Tracer(True)
    stop = {}

    def stop_trace():
        stop["t"] = time.perf_counter()
        tracer.stop()

    t_start = time.perf_counter()
    tracer.start()
    window = sp.serve_window(rig, cell.traffic, seed=seed, seconds=seconds,
                             on_first_batch=stop_trace)
    tracer.stop()
    t_done = time.perf_counter()
    try:
        path = trace_reduce.find_xplane(tracer.dir)
        record = trace_reduce.extract(path)
        record["modules"] = scope_reduce.extract_modules(path)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    trace = trace_reduce.reduce(record)

    # the decode program as it ran: a hit in the jitted call's cache
    backend = rig.backend
    dev = backend.devices[0]
    cache = next(iter(backend._caches.values()))
    inputs = {"inputs": jax.device_put(
        np.zeros((backend.max_batch, 1), np.int32), dev)}
    text, state = scope_reduce.program_text(
        backend._decode, backend._params[dev], cache, inputs, state_arg=1)
    module = scope_reduce.module_name(text)
    scopes = scope_reduce.reduce(
        record, scope_reduce.hlo_scopes(text, state), module)

    spans = log.export()
    run = {"plane": "serve", "model": rig.model, "peaks": peaks,
           "window": window, "steps": sp.step_log(rig, window),
           "max_batch": cell.config["serve"]["max_batch"],
           "compile_setup_s": clock.seconds_before(t_start),
           "trace": trace, "window_excluded_s": tracer.stop_s,
           "program_spans": spans, "trace_stop": stop["t"],
           "scopes": scopes}
    metrics = read_all(cell, [m["name"] for m in cell.per_layer]
                       + list(NEW_METRICS), run)
    traced_steps = [s for s in run["steps"] if s["t_return"] <= stop["t"]]
    step_spans = [s["end"] - s["start"] for s in spans
                  if s["name"] == "serve.step" and window[0] <= s["start"]
                  and s["end"] <= stop["t"]]
    busy = trace["busy_s"]
    named = sum(t for name, t in trace["device_ops"] if name in NAMED_CACHE_OPS)
    offset = clock_offset(record, module)
    return {
        "window": list(window), "trace_stop_s": tracer.stop_s,
        "batches": len(rig.batches), "window_compiles":
            clock.count_between(t_start, t_done),
        "metrics": metrics, "trace": trace, "scopes": scopes,
        "module": module, "state_params": sorted(state),
        "cross": {
            "serve_step_mean_ms": 1e3 * statistics.fmean(step_spans),
            "decode_step_ms_traced": 1e3 * statistics.fmean(
                s["t_return"] - s["t_call"] for s in traced_steps),
            "named_cache_ops_share": 100.0 * named / busy if busy else None,
            "layer_weights_share": (100.0 * scopes["layer_weights"] / busy
                                    if busy and scopes else None),
        },
        "span_ms": span_stats(spans, window[0], stop["t"]),
        "idle_midpoint": trace["idle_gaps"],
        "idle_by_span": {
            "trace_clock": idle_by_span(record),
            **({} if offset is None else {
                "offset_ns": list(offset),
                "shifted_lo": idle_by_span(record, offset[0]),
                "shifted_hi": idle_by_span(record, offset[1])}),
        },
    }


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--batches", type=int, default=4,
                    help="batches of the log's cost, off and on in turn")
    ap.add_argument("--seconds", type=float, default=40.0,
                    help="the traced window, as bench/run.py's --seconds")
    ap.add_argument("--out", default="span_probe.json")
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    args = parse(argv)
    from bench.harness import (BenchError, CompileClock, Spans, load_peaks,
                               require_chips, resolve_cell)

    try:
        cell = resolve_cell(args.workload)
        devices = require_chips(cell.chips)
    except (BenchError, FileNotFoundError) as err:
        print(f"span_probe: {err}", file=sys.stderr)
        return 2
    if cell.config["plane"] != "serve":
        print("span_probe: a serve cell only", file=sys.stderr)
        return 2
    import jax

    from bench import serve_phase as sp
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = load_peaks(devices[0].device_kind, cell.peaks_path)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        rig = sp.build(cell.config, cell.traffic, args.seed,
                       Spans(annotate=True), devices)
        sp.warm_up(rig, args.seed)
        out = {"device": devices[0].device_kind,
               "setup_s": time.perf_counter() - t0,
               "log_cost": log_cost(rig, cell, args.seed, args.batches)}
        out["traced"] = traced_run(rig, cell, args.seed, args.seconds,
                                   peaks, clock)
    rig.driver.shutdown()
    for key in ("decode_step_ms", "host_gap_ms.serve", "serve_tokens_per_s",
                "tbt_p95_ms"):
        for on in (False, True):
            vals = [r[key] for r in out["log_cost"] if r["log_on"] is on]
            if vals:
                out.setdefault("log_cost_mean", {})[
                    f"{key}.{'on' if on else 'off'}"] = statistics.fmean(vals)
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    summary = {"metrics": out["traced"]["metrics"],
               "cross": out["traced"]["cross"],
               "log_cost_mean": out.get("log_cost_mean"),
               "offset_ns": out["traced"]["idle_by_span"].get("offset_ns")}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
