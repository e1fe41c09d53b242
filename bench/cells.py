"""One run of a serve cell or a train cell: set-up, window, check.

Each returns the end-to-end metrics, the record the per-layer readers
read (``run``), the checks with their limits, the request or step counts,
and the device as JAX reports it.
"""
from __future__ import annotations

import gc
import sys
import time

from bench.harness import CompileClock, Spans, check, device_info, process_age_s


def run_serve(cell, *, seed: int, seconds: float, devices: list,
              clock: CompileClock, tracer, peaks: dict) -> dict:
    from bench import serve_phase as sp

    spans = Spans(annotate=tracer.enabled)
    started_s = process_age_s()
    rig = sp.build(cell.config, cell.traffic, seed, spans, devices)
    sp.warm_up(rig, seed)
    setup_s = process_age_s()
    t_setup_end = time.perf_counter()
    tracer.start()
    window = sp.serve_window(rig, cell.traffic, seed=seed, seconds=seconds,
                             on_first_batch=tracer.stop)
    tracer.stop()
    t_done = time.perf_counter()
    device = device_info(devices)
    trace = tracer.reduce()

    problems = [p for b in rig.batches for p in sp.batch_problems(rig, b)]
    e2e = sp.end_to_end(rig, window)
    e2e["setup_s"] = setup_s
    attempted = sum(len(b.requests) for b in rig.batches)
    failed = sum(1 for b in rig.batches for s in b.served if s.status != "done")
    run = {"plane": "serve", "model": rig.model, "peaks": peaks,
           "window": window, "steps": sp.step_log(rig, window),
           "max_batch": cell.config["serve"]["max_batch"],
           "compile_setup_s": clock.seconds_before(t_setup_end),
           "trace": trace, "window_excluded_s": tracer.stop_s,
           "setup_parts": setup_parts(started_s, setup_s, spans)}

    sample = sp.sample_requests(rig, seed)
    sp.release_program_state(rig)
    gc.collect()
    length = cell.config["serve"]["max_len"]
    gap = sp.served_gap(rig.model, rig.seed, sample, length) if sample else None
    checks = {
        **sp.gap_checks(gap, cell.config["limits"]),
        "missing_answers": check(failed, 0, failed == 0),
        "seating_mismatches": check(len(problems), 0, not problems),
        "window_compiles": check(clock.count_between(t_setup_end, t_done), 0,
                                  clock.count_between(t_setup_end, t_done) == 0),
    }
    if problems:
        run["problems"] = problems[:5]
    return {"end_to_end": e2e, "run": run, "checks": checks,
            "attempted": attempted, "failed": failed, "device": device}


def setup_parts(started_s: float, setup_s: float, spans: Spans) -> dict:
    """Where set-up went: the process's start and imports, then each
    ``setup.*`` span, in seconds; printed to standard error."""
    parts = {"start_and_imports": started_s}
    parts.update({name.split(".", 1)[1]: t1 - t0 for name, t0, t1 in
                  spans.records if name.startswith("setup.")})
    parts["other"] = setup_s - sum(parts.values())
    print("setup_s parts: " + ", ".join(f"{k} {v:.3f}" for k, v in parts.items()),
          file=sys.stderr)
    return parts


def run_train(cell, *, seed: int, seconds: float, devices: list,
              clock: CompileClock, tracer, peaks: dict) -> dict:
    from bench import train_phase as tp

    return tp.run_cell(cell, seed=seed, seconds=seconds, devices=devices,
                       clock=clock, tracer=tracer, peaks=peaks)
