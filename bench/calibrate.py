"""Readings that set a cell's limits: the program's numbers on many seeds,
and the control's and the planted faults' on a few, in one process.

    python bench/calibrate.py --workload granite-serve-gen --seeds 101-112 --controls 3

Serve cells: per seed, one batch at the cell's own load through the timed
path, the run's sample of requests, the served-token gap against the
float32 reference, and (on the first ``--controls`` seeds) the control:
the reference computed in float8, read at the same positions.  Each goes
through the run's own comparison with the committed limits, so the
control's line shows ``served_logit_gap`` failing.

Train cells: per seed, the cell's set-up steps through the supervisor
against the reference; on the first ``--controls`` seeds also the control
(the reference in float8 in the program's place) and a planted fault (the
reference with half of each batch left out), each against the float32
reference.

One JSON line per seed goes to standard output.  The benchmark's own runs
never run this; it runs only on a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import (BenchError, Spans, require_chips,  # noqa: E402
                           resolve_cell)


def seeds_of(spec: str) -> list[int]:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


def serve_seed(cell, seed: int, control: bool) -> dict:
    from bench import serve_phase as sp
    from bench.traffic_gen import closed_batch

    rig = sp.build(cell.config, cell.traffic, seed, Spans())
    sp.warm_up(rig, seed)
    sp.run_batch(rig, closed_batch(cell.traffic, seed=seed, index=0,
                                   vocab=rig.model["vocab_size"]))
    problems = sp.batch_problems(rig, rig.batches[0])
    sample = sp.sample_requests(rig, seed)
    sp.release_program_state(rig)
    length = cell.config["serve"]["max_len"]
    limits = cell.config["limits"]
    out = {"seed": seed, "problems": problems}
    readings = [("program", sp.served_gap)] + ([("control", sp.control_gap)]
                                               if control else [])
    for name, read in readings:
        out[name] = read(rig.model, seed, sample, length)
        # the same comparison as a run's: the control has to fail it
        out[name]["checks"] = sp.gap_checks(out[name], limits)
    return out


def train_seed(cell, seed: int, control: bool) -> dict:
    from bench import train_phase as tp

    probe, _ = tp.run_setup(cell.config, cell.traffic, seed, seconds=0.0,
                            spans=Spans())
    steps = cell.traffic["checked_steps"]
    prog = {"losses": [probe.step_loss(k) for k in range(steps)],
            "first_grad": probe.first_grad, "change": probe.change}
    del probe
    gc.collect()
    ref = tp.reference_readings(cell.config, cell.traffic, seed)
    out = {"seed": seed, "program": tp.compare(prog, ref)}
    if control:
        half = cell.traffic["global_batch"] // 2
        for name, kw in (("control", {"mode": "fp8"}), ("half_batch", {"rows": half})):
            out[name] = tp.compare(
                tp.reference_readings(cell.config, cell.traffic, seed, **kw), ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,200")
    ap.add_argument("--controls", type=int, default=3,
                    help="seeds, from the first, that also read the control")
    args = ap.parse_args(argv)
    try:
        cell = resolve_cell(args.workload)
        require_chips(cell.chips)
    except BenchError as err:
        print(f"calibrate: {err}", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    fn = serve_seed if cell.config["plane"] == "serve" else train_seed
    for i, seed in enumerate(seeds_of(args.seeds)):
        t0 = time.perf_counter()
        out = fn(cell, seed, i < args.controls)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
