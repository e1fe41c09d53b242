"""WRATH-supervised serving launcher.

Static batching (the historical baseline)::

    python -m repro.launch.serve --arch olmoe-1b-7b --requests 16 \
        --replicas 3 --kill replica0:5

Continuous batching with SLO admission and autoscaling::

    python -m repro.launch.serve --continuous --arrival-rate 40 \
        --deadline-ms 800 --autoscale 1:6 --scheduler least_loaded

``--decode sim`` swaps the jax model for the deterministic simulated
backend on a virtual clock: a minute of traffic replays byte-identically
in milliseconds, which is how the serving benchmarks and chaos tests run.

``--full`` serves the configuration at its published widths in place of
the reduced smoke variant (one replica per device on a multi-chip host)::

    python -m repro.launch.serve --full --decode jax --prompt-len 64 \
        --new-tokens 16 --replicas 2 --max-batch 8
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.engine.scheduler import SCHEDULERS, make_scheduler
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import (ReplicaAutoscaler, Request, SLOAdmissionPolicy,
                         WrathServeDriver)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    help=f"one of {', '.join(a.replace('_', '-') for a in ARCH_IDS)}")
    ap.add_argument("--full", action="store_true",
                    help="published widths instead of the smoke config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--scheduler", default=None, choices=sorted(SCHEDULERS),
                    help="replica-selection policy (default round_robin)")
    ap.add_argument("--kill", default=None,
                    help="replica:step — kill a replica mid-decode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    # -- continuous plane ------------------------------------------------
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (queue -> admission -> slots)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO; enables deadline-aware admission")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="mean request arrivals per second (default: all "
                         "requests arrive at t=0); implies --continuous")
    ap.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                    help="enable the replica autoscaler, e.g. 1:6; "
                         "implies --continuous")
    ap.add_argument("--decode", default="jax", choices=("jax", "sim"),
                    help="decode backend; 'sim' runs the modeled-cost "
                         "backend on a virtual clock (deterministic)")
    args = ap.parse_args(argv)
    continuous = (args.continuous or args.arrival_rate is not None
                  or args.autoscale is not None)

    enable_compile_cache()
    cfg = (get_config if args.full else get_smoke_config)(args.arch)
    clock = None
    if args.decode == "sim":
        from repro.sim import VirtualClock
        clock = VirtualClock()
    policy = None
    if args.autoscale:
        lo, _, hi = args.autoscale.partition(":")
        from repro.engine.policies import WrathPolicy
        policy = [WrathPolicy(),
                  ReplicaAutoscaler(min_replicas=int(lo or 1),
                                    max_replicas=int(hi or 6))]
    driver = WrathServeDriver(
        cfg, n_replicas=args.replicas, max_batch=args.max_batch,
        seed=args.seed, clock=clock, decode=args.decode, policy=policy,
        scheduler=make_scheduler(args.scheduler) if args.scheduler else None,
        admission=SLOAdmissionPolicy() if args.deadline_ms else None,
        # room for prompt and output, so one batch's cache ring never wraps
        max_len=args.prompt_len + args.new_tokens)
    rng = np.random.default_rng(args.seed)
    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=args.prompt_len).tolist(),
                    max_new_tokens=args.new_tokens,
                    deadline_s=deadline_s)
            for i in range(args.requests)]
    kill = None
    if args.kill:
        name, _, step = args.kill.partition(":")
        kill = (name, int(step or 5))

    if continuous:
        arrivals = None
        if args.arrival_rate:
            gaps = rng.exponential(1.0 / args.arrival_rate,
                                   size=args.requests)
            arrivals = np.cumsum(gaps).tolist()
        faults = None
        if kill:
            # in the continuous plane the kill is time-based: fire it when
            # roughly that many decode steps have elapsed at nominal cost
            faults = [(0.02 * kill[1], "kill", kill[0])]
        rep = driver.serve_continuous(reqs, arrivals=arrivals, faults=faults)
        driver.shutdown()
    else:
        rep = driver.serve(reqs, kill_replica_at=kill)

    if args.json:
        print(json.dumps({
            "arch": cfg.name, "mode": "continuous" if continuous else "static",
            "completed": rep.completed, "failed": rep.failed,
            "rejected": rep.rejected, "shed": rep.shed,
            "tokens": rep.tokens_generated, "tokens_per_s": rep.tokens_per_s,
            "requests_per_s": rep.requests_per_s,
            "p50_s": rep.p50_s, "p99_s": rep.p99_s,
            "denylisted": rep.denylisted, "recoveries": rep.recoveries,
            "autoscaled_up": rep.autoscaled_up,
            "autoscaled_down": rep.autoscaled_down,
            "replicas_final": rep.replicas_final,
        }, indent=1))
        return
    print(f"{cfg.name}: {rep.completed}/{len(reqs)} requests, "
          f"{rep.tokens_generated} tokens ({rep.tokens_per_s:.1f} tok/s)")
    if continuous:
        print(f"  rps={rep.requests_per_s:.2f} p50={rep.p50_s*1e3:.1f}ms "
              f"p99={rep.p99_s*1e3:.1f}ms rejected={rep.rejected} "
              f"shed={rep.shed} replicas={rep.replicas_final} "
              f"(+{rep.autoscaled_up}/-{rep.autoscaled_down})")
    if rep.denylisted:
        print(f"denylisted replicas: {rep.denylisted}")
    for r in rep.recoveries:
        where = f"step {r['step']}" if "step" in r else f"request {r['rid']}"
        print(f"  recovery: {r['replica']} at {where} -> {r['action']}")


if __name__ == "__main__":
    main()
