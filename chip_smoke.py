"""Smoke run of the serve and train planes on a TPU.

    python chip_smoke.py            # one chip: the serve and train phases
    python chip_smoke.py --chips 4  # four chips: replica placement and failover

*serve* — granite-3-2b at its published widths (all 40 layers, random
weights from a fixed seed) behind ``WrathServeDriver``'s continuous plane:
2 replicas of 8 slots, 8 requests with equal 64-token prompts, all
arriving at t = 0, 16 new tokens each.  Checks that every request
completes with in-vocabulary tokens, that the served tokens are the greedy
tokens of the served decode program, and that its logits over one batch
agree with ``forward_train``'s logits over the same tokens.

*train* — ``python -m repro.launch.train --full --layers 4``: granite
widths cut to 4 layers, seq 1024, global batch 8 over 4 virtual hosts,
4 steps, ``host01`` lost during step 2.  Checks a finite loss every step,
one recovery of the lost host's shard, the global batch re-split over the
3 hosts left, and that the final checkpoint reads back with the trained
parameters.

*failover* (``--chips 4`` only) — the serve phase's requests on 4
replicas, one per device, with ``replica1`` killed mid-traffic, against
the same requests on 4 replicas that share one device.  Checks that every
replica's cache sits on its own device and that every request completes.

The script refuses any backend but the TPU.  A failed check raises, so
the exit code is not 0 and the result line is not printed.  The last line
of standard output is the JSON result.  Every number printed comes from a
smoke run, not from a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import main as train_main  # noqa: E402
from repro.models import (cache_defs, forward_train, materialize,  # noqa: E402
                          param_defs)
from repro.models.model import _logits  # noqa: E402
from repro.optim import OptConfig, init_opt_state  # noqa: E402
from repro.serve import JaxDecodeBackend, Request, WrathServeDriver  # noqa: E402

ARCH = "granite-3-2b"
#: seed of the random weights and of the prompts
SEED = 0
#: bound on max|decode - forward| / max|forward| over one batch's logits.
#: Both paths run in bf16 and round differently at every layer (one bf16
#: step of the largest logit alone is 1/128 of it); a decode one position
#: off, with one stale cache entry, moves the ratio past 1
LOGIT_REL_BOUND = 0.1
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A check of a smoke phase failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds the backend spent compiling, or loading compiled programs
    from the persistent cache, while the context is open."""

    def __init__(self) -> None:
        self.seconds = 0.0

    def _on_duration(self, event: str, secs: float, **_: object) -> None:
        if event == _COMPILE_EVENT:
            self.seconds += secs

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc: object) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


class _KillAfterSteps(JaxDecodeBackend):
    """Decode backend that kills one replica through its driver's chaos
    hook once the plane has taken ``at_step`` decode steps, so the kill
    lands at the same point of the traffic on every run."""

    def __init__(self, cfg, *, victim: str, at_step: int, **kw) -> None:
        super().__init__(cfg, **kw)
        self.victim, self.at_step = victim, at_step
        self.driver: WrathServeDriver | None = None
        self.steps = 0

    def step(self, replica, inputs):
        self.steps += 1
        if self.steps == self.at_step:
            self.driver.inject_fault("kill", self.victim)
        return super().step(replica, inputs)


def make_requests(cfg, n: int, prompt_len: int,
                  new_tokens: int) -> list[Request]:
    rng = np.random.default_rng(SEED)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=prompt_len).tolist(),
                    max_new_tokens=new_tokens)
            for i in range(n)]


def serve(cfg, *, replicas: int, max_batch: int, n_requests: int,
          prompt_len: int, new_tokens: int, devices: list | None = None,
          kill: tuple[str, int] | None = None):
    """Serve the requests on the continuous plane; returns the backend,
    the requests, the report and each replica's cache device."""
    max_len = prompt_len + new_tokens
    kw = dict(max_batch=max_batch, seed=SEED, max_len=max_len,
              devices=devices)
    backend = (_KillAfterSteps(cfg, victim=kill[0], at_step=kill[1], **kw)
               if kill else JaxDecodeBackend(cfg, **kw))
    reqs = make_requests(cfg, n_requests, prompt_len, new_tokens)
    with WrathServeDriver(cfg, n_replicas=replicas, max_batch=max_batch,
                          seed=SEED, decode=backend) as driver:
        backend.driver = driver
        placement = {name: str(dev)
                     for name, dev in backend.cache_devices().items()}
        report = driver.serve_continuous(reqs, horizon=600.0)
    backend.driver = None
    return backend, reqs, report, placement


def check_served(cfg, reqs: list[Request], report, new_tokens: int) -> None:
    check(report.completed == len(reqs),
          f"{report.completed}/{len(reqs)} requests completed")
    for r in reqs:
        check(len(r.generated) == new_tokens,
              f"request {r.rid} has {len(r.generated)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.generated),
              f"request {r.rid} has a token outside the vocabulary")


def logit_parity(cfg, backend: JaxDecodeBackend, reqs: list[Request],
                 max_batch: int) -> dict:
    """Rerun the served decode program over one batch of the served token
    streams from a fresh cache, and compare it with the served tokens and
    with ``forward_train`` over the same tokens."""
    batch = reqs[:max_batch]
    tokens = jnp.asarray([r.prompt + r.generated[:-1] for r in batch],
                         jnp.int32)                          # (B, T)
    b, t = tokens.shape
    cache = materialize(cache_defs(cfg, max_batch, backend.max_len),
                        jax.random.PRNGKey(0))
    pad = jnp.zeros((max_batch - b, 1), jnp.int32)
    steps = []
    for i in range(t):
        logits, cache = backend._decode(
            backend.params, cache,
            {"inputs": jnp.concatenate([tokens[:, i:i + 1], pad])})
        steps.append(logits[:b, 0])
    dec = jnp.stack(steps, axis=1)                           # (B, T, V)
    fwd = jax.jit(lambda p, x: _logits(
        p, forward_train(p, {"inputs": x}, cfg, remat=False)[0], cfg))(
        backend.params, tokens)
    prompt_len = len(batch[0].prompt)
    greedy = np.asarray(jnp.argmax(dec[:, prompt_len - 1:], axis=-1))
    served = np.asarray([r.generated for r in batch])
    scale = float(jnp.max(jnp.abs(fwd)))
    diff = float(jnp.max(jnp.abs(dec - fwd)))
    agree = float(jnp.mean(jnp.argmax(dec, -1) == jnp.argmax(fwd, -1)))
    return {"dec_finite": bool(jnp.all(jnp.isfinite(dec))),
            "max_abs_diff": diff, "max_abs_logit": scale,
            "rel_diff": diff / max(scale, 1e-30),
            "argmax_agree": agree,
            "served_greedy": int((served == greedy).sum()),
            "served_tokens": int(served.size)}


def serve_phase(cfg, *, replicas: int = 2, max_batch: int = 8,
                n_requests: int = 8, prompt_len: int = 64,
                new_tokens: int = 16) -> dict:
    backend, reqs, report, placement = serve(
        cfg, replicas=replicas, max_batch=max_batch, n_requests=n_requests,
        prompt_len=prompt_len, new_tokens=new_tokens)
    check_served(cfg, reqs, report, new_tokens)
    parity = logit_parity(cfg, backend, reqs, max_batch)
    check(parity["dec_finite"], "decode logits are not finite")
    check(parity["served_greedy"] == parity["served_tokens"],
          f"{parity['served_greedy']}/{parity['served_tokens']} served "
          "tokens are the decode program's greedy tokens")
    check(parity["rel_diff"] <= LOGIT_REL_BOUND,
          f"decode and forward logits differ by {parity['rel_diff']} of "
          f"their scale (bound {LOGIT_REL_BOUND})")
    return {"layers": cfg.n_layers, "d_model": cfg.d_model,
            "param_bytes": sum(x.nbytes for x in jax.tree.leaves(
                backend.params)),
            "completed": report.completed, "requests": len(reqs),
            "decode_steps": report.decode_steps,
            "placement": placement, **parity}


def train_phase(ckpt_dir: str, *, full: bool = True, layers: int = 4,
                seq: int = 1024, steps: int = 4) -> dict:
    argv = ["--arch", ARCH, "--layers", str(layers), "--seq", str(seq),
            "--global-batch", "8", "--hosts", "4", "--steps", str(steps),
            "--inject", "host_down:2:host01", "--ckpt-dir", ckpt_dir]
    rep = train_main(argv + (["--full"] if full else []))
    check(rep.steps_completed == steps,
          f"{rep.steps_completed}/{steps} steps completed")
    check(all(np.isfinite(rep.losses)), f"a loss is not finite: {rep.losses}")
    # host01 is lost during step 2: its shard fails, the policy stack moves
    # it to another host, and later steps re-split the batch over 3 hosts
    lost = [r for r in rep.recoveries if r["host"] == "host01"]
    check(len(lost) == 1 and lost[0]["step"] == 2
          and lost[0]["error"] == "HardwareShutdownError",
          f"not one recovery of host01's shard at step 2: {rep.recoveries}")
    check(rep.recovered_all, f"a failure was not recovered: {rep.recoveries}")
    check(rep.final_hosts == 3,
          f"{rep.final_hosts} hosts left after losing host01, not 3")

    # read the final checkpoint back: the trained parameters, not the
    # initial ones, and finite
    cfg = (get_config if full else get_smoke_config)(ARCH)
    cfg = cfg.scaled(n_layers=layers)
    # the supervisor draws its initial weights from its data seed, 0
    key = jax.random.PRNGKey(0)
    like_params = jax.eval_shape(lambda: materialize(param_defs(cfg), key))
    like = {"params": like_params,
            "opt": jax.eval_shape(lambda p: init_opt_state(p, OptConfig()),
                                   like_params)}
    restored = CheckpointManager(ckpt_dir).restore_latest(like)
    check(restored is not None, "no checkpoint was written")
    tree, meta = restored
    check(meta["step"] == steps - 1,
          f"latest checkpoint is step {meta['step']}, not {steps - 1}")
    leaves = jax.tree.leaves(tree["params"])
    check(all(bool(jnp.all(jnp.isfinite(x))) for x in leaves),
          "a restored parameter is not finite")
    init = jax.tree.leaves(materialize(param_defs(cfg), key))
    check(any(not bool(jnp.array_equal(a, b)) for a, b in zip(leaves, init)),
          "the restored parameters are the initial ones")
    return {"layers": cfg.n_layers, "d_model": cfg.d_model, "seq": seq,
            "steps": rep.steps_completed, "losses": rep.losses,
            "hosts_left": rep.final_hosts,
            "recoveries": [(r["step"], r["host"], r["action"])
                           for r in rep.recoveries],
            "speculations": rep.speculations,
            "checkpoint_step": meta["step"]}


def failover_phase(cfg, *, devices: list, replicas: int = 4,
                   max_batch: int = 8, n_requests: int = 8,
                   prompt_len: int = 64, new_tokens: int = 16) -> dict:
    """The same requests and the same mid-traffic kill of ``replica1`` on
    replicas spread over ``devices`` and on replicas sharing the first."""
    # kill once every replica has taken about a quarter of its steps
    kill = ("replica1", replicas * (prompt_len + new_tokens) // 4)
    kw = dict(replicas=replicas, max_batch=max_batch, n_requests=n_requests,
              prompt_len=prompt_len, new_tokens=new_tokens, kill=kill)
    out = {}
    streams = {}
    for label, devs in (("one_device", devices[:1]), ("spread", devices)):
        with CompileClock() as cc:
            t0 = time.perf_counter()
            _, reqs, report, placement = serve(cfg, devices=devs, **kw)
            wall = time.perf_counter() - t0
        gc.collect()
        check_served(cfg, reqs, report, new_tokens)
        check(any(r["replica"] == kill[0] for r in report.recoveries),
              f"{kill[0]} held no request when it was killed")
        streams[label] = [r.generated for r in reqs]
        out[label] = {"placement": placement,
                      "completed": report.completed,
                      "recovered": sum(r.recoveries for r in reqs),
                      "denylisted": report.denylisted,
                      "compile_s": cc.seconds, "wall_s": wall}
    spread = out["spread"]["placement"]
    check(len(set(spread.values())) == min(replicas, len(devices)),
          f"replica caches are not on distinct devices: {spread}")
    out["same_tokens"] = sum(a == b for a, b in zip(streams["one_device"],
                                                    streams["spread"]))
    return out


def _run(name: str, fn, *args, **kw) -> dict:
    with CompileClock() as cc:
        t0 = time.perf_counter()
        res = fn(*args, **kw)
        wall = time.perf_counter() - t0
    gc.collect()  # the phase's device arrays go before the next phase
    res = {"compile_s": cc.seconds, "wall_s": wall, **res}
    print(f"{name}: {json.dumps(res)}", flush=True)
    return res


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the replica placement and failover "
                         "phase across four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    if args.chips == 4:
        _run("failover", failover_phase, cfg, devices=devices[:4])
    else:
        _run("serve", serve_phase, cfg)
        with tempfile.TemporaryDirectory(prefix=".smoke_ckpt_",
                                         dir=ROOT) as ckpt:
            _run("train", train_phase, ckpt)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
