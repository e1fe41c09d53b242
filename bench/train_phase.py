"""Train cells: the program's ``WrathTrainSupervisor``, built as
``python -m repro.launch.train --full --layers N`` builds it, driven by
one ``run`` call.

Set-up makes the weights from the seed, warms the gradient of every shard
size the cell uses (the 3-row shard that follows the host loss among
them), and lets the same ``run`` call take its first steps; the reference
follows those.  The window opens when the last of them ends and closes at
the end of the first step to end past ``--seconds`` (and not before the
step after the host loss); every step in it counts.  The host loss comes in the
window's 5th step (``timed_step`` 4 of the traffic file).

Spans come from two wrappers: around the supervisor's per-shard gradient
call (``_shard_task``, which ends in ``float(loss)``, a sync) and around
its ``adamw_apply``, whose return ends a step.  The checkpoint store is
replaced by one that keeps nothing: the cell measures no save, and a run
writes nothing to disk.
"""
from __future__ import annotations

import functools
import gc
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.train.supervisor as supervisor_mod
from repro.engine.policies import WrathPolicy
from repro.models import abstract, param_defs
from repro.optim import OptConfig
from repro.train import TrainEvent, WrathTrainSupervisor

from bench import cost, reference, weights
from bench.harness import Spans, check, device_info, process_age_s
from bench.program import program_config
from bench.synthetic_tokens import synthetic_batch


class WindowClosed(Exception):
    """Raised at the first step boundary past the window's end."""


class NoCheckpoints:
    """The supervisor's checkpoint store, keeping nothing."""

    def save(self, step, tree, metadata=None) -> None:
        pass

    def restore_latest(self, tree_like, *, shardings=None):
        return None

    def wait(self) -> None:
        pass


def build_supervisor(config: dict, traffic: dict, seed: int,
                     ckpt_dir: str) -> WrathTrainSupervisor:
    opt = config["optimizer"]
    sup = WrathTrainSupervisor(
        program_config(config),
        OptConfig(lr=opt["lr"], warmup_steps=opt["warmup_steps"],
                  total_steps=opt["total_steps"],
                  min_lr_ratio=opt["min_lr_ratio"], b1=opt["b1"], b2=opt["b2"],
                  eps=opt["eps"], weight_decay=opt["weight_decay"],
                  clip_norm=opt["clip_norm"], moment_dtype=opt["moment_dtype"]),
        n_hosts=traffic["hosts"], global_batch=traffic["global_batch"],
        seq_len=traffic["seq_len"], ckpt_dir=ckpt_dir,
        ckpt_every=10 ** 9, data_seed=seed, policy=[WrathPolicy()])
    sup.ckpt = NoCheckpoints()
    return sup


class TrainProbe:
    """Wraps one supervisor's per-step calls; records shard calls, step
    ends, the first steps' readings, and opens and closes the window."""

    def __init__(self, sup: WrathTrainSupervisor, to_bench, p0: dict, *,
                 checked: int, seconds: float, spans: Spans, tracer=None,
                 trace_steps: int = 0, closes_after: int = 0):
        #: a tree shaped like the program's parameters, as {benchmark name: leaf}
        self.to_bench = to_bench
        self.sup, self.p0 = sup, p0
        self.checked, self.seconds = checked, seconds
        #: the window holds at least the steps before this one
        self.closes_after = closes_after
        self.spans, self.tracer, self.trace_steps = spans, tracer, trace_steps
        self.step_ends: list[float] = []
        self.shards: list[dict] = []
        self._step_shards: dict[int, dict] = {}
        self.first_grad: dict | None = None
        self.change: dict | None = None
        self.window: tuple[float, float] | None = None
        self.setup_s: float | None = None
        self._orig_shard = sup._shard_task
        self._orig_adamw = supervisor_mod.adamw_apply

    def __enter__(self) -> "TrainProbe":
        self.sup._shard_task = self._shard_task
        supervisor_mod.adamw_apply = self._adamw
        return self

    def __exit__(self, *exc) -> None:
        self.sup._shard_task = self._orig_shard
        supervisor_mod.adamw_apply = self._orig_adamw

    def warm(self, params: dict, sizes: list[int]) -> None:
        """Compile each shard size through the supervisor's own call."""
        host = self.sup.healthy_hosts()[0]
        batch = supervisor_mod.batch_for(self.sup.cfg, max(sizes),
                                         self.sup.seq_len, 0, seed=0)
        for n in sizes:
            sub = {k: v[:n] for k, v in batch.items()}
            self._orig_shard(-1, host, params, sub, False)

    def _shard_task(self, step, host, params, batch, injected_nan):
        k = len(self.step_ends)
        t0 = time.perf_counter()
        ok = False
        try:
            with self.spans.span("train.shard_grad"):
                loss, grads = self._orig_shard(step, host, params, batch,
                                               injected_nan)
            ok = True
        finally:
            rows = int(batch["inputs"].shape[0])
            self.shards.append({"step": k, "t0": t0, "t1": time.perf_counter(),
                                "rows": rows, "host": host.name, "ok": ok})
        # a speculative re-run of a shard replaces its first result
        self._step_shards.setdefault(k, {})[id(batch)] = (loss, rows)
        return loss, grads

    def step_loss(self, k: int) -> float:
        parts = self._step_shards[k].values()
        return sum(loss * rows for loss, rows in parts) / sum(r for _, r in parts)

    def _adamw(self, params, grads, state, opt_cfg):
        with self.spans.span("train.optimizer"):
            out = self._orig_adamw(params, grads, state, opt_cfg)
        t = time.perf_counter()
        k = len(self.step_ends)
        self.step_ends.append(t)
        if k == 0:
            # the first gradient as the optimizer got it: m = (1 - b1) g
            b1 = opt_cfg.b1
            self.first_grad = {name: float(v) / (1 - b1) for name, v in
                               reference.norms(self.to_bench(out[1]["m"])).items()}
        if k == self.checked - 1:
            self.change = {name: float(v) for name, v in
                           reference.diff_norms(self.to_bench(out[0]),
                                                self.to_bench(self.p0)).items()}
            self.p0 = None
            # the window opens once the readings above are taken
            t = self.step_ends[k] = time.perf_counter()
            self.setup_s = process_age_s()
            self.window = (t, t + self.seconds)
            if self.tracer is not None:
                self.tracer.start()
        elif k >= self.checked:
            if self.tracer is not None and k - self.checked + 1 >= self.trace_steps:
                self.tracer.stop()
            if t >= self.window[1] and k >= self.closes_after:
                raise WindowClosed
        return out


def _timed(probe: TrainProbe) -> list[dict]:
    """One record per step of the window: its wall time and the time of
    its shard gradient calls."""
    out = []
    for k in range(probe.checked, len(probe.step_ends)):
        wall = probe.step_ends[k] - probe.step_ends[k - 1]
        grad = sum(s["t1"] - s["t0"] for s in probe.shards if s["step"] == k)
        out.append({"k": k, "end": probe.step_ends[k], "wall": wall,
                    "grad_calls_s": grad,
                    "shards": [s["rows"] for s in probe.shards
                               if s["step"] == k and s["ok"]],
                    "hosts": sorted({s["host"] for s in probe.shards
                                     if s["step"] == k})})
    return out


def rel_leaf_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """Worst leaf's |prog - ref| over the larger of its reference norm and
    the median leaf's."""
    med = statistics.median(ref.values())
    worst, name = 0.0, ""
    for k, r in ref.items():
        g = abs(prog[k] - r) / max(r, med)
        if g > worst:
            worst, name = g, k
    return worst, name


def reference_readings(config: dict, traffic: dict, seed: int, *,
                       mode: str = "f32", rows: int | None = None) -> dict:
    """The reference's first steps from the seed: loss before each step,
    the clipped first gradient's leaf norms, and each leaf's change."""
    m, opt = config["model"], config["optimizer"]
    n = rows or traffic["global_batch"]
    batches = []
    for step in range(traffic["checked_steps"]):
        b = synthetic_batch(m["vocab_size"], traffic["global_batch"],
                            traffic["seq_len"], step, seed=seed)
        batches.append({k: jnp.asarray(v[:n]) for k, v in b.items()})
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights.make_weights(m, seed))
    losses, first_grad, w = reference.adamw_steps(w, batches, m, opt, mode=mode)
    change = reference.change_norms(w, weights.make_weights(m, seed))
    return {"losses": losses, "first_grad": first_grad, "change": change}


def compare(prog: dict, ref: dict) -> dict:
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    grad_gap, grad_leaf = rel_leaf_gap(prog["first_grad"], ref["first_grad"])
    change_gap, change_leaf = rel_leaf_gap(prog["change"], ref["change"])
    return {"loss_rel_gap": loss_gap, "first_grad_leaf_gap": grad_gap,
            "change_leaf_gap": change_gap, "first_grad_worst": grad_leaf,
            "change_worst": change_leaf}


def run_setup(config: dict, traffic: dict, seed: int, *, seconds: float,
              spans: Spans, tracer=None, trace_steps: int = 0):
    """Build the supervisor, warm it, and drive its one ``run`` call through
    the first steps and the window.  Returns the probe and supervisor."""
    m = config["model"]
    cfg = program_config(config)
    p0 = weights.to_program(weights.make_weights(m, seed), m, cfg)
    weights.check_layout(p0, abstract(param_defs(cfg)))
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        sup = build_supervisor(config, traffic, seed, ckpt_dir)
        checked = traffic["checked_steps"]
        events = [TrainEvent(step=checked + ev["timed_step"], kind=ev["kind"],
                             host=ev.get("host")) for ev in traffic["events"]]
        hosts = traffic["hosts"]
        gb = traffic["global_batch"]
        sizes = sorted({len(a) for n in (hosts, hosts - 1)
                        for a in np.array_split(np.arange(gb), n)})
        # the window runs on to the step after the last host loss, so it
        # holds the loss and the recovery however slow the steps are
        last = max((e.step for e in events), default=checked - 1)
        to_bench = functools.partial(weights.flat_from_program, m=m, cfg=cfg)
        with TrainProbe(sup, to_bench, p0, checked=checked, seconds=seconds,
                        spans=spans, tracer=tracer, trace_steps=trace_steps,
                        closes_after=last + 1) as probe:
            probe.warm(p0, sizes)
            try:
                sup.run(10 ** 9, events=events, start_params=p0)
            except WindowClosed:
                pass
        del p0
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return probe, sup


def run_cell(cell, *, seed: int, seconds: float, devices: list, clock,
             tracer, peaks: dict) -> dict:
    config, traffic = cell.config, cell.traffic
    m = config["model"]
    spans = Spans(annotate=tracer.enabled)
    probe, sup = run_setup(config, traffic, seed, seconds=seconds, spans=spans,
                           tracer=tracer, trace_steps=8)
    tracer.stop()
    t_done = time.perf_counter()
    device = device_info(devices)
    trace = tracer.reduce()
    # the window ends with the step that crossed --seconds: every step of
    # it counts, over all of its time
    t0 = probe.window[0]
    t_end = probe.step_ends[-1]
    steps = _timed(probe)
    done = steps
    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    tps = len(done) * tokens_per_step / (t_end - t0)
    loss_step = [ev["timed_step"] for ev in traffic["events"]
                 if ev["kind"] == "host_down"]
    # a traced run stops its trace between two steps of the window
    run = {"plane": "train", "model": m, "peaks": peaks, "window": (t0, t_end),
           "steps": steps,
           "tokens_per_s": len(done) * tokens_per_step / (t_end - t0 - tracer.stop_s),
           "flops_per_token": cost.train_flops_per_token(m, traffic["seq_len"]),
           "loss_steps": loss_step,
           "compile_setup_s": clock.seconds_before(t0), "trace": trace}
    failed_hosts = [(s["step"], s["host"]) for s in probe.shards if not s["ok"]]
    want_failed = [(traffic["checked_steps"] + ev["timed_step"], ev["host"])
                   for ev in traffic["events"] if ev["kind"] == "host_down"]
    last_loss = max(want_failed)[0] if want_failed else -1
    recovered = (failed_hosts == want_failed
                 and not {h for _, h in want_failed}
                 & {n.name for n in sup.healthy_hosts()}
                 and any(s["k"] > last_loss for s in done))

    gc.collect()
    prog = {"losses": [probe.step_loss(k) for k in range(traffic["checked_steps"])],
            "first_grad": probe.first_grad, "change": probe.change}
    ref = reference_readings(config, traffic, seed)
    got = compare(prog, ref)
    lim = config["limits"]
    compiles = clock.count_between(t0, t_done)
    checks = {
        name: check(got[name], lim[name], got[name] <= lim[name])
        for name in ("loss_rel_gap", "first_grad_leaf_gap", "change_leaf_gap")}
    checks["host_loss_recovered"] = check(int(recovered), 1, recovered)
    checks["window_compiles"] = check(compiles, 0, compiles == 0)
    run["readings"] = {"program": prog, "reference": ref, "worst": got}
    e2e = {"train_tokens_per_s": tps, "setup_s": probe.setup_s}
    return {"end_to_end": e2e, "run": run, "checks": checks,
            "attempted": len(done), "failed": 0, "device": device}
