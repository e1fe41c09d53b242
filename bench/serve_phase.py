"""Serve cells: a closed-loop batch client in front of the program's
continuous serving plane.

The client sends one batch of as many requests as the replica has slots
to ``WrathServeDriver.serve_continuous``, waits for all of them, and sends
the next.  Each batch starts on a fresh decode state, through the plane's
own calls (``remove_replica`` then ``add_replica``), so every request is
seated at the batch's first step and its token j sits at cache position
j.  The reset is inside the window.

The window is made of whole batches: the client sends batches while
``--seconds`` have not passed since the window opened, and the batch
under way at that moment runs to its end, where the window closes.  Every
batch holds the same work, so the rate, all tokens over all of the
window's time, follows the step time and not where a cut falls.

Token times are the ends of the decode steps that produced them: token
k of a request with a prompt of P tokens comes from the batch's step
P - 1 + k.  The harness checks that each batch's steps hold exactly the
requests it expects, so that mapping is exact.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import abstract, param_defs
from repro.serve import JaxDecodeBackend, WrathServeDriver
from repro.serve import Request as ServeRequest

from bench import reference, weights
from bench.program import program_config
from bench.harness import BenchError, Spans, check, percentile
from bench.traffic_gen import Request, closed_batch, longest_sequence, steps_of

#: seconds after the client's call at which a batch's requests arrive, so
#: that every one of them is queued before the plane's first refill
ARRIVAL_S = 0.01
#: the plane gives up on a request after this long; no batch comes near it
HORIZON_S = 600.0
#: requests the reference replays after the window, the longest among them
SAMPLE_REQUESTS = 8


@dataclass
class StepRecord:
    t_call: float
    t_return: float
    occupied: int


@dataclass
class BatchRecord:
    send: float
    first_step: int
    end_step: int
    requests: list[Request]
    served: list                       # the plane's ServeRequest objects


class BenchDecodeBackend(JaxDecodeBackend):
    """The program's decode backend serving the benchmark's weights, with a
    host span around each decode step."""

    def __init__(self, cfg, params, *, spans: Spans, **kw):
        super().__init__(cfg, seed=0, **kw)
        weights.check_layout(params, abstract(param_defs(cfg)))
        # the program's own random weights make way for the benchmark's
        dev = self.devices[0]
        self.params = jax.device_put(params, dev)
        self._params = {dev: self.params}
        self.spans = spans
        self.records: list[StepRecord] = []

    def step(self, replica, inputs):
        t0 = time.perf_counter()
        with self.spans.span("serve.decode_step"):
            out = super().step(replica, inputs)
        self.records.append(StepRecord(
            t0, time.perf_counter(), sum(1 for t in inputs if t is not None)))
        return out


@dataclass
class ServeRig:
    cell_config: dict
    cfg: object
    model: dict
    seed: int
    backend: BenchDecodeBackend
    driver: WrathServeDriver
    spans: Spans
    batches: list[BatchRecord] = field(default_factory=list)


def build(config: dict, traffic: dict, seed: int, spans: Spans,
          devices: list | None = None) -> ServeRig:
    """Weights from the seed, the program's backend and plane over them."""
    m = config["model"]
    serve = config["serve"]
    if traffic.get("kind") != "closed_batches":
        raise BenchError(f"a serve cell sends closed_batches, not {traffic.get('kind')!r}")
    if int(traffic["batch"]) != serve["max_batch"] * serve["replicas"]:
        raise BenchError("a batch must fill every slot of the replicas")
    if longest_sequence(traffic) > serve["max_len"]:
        raise BenchError("the mix's longest request overruns max_len")
    cfg = program_config(config)
    with spans.span("setup.weights"):
        params = jax.block_until_ready(weights.program_weights(m, seed))
    with spans.span("setup.backend"):
        backend = BenchDecodeBackend(
            cfg, params, spans=spans, max_batch=serve["max_batch"],
            max_len=serve["max_len"], devices=devices)
    driver = WrathServeDriver(cfg, n_replicas=serve["replicas"],
                              max_batch=serve["max_batch"], decode=backend)
    return ServeRig(config, cfg, m, seed, backend, driver, spans)


def _fresh_state(rig: ServeRig) -> None:
    """Every replica retired and a new one started, on a fresh cache."""
    drv = rig.driver
    names = [n.name for n in drv.live_replicas()]
    for name in names:
        if not drv.remove_replica(name, reason="fresh batch"):
            raise BenchError(f"replica {name} is still busy")
    for _ in names:
        drv.add_replica(reason="fresh batch")


def run_batch(rig: ServeRig, reqs: list[Request]) -> BatchRecord:
    send = time.perf_counter()
    with rig.spans.span("serve.reset"):
        _fresh_state(rig)
    first = len(rig.backend.records)
    served = [ServeRequest(rid=r.rid, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens) for r in reqs]
    with rig.spans.span("serve.batch"):
        rig.driver.serve_continuous(served, arrivals=[ARRIVAL_S] * len(served),
                                    horizon=HORIZON_S)
    rec = BatchRecord(send, first, len(rig.backend.records), reqs, served)
    rig.batches.append(rec)
    return rec


def warm_up(rig: ServeRig, seed: int) -> None:
    """One short batch through the same plane: compiles the decode step
    and the host-side ops of its one shape, and the fresh-state reset."""
    n = rig.cell_config["serve"]["max_batch"] * rig.cell_config["serve"]["replicas"]
    rng = np.random.default_rng([int(seed), 1 << 40])
    vocab = rig.model["vocab_size"]
    with rig.spans.span("setup.warm_up"):
        run_batch(rig, [Request(rid=-1 - i,
                                prompt=tuple(int(t) for t in
                                             rng.integers(0, vocab, 2)),
                                max_new_tokens=2) for i in range(n)])
    rig.batches.clear()


def serve_window(rig: ServeRig, traffic: dict, *, seed: int, seconds: float,
                 on_first_batch=None) -> tuple[float, float]:
    """Send batches until ``seconds`` have passed; the batch under way then
    runs to its end.  Returns the window: its start, and the end of its
    last batch."""
    n = int(traffic["batch"])
    vocab = rig.model["vocab_size"]
    t0 = time.perf_counter()
    t_end = t0 + seconds
    index = 0
    while time.perf_counter() < t_end:
        run_batch(rig, closed_batch(traffic, seed=seed, index=index,
                                    vocab=vocab, first_rid=index * n))
        if index == 0 and on_first_batch is not None:
            on_first_batch()
        index += 1
    return t0, time.perf_counter()


# ---------------------------------------------------------------------------
# what the window measured
# ---------------------------------------------------------------------------


def batch_problems(rig: ServeRig, b: BatchRecord) -> list[str]:
    """What does not match a batch seated whole at its first step."""
    out = []
    recs = rig.backend.records[b.first_step:b.end_step]
    lens = [steps_of(r) for r in b.requests]
    if len(recs) != max(lens):
        out.append(f"batch took {len(recs)} steps, expected {max(lens)}")
    for j, rec in enumerate(recs):
        want = sum(1 for n in lens if n > j)
        if rec.occupied != want:
            out.append(f"step {j} held {rec.occupied} requests, expected {want}")
            break
    for r, s in zip(b.requests, b.served):
        if s.status == "done" and len(s.generated) != r.max_new_tokens:
            out.append(f"request {r.rid} served {len(s.generated)} tokens")
    return out


def token_times(rig: ServeRig, b: BatchRecord, r: Request) -> list[float]:
    recs = rig.backend.records
    p = len(r.prompt)
    return [recs[b.first_step + p - 1 + k].t_return
            for k in range(r.max_new_tokens)]


def end_to_end(rig: ServeRig, window: tuple[float, float]) -> dict:
    """Rate over the window, tails over every request and token gap; the
    window ends with its last batch, so every token counts."""
    t0, t_end = window
    tokens = 0
    ttft: list[float] = []
    gaps: list[float] = []
    for b in rig.batches:
        for r, s in zip(b.requests, b.served):
            if s.status != "done":
                ttft.append(math.inf)        # a failed request misses
                continue
            times = token_times(rig, b, r)
            ttft.append(times[0] - b.send)
            tokens += sum(1 for t in times if t <= t_end)
            gaps += [(b2 - a) * 1e3 for a, b2 in zip(times, times[1:])
                     if b2 <= t_end]
    return {"serve_tokens_per_s": tokens / (t_end - t0),
            "ttft_p95_s": percentile(ttft, 95),
            "tbt_p95_ms": percentile(gaps, 95) if gaps else math.inf}


def step_log(rig: ServeRig, window: tuple[float, float]) -> list[dict]:
    """One record per decode step that ended in the window, with its batch
    step (the cache position every seated request writes) and how many of
    its seats consumed a prompt token."""
    out = []
    recs = rig.backend.records
    for b in rig.batches:
        prompts = [len(r.prompt) for r in b.requests]
        prev_return = None
        for j in range(b.first_step, b.end_step):
            rec = recs[j]
            if rec.t_return > window[1]:
                break
            pos = j - b.first_step
            out.append({"t_call": rec.t_call, "t_return": rec.t_return,
                        "occupied": rec.occupied, "position": pos,
                        "prompt_slots": sum(1 for p in prompts if p > pos),
                        "gap_before": (None if prev_return is None
                                       else rec.t_call - prev_return)})
            prev_return = rec.t_return
    return out


# ---------------------------------------------------------------------------
# correctness: served tokens against the plain reference
# ---------------------------------------------------------------------------


def sample_requests(rig: ServeRig, seed: int) -> list[tuple[Request, list[int]]]:
    """The longest finished request and others drawn from the seed."""
    done = [(r, list(s.generated)) for b in rig.batches
            for r, s in zip(b.requests, b.served) if s.status == "done"]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i][1]))
    rng = np.random.default_rng([int(seed), 7])
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + [int(i) for i in rng.choice(
        rest, size=min(SAMPLE_REQUESTS - 1, len(rest)), replace=False)]
    return [done[i] for i in pick]


def _replay_arrays(sample, length: int):
    """Token rows (prompt + served tokens but the last), and per position
    the served token it predicted (-1 where none)."""
    toks = np.zeros((len(sample), length), np.int32)
    served = np.full((len(sample), length), -1, np.int32)
    for i, (r, gen) in enumerate(sample):
        seq = list(r.prompt) + gen[:-1]
        toks[i, :len(seq)] = seq
        p = len(r.prompt)
        served[i, p - 1:p - 1 + len(gen)] = gen
    return toks, served


def served_gap(model: dict, seed: int, sample, length: int) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served token of the sample.  The
    reference makes its own weights from the seed."""
    w = weights.make_weights(model, seed)
    toks, served = _replay_arrays(sample, length)
    read = np.maximum(served, 0)
    best, at, _ = reference.logits_summary(w, model, jnp.asarray(toks),
                                           jnp.asarray(read))
    mask = served >= 0
    gap = (np.asarray(best) - np.asarray(at))[mask]
    return {"served_logit_gap": float(gap.max()), "served_tokens": int(mask.sum())}


def control_gap(model: dict, seed: int, sample, length: int) -> dict:
    """The same reading for the reference computed in float8: the gap of
    the token that the lower precision puts first, at each served
    position."""
    w = weights.make_weights(model, seed)
    toks, served = _replay_arrays(sample, length)
    t = jnp.asarray(toks)
    _, _, first = reference.logits_summary(w, model, t, t, mode="fp8")
    best, at, _ = reference.logits_summary(w, model, t, first)
    mask = served >= 0
    gap = (np.asarray(best) - np.asarray(at))[mask]
    return {"served_logit_gap": float(gap.max()), "served_tokens": int(mask.sum())}


def gap_checks(gap: dict | None, limits: dict) -> dict:
    """The served-token gap beside its limit, and the tokens it covered
    beside theirs (a lower limit: the sample must hold some hundreds)."""
    limit, least = limits["served_logit_gap"], limits["served_tokens_compared"]
    return {
        "served_logit_gap": check(
            None if gap is None else gap["served_logit_gap"], limit,
            gap is not None and gap["served_logit_gap"] <= limit),
        "served_tokens_compared": check(
            0 if gap is None else gap["served_tokens"], least,
            gap is not None and gap["served_tokens"] >= least),
    }


def release_program_state(rig: ServeRig) -> None:
    """Drop the program's decode caches, weights and plane before the
    reference makes its own."""
    rig.driver.shutdown()
    for name in list(rig.backend._caches):
        rig.backend.drop_replica(name)
    rig.backend.params = None
    rig.backend._params.clear()
