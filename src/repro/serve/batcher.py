"""Continuous (dynamic) batching: slot-structured decode state per replica.

The saxml servable-model idiom: each replica runs ONE padded decode
program at a fixed batch size (``max_batch``).  The program's cost is set
by the padding, not the occupancy, so the throughput lever is *slot
utilization*: a finished request vacates its slot at the step boundary
and the next queued request moves in immediately — no waiting for the
rest of the batch, no head-of-line blocking behind the longest request.

Two decode backends share the slot protocol:

* :class:`JaxDecodeBackend` — the real model: one device-resident KV
  cache per replica sized ``(max_batch, max_len)``, each replica on its
  own chip where the host has several, one jitted
  ``decode_step`` program reused every step, the cache donated to it and
  written in place (ring-buffer cache, so the program never recompiles
  as requests come and go).  A request joining
  mid-flight is teacher-forced through its prompt (plus any tokens
  recovered from a lost replica) inside the shared program — the
  reproduction-scale stand-in for a prefill/generate split.
* :class:`SimDecodeBackend` — the deterministic stand-in for the
  simulation plane: tokens are a pure function of (rid, position), and
  the step *cost* is a modeled virtual duration (scaled by replica
  speed), so sustained-load and chaos scenarios run byte-identically
  under :class:`~repro.sim.VirtualClock` at microsecond wall cost.
"""
from __future__ import annotations

from typing import Any

from repro.core.failures import HardwareShutdownError, WorkerLostError
from repro.core.monitoring import SpanLog
from repro.serve.queue import ServeRequest


class ReplicaSlots:
    """Slot occupancy of one replica's in-flight continuous batch."""

    def __init__(self, max_batch: int):
        self.max_batch = max_batch
        self.slots: list[ServeRequest | None] = [None] * max_batch

    def occupants(self) -> list[ServeRequest]:
        return [r for r in self.slots if r is not None]

    def free_count(self) -> int:
        return sum(1 for r in self.slots if r is None)

    def admit(self, req: ServeRequest) -> int:
        """Seat ``req`` in the first free slot; returns the slot index."""
        for i, r in enumerate(self.slots):
            if r is None:
                # (re)start the token feed: teacher-force the prompt plus
                # everything already generated (failover recovery replays
                # recovered tokens, so no generated token is ever lost)
                req.feed = list(req.prompt) + list(req.generated)
                req.pos = 0
                req.status = "running"
                self.slots[i] = req
                return i
        raise RuntimeError("no free slot")  # pragma: no cover - guarded

    def vacate(self, i: int) -> None:
        self.slots[i] = None

    def evict_all(self) -> list[ServeRequest]:
        """Clear every slot (replica loss); returns the evicted requests."""
        out = self.occupants()
        self.slots = [None] * self.max_batch
        return out


def advance_slots(slots: ReplicaSlots, next_tokens: list[int]) -> list[ServeRequest]:
    """Apply one decode step's outputs to every occupied slot.

    ``next_tokens[i]`` is the model's prediction after consuming slot
    ``i``'s current feed token.  While the feed still has tokens ahead
    (teacher-forced prefill/replay) the prediction is discarded; once the
    feed is exhausted the prediction is the next generated token and is
    appended to both ``generated`` and the feed (it is the next step's
    input).  Returns the requests that finished this step.
    """
    finished: list[ServeRequest] = []
    for i, req in enumerate(slots.slots):
        if req is None:
            continue
        tok = next_tokens[i]
        req.pos += 1
        if req.pos >= len(req.feed) and not req.done:
            req.generated.append(int(tok))
            req.feed.append(int(tok))
        if req.done:
            finished.append(req)
            slots.vacate(i)
    return finished


def decode_program(cfg: Any):
    """The jitted decode step every replica runs: ONE program for every
    replica and every occupancy, its shapes pinned to (max_batch, 1) so
    slot churn never recompiles.  The decode state (argument 1) is
    donated: the step writes its one new position per layer into the
    caller's buffers, and the caller's handle is deleted."""
    import jax

    from repro.models import decode_step

    return jax.jit(lambda p, c, b: decode_step(p, c, b, cfg),
                   donate_argnums=(1,))


class DecodeBackend:
    """Decode executor protocol shared by the real and simulated planes.

    The serving driver sets ``span_log`` to its monitor's
    :class:`~repro.core.monitoring.SpanLog`, which times the steps of a
    backend that records spans, and ``monitor`` to the monitor itself,
    which takes its gauges.
    """

    name = "base"
    monitor: Any = None

    def start_replica(self, replica: Any) -> None:
        """Allocate per-replica decode state (KV cache)."""

    def drop_replica(self, name: str) -> None:
        """Release a (lost or scaled-down) replica's decode state."""

    def step(self, replica: Any, inputs: list[int | None]) -> list[int]:
        """One decode step: per-slot input token (None = free slot) →
        per-slot next token.  Raises
        :class:`~repro.core.failures.HardwareShutdownError` if the
        replica's hardware is down, and
        :class:`~repro.core.failures.WorkerLostError` if its decode state
        is gone."""
        raise NotImplementedError

    def step_cost_s(self, replica: Any) -> float | None:
        """Modeled step duration (virtual clocks); ``None`` = measure
        wall time (real clocks)."""
        return None


class JaxDecodeBackend(DecodeBackend):
    """Real decode: one padded program + one resident cache per replica.

    The i-th replica started lives on ``devices[i % len(devices)]``
    (default ``jax.devices()``): its cache is allocated there and the
    params are copied to that device when its first replica starts, so a
    lost chip takes only its own replicas with it.  On one chip every
    replica shares the one copy of the params.
    """

    name = "jax"

    def __init__(self, cfg: Any, *, max_batch: int, seed: int = 0,
                 max_len: int = 64, devices: list | None = None):
        import jax

        from repro.models import materialize, param_defs

        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.devices = list(devices) if devices is not None else jax.devices()
        self.params = jax.device_put(
            materialize(param_defs(cfg), jax.random.PRNGKey(seed)),
            self.devices[0])
        self._params = {self.devices[0]: self.params}
        self._decode = decode_program(cfg)
        self._caches: dict[str, Any] = {}
        # disabled until a serving driver binds its monitor's log
        self.span_log = SpanLog()
        # a restored replica keeps the device it was first given
        self._placement: dict[str, Any] = {}

    def start_replica(self, replica: Any) -> None:
        import jax

        from repro.models import cache_defs, decode_state_bytes, materialize

        dev = self._placement.setdefault(
            replica.name,
            self.devices[len(self._placement) % len(self.devices)])
        if dev not in self._params:
            self._params[dev] = jax.device_put(self.params, dev)
        cache = self._caches[replica.name] = jax.device_put(materialize(
            cache_defs(self.cfg, self.max_batch, self.max_len),
            jax.random.PRNGKey(0)), dev)
        if self.monitor is not None:
            for label, n in decode_state_bytes(cache).items():
                self.monitor.record_gauge("serve.decode_state_bytes", n,
                                          label=label)

    def drop_replica(self, name: str) -> None:
        self._caches.pop(name, None)

    def cache_devices(self) -> dict[str, Any]:
        """Live replica name -> the device that holds its decode cache."""
        import jax

        return {name: next(iter(jax.tree.leaves(cache)[0].devices()))
                for name, cache in self._caches.items()}

    def step(self, replica: Any, inputs: list[int | None]) -> list[int]:
        import jax
        import jax.numpy as jnp
        import numpy as np

        if not replica.healthy:
            raise HardwareShutdownError(
                f"replica {replica.name} is down", node=replica.name)
        cache = self._caches.get(replica.name)
        if cache is None:  # pragma: no cover - start_replica guards this
            raise HardwareShutdownError(
                f"replica {replica.name} has no decode state",
                node=replica.name)
        if any(leaf.is_deleted() for leaf in jax.tree.leaves(cache)):
            # the state was donated to an earlier step (or freed) and is
            # gone: the replica's decode worker is lost, its chip is not
            raise WorkerLostError(
                f"replica {replica.name} lost its decode state",
                node=replica.name)
        spans = self.span_log
        with spans.span("serve.step", replica=replica.name):
            with spans.span("serve.step.pack"):
                toks = np.zeros((self.max_batch, 1), np.int32)
                for i, tok in enumerate(inputs):
                    if tok is not None:
                        toks[i, 0] = tok
            dev = self._placement[replica.name]
            with spans.span("serve.step.put"):
                batch = {"inputs": jax.device_put(toks, dev)}
            with spans.span("serve.step.launch"):
                logits, cache = self._decode(self._params[dev], cache, batch)
            self._caches[replica.name] = cache
            with spans.span("serve.step.sample"):
                nxt = jnp.argmax(logits[:, -1], axis=-1)
            # the host waits here for the device to run the step
            with spans.span("serve.step.wait"):
                nxt = np.asarray(nxt)
            with spans.span("serve.step.unpack"):
                return [int(nxt[i]) for i in range(self.max_batch)]


class SimDecodeBackend(DecodeBackend):
    """Deterministic simulated decode for ``repro.sim`` serving scenarios.

    The next token is a pure function of the input token and the slot's
    request id, so same-seed scenarios produce byte-identical token
    streams; the modeled step cost is ``step_s`` scaled down by replica
    speed (a 0.25× replica decodes 4× slower), feeding the monitoring
    profile exactly like a measured duration would.
    """

    name = "sim"

    def __init__(self, *, step_s: float = 0.02, vocab_size: int = 256):
        self.step_s = step_s
        self.vocab_size = vocab_size
        self._started: set[str] = set()

    def start_replica(self, replica: Any) -> None:
        self._started.add(replica.name)

    def drop_replica(self, name: str) -> None:
        self._started.discard(name)

    def step(self, replica: Any, inputs: list[int | None]) -> list[int]:
        if not replica.healthy:
            raise HardwareShutdownError(
                f"replica {replica.name} is down", node=replica.name)
        return [((tok * 1009 + 101) % self.vocab_size) if tok is not None
                else 0 for tok in inputs]

    def step_cost_s(self, replica: Any) -> float:
        return self.step_s / max(getattr(replica, "speed", 1.0), 1e-6)
