"""The serving decode step updates its state in place.

``JaxDecodeBackend`` donates the decode state to each step, so the step
writes its one new position into the caller's buffers and the caller's
old handle is deleted.  These tests run the backend at smoke size on the
CPU, for every mixer's kind of state, and drive the serving plane with a
state that is gone.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.engine.cluster import Node
from repro.models import forward_train
from repro.models.model import _logits
from repro.serve import JaxDecodeBackend, ServeRequest, WrathServeDriver

#: a smoke configuration whose layers hold each kind of decode state
MIXERS = {"attn": "granite_3_2b", "swa": "gemma3_27b",
          "mla": "deepseek_v3_671b", "ssd": "mamba2_780m",
          "rglru": "recurrentgemma_9b"}
STEPS = 20


def _fp32(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


@pytest.mark.parametrize("mixer", list(MIXERS))
def test_a_step_donates_the_state_and_serves_greedy_tokens(mixer):
    cfg = dataclasses.replace(get_smoke_config(MIXERS[mixer]),
                              compute_dtype="float32")
    assert mixer in {m for m, _ in cfg.block_kinds()}
    backend = JaxDecodeBackend(cfg, max_batch=4, max_len=32)
    node = Node("replica0", workers_per_node=1)
    backend.start_replica(node)
    # weights and state in float32, so that the step and the full forward
    # agree to rounding and the greedy tokens can be compared one for one
    backend.params = _fp32(backend.params)
    backend._params = {backend.devices[0]: backend.params}
    backend._caches[node.name] = _fp32(backend._caches[node.name])
    rng = np.random.default_rng(7)
    # slots 1 and 3 stay free; 0 and 2 are fed their prompts, then their
    # own greedy tokens
    seqs = {0: [int(t) for t in rng.integers(0, cfg.vocab_size, 3)],
            2: [int(t) for t in rng.integers(0, cfg.vocab_size, 5)]}
    prompt = {i: len(s) for i, s in seqs.items()}
    for t in range(STEPS):
        before = backend._caches[node.name]
        out = backend.step(node, [seqs[i][t] if i in seqs else None
                                  for i in range(4)])
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
        for i, seq in seqs.items():
            if len(seq) == t + 1:
                seq.append(out[i])
    tokens = jnp.asarray([seqs[i][:STEPS] for i in sorted(seqs)], jnp.int32)
    h, _, _ = forward_train(backend.params, {"inputs": tokens}, cfg,
                            remat=False)
    greedy = np.asarray(jnp.argmax(_logits(backend.params, h, cfg), -1))
    for row, i in enumerate(sorted(seqs)):
        p = prompt[i]
        assert seqs[i][p:] == greedy[row, p - 1:].tolist(), mixer


def _plane(cfg, backend):
    return WrathServeDriver(cfg, n_replicas=1, max_batch=4, decode=backend)


def _requests(cfg, first_rid, n=3, new=4):
    rng = np.random.default_rng(first_rid)
    return [ServeRequest(rid=first_rid + i,
                         prompt=[int(t) for t in
                                 rng.integers(0, cfg.vocab_size, 3)],
                         max_new_tokens=new) for i in range(n)]


@pytest.mark.parametrize("every_step", [False, True], ids=["once", "always"])
def test_a_replica_whose_state_is_gone_is_restarted_and_the_plane_settles(
        every_step):
    # a step on a deleted state (a handle kept past its donation) loses the
    # replica's decode worker, not its chip: the plane evicts the requests
    # it held, starts the replica afresh and goes on serving, where it
    # once waited out its horizon with the requests still seated
    cfg = get_smoke_config("granite_3_2b")
    backend = JaxDecodeBackend(cfg, max_batch=4, max_len=32)
    drv = _plane(cfg, backend)
    name = drv.live_replicas()[0].name
    step = backend.step
    calls = []

    def step_on_a_deleted_state(replica, inputs):
        if len(calls) == 2 or (every_step and len(calls) > 2):
            for leaf in jax.tree.leaves(backend._caches[replica.name]):
                leaf.delete()
        calls.append(replica.name)
        return step(replica, inputs)

    backend.step = step_on_a_deleted_state
    reqs = _requests(cfg, 0)
    t0 = time.monotonic()
    drv.serve_continuous(reqs, horizon=600.0)
    assert time.monotonic() - t0 < 30.0
    assert all(r.terminal for r in reqs)
    events = [e["event"] for e in drv.monitor.system_events]
    assert events.count("replica_lost") >= 1
    assert events.count("replica_restarted") == events.count("replica_lost")
    # the restarted replica serves the next requests on its fresh state
    assert [n.name for n in drv.live_replicas()] == [name]
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(backend._caches[name]))
    later = _requests(cfg, 10)
    drv.serve_continuous(later, horizon=600.0)
    drv.shutdown()
    if every_step:
        assert all(r.status == "failed" for r in later)
    else:
        assert all(r.status == "done" and len(r.generated) == 4
                   for r in later)
