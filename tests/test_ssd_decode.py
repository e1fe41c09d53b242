"""The Mamba-2 decode update of the occupied slots only.

``kernels.ssd_decode`` runs in Pallas interpret mode here, against the
XLA update that the CPU runs (``ssm.state_update_xla``): the live slots'
state and read-out agree to bfloat16 rounding, a free slot's state is
left bit for bit and its read-out is zero, and no other layer is touched.
Through the serving backend, a batch whose requests finish at different
steps serves the tokens that the dense update (every slot updated)
serves, and the step spans count the slots the step updated.
"""
import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.engine.cluster import Node
from repro.kernels.ssd_decode import pack_aux, ssd_decode_update
from repro.models import ssm
from repro.serve import JaxDecodeBackend

ROOT = Path(__file__).resolve().parents[1]
LAYERS, SLOTS, P, N = 3, 8, 16, 32
#: live slot sets of the batch of 8
LIVE = {"none": [], "one": [3], "all": list(range(SLOTS)),
        "scattered": [0, 2, 5, 6], "last": [SLOTS - 1]}


def _operands(heads: int, seed: int = 0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(k[0], (LAYERS, SLOTS, heads, P, N)).astype(
        jnp.bfloat16)
    x = jax.random.normal(k[1], (SLOTS, heads, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (SLOTS, heads)))
    a = -jnp.exp(0.5 * jax.random.normal(k[3], (heads,)))
    b = jax.random.normal(k[4], (SLOTS, 1, N)).astype(jnp.bfloat16)
    c = jax.random.normal(k[5], (SLOTS, 1, N)).astype(jnp.bfloat16)
    return state, x, dt, a, b, c


@pytest.mark.parametrize("heads", [48, 64])
@pytest.mark.parametrize("live", list(LIVE))
def test_the_kernel_updates_the_live_slots_as_xla_does(heads, live):
    state, x, dt, a, b, c = _operands(heads)
    mask = np.zeros(SLOTS, bool)
    mask[LIVE[live]] = True
    slots = ssm.Live.of(jnp.asarray(mask))
    layer = jnp.int32(1)
    y_k, s_k = ssm.state_update_kernel(state, layer, slots, x, dt, a, b, c,
                                       interpret=True)
    y_x, s_x = ssm.state_update_xla(state, layer, slots, x, dt, a, b, c)
    y_k, y_x = np.asarray(y_k, np.float32), np.asarray(y_x, np.float32)
    s_k, s_x, s_0 = (np.asarray(s, np.float32) for s in (s_k, s_x, state))
    # every other layer, bit for bit
    for other in (0, 2):
        np.testing.assert_array_equal(s_k[other], s_0[other])
    # a free slot's state as it was, its read-out zero
    np.testing.assert_array_equal(s_k[1][~mask], s_0[1][~mask])
    assert not y_k[~mask].any()
    # a live slot's, as the XLA update's to bfloat16 rounding: XLA rounds
    # each product to bfloat16, the kernel once at the end
    scale = np.abs(s_x[1][mask]).max(initial=1.0)
    np.testing.assert_allclose(s_k[1][mask], s_x[1][mask], rtol=0,
                               atol=4 * 2**-8 * scale)
    scale = np.abs(y_x[mask]).max(initial=1.0)
    np.testing.assert_allclose(y_k[mask], y_x[mask], rtol=0,
                               atol=4 * 2**-8 * scale)
    if live != "none":
        assert not np.array_equal(s_k[1][mask], s_0[1][mask])


def test_the_kernel_reads_its_operands_from_the_packed_block():
    # pack_aux and the kernel against a float32 update by hand, so that
    # neither path above is its own reference
    heads = 4
    state, x, dt, a, b, c = _operands(heads, seed=1)
    decay = jnp.exp(dt * a[None])
    ids = jnp.asarray([6, 1] + [0] * (SLOTS - 2), jnp.int32)
    y, new = ssd_decode_update(state, jnp.int32(2), ids, jnp.int32(2),
                               pack_aux(x, dt, decay, b[:, 0], c[:, 0]),
                               interpret=True)
    f32 = lambda v: np.asarray(v, np.float32)  # noqa: E731
    for slot in (6, 1):
        want = (f32(state)[2, slot] * f32(decay)[slot][:, None, None]
                + (f32(x)[slot] * f32(dt)[slot][:, None])[..., None]
                * f32(b)[slot, 0])
        got = f32(new)[2, slot]
        np.testing.assert_allclose(got, want, rtol=2**-7, atol=2**-7)
        read = got @ f32(c)[slot, 0]
        np.testing.assert_allclose(f32(y)[slot].reshape(heads, P), read,
                                   rtol=2**-7, atol=2**-6 * np.abs(read).max())


# ---------------------------------------------------------------------------
# through the serving backend
# ---------------------------------------------------------------------------


def _bench_on_path():
    for path in (ROOT, ROOT / "tests" / "bench"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))


def _hybrid_tiny():
    _bench_on_path()
    from bench.harness import load_json
    from bench.program import program_config

    return program_config(load_json(ROOT / "tests/bench/data/hybrid-tiny.json"))


STACKS = {"hybrid-tiny": _hybrid_tiny,
          "mamba2": lambda: get_smoke_config("mamba2_780m")}
#: (prompt, new tokens) of four requests seated together; they take 4, 11,
#: 7 and 14 steps
REQUESTS = [(3, 2), (4, 8), (2, 6), (5, 10)]


def _fp32(tree):
    return jax.tree.map(
        lambda v: v.astype(jnp.float32)
        if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)


def _serve(cfg, *, dense: bool) -> list[list[int]]:
    """Greedy tokens of REQUESTS, seated at step 0 of one batch; ``dense``
    feeds a free slot token 0, so that every slot's state is updated."""
    backend = JaxDecodeBackend(cfg, max_batch=len(REQUESTS), max_len=32)
    node = Node("replica0", workers_per_node=1)
    backend.start_replica(node)
    # float32 weights and state, so that the kernel (float32 inside) and
    # the XLA update agree to rounding and greedy tokens compare one for one
    backend.params = _fp32(backend.params)
    backend._params = {backend.devices[0]: backend.params}
    backend._caches[node.name] = _fp32(backend._caches[node.name])
    rng = np.random.default_rng(3)
    feed = [[int(t) for t in rng.integers(0, cfg.vocab_size, p)]
            for p, _ in REQUESTS]
    out: list[list[int]] = [[] for _ in REQUESTS]
    done: dict[int, list[np.ndarray]] = {}
    for t in range(max(p + n for p, n in REQUESTS) - 1):
        inputs = [f[t] if t < len(f) and len(o) < n else None
                  for f, o, (_, n) in zip(feed, out, REQUESTS)]
        nxt = backend.step(node, [0 if dense and tok is None else tok
                                  for tok in inputs])
        for i, (p, n) in enumerate(REQUESTS):
            if inputs[i] is not None and t >= p - 1 and len(out[i]) < n:
                out[i].append(nxt[i])
                if len(feed[i]) == t + 1:
                    feed[i].append(nxt[i])
                if len(out[i]) == n:
                    done[i] = _ssd_slot(backend._caches[node.name], i)
    assert [len(o) for o in out] == [n for _, n in REQUESTS]
    if not dense:
        # a finished request's slot is free from its next step on: its
        # state and window stay as its last step left them
        for i, held in done.items():
            for a, b in zip(held, _ssd_slot(backend._caches[node.name], i)):
                np.testing.assert_array_equal(a, b)
    return out


def _ssd_slot(cache, slot: int) -> list[np.ndarray]:
    return [np.asarray(leaf[:, slot])
            for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
            if "ssd" in [getattr(k, "key", None) for k in path]]


@pytest.mark.parametrize("stack", list(STACKS))
@pytest.mark.parametrize("update", ["xla", "kernel"])
def test_a_staggered_batch_serves_the_dense_updates_tokens(monkeypatch, stack,
                                                            update):
    cfg = dataclasses.replace(STACKS[stack](), compute_dtype="float32")
    assert "ssd" in {m for m, _ in cfg.block_kinds()}
    dense = _serve(cfg, dense=True)
    if update == "kernel":
        # the CPU lowers the XLA update; put the kernel, interpreted, there
        monkeypatch.setattr(ssm, "state_update_xla", functools.partial(
            ssm.state_update_kernel, interpret=True))
    assert _serve(cfg, dense=False) == dense


@pytest.mark.parametrize("stack", ["hybrid-tiny", "mamba2-780m"])
def test_the_step_spans_count_the_occupied_slot_steps(stack):
    _bench_on_path()
    from bench import serve_phase as sp
    from bench.harness import Spans
    from bench.traffic_gen import closed_batch
    from bench_smoke import DATA, smoke_cell

    cell = smoke_cell(DATA / "hybrid-tiny.json" if stack == "hybrid-tiny"
                      else stack, "serve-gen")
    rig = sp.build(cell.config, cell.traffic, 2**31 + 5, Spans())
    log = rig.driver.monitor.spans
    log.enable()
    reqs = closed_batch(cell.traffic, seed=5, index=0, vocab=256)
    assert len({len(r.prompt) + r.max_new_tokens for r in reqs}) > 1
    b = sp.run_batch(rig, reqs)
    occupied = [r.occupied for r in rig.backend.records[b.first_step:b.end_step]]
    live = [s["live"] for s in log.export() if s["name"] == "serve.step"]
    assert live == occupied
    assert min(live) < max(live) == len(reqs)
    sp.release_program_state(rig)
