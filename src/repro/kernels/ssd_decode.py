"""Mamba-2 SSD decode update of the occupied slots, in place (Pallas TPU).

One decode step of one layer, for the slots that hold a request: each
live slot's ``(H, P, N)`` state block is read from the stacked state once,
decayed per head, given ``dt·x ⊗ B``, written back once, and read out as
``y = state · C``.  The stacked ``(L, B, H, P, N)`` state is aliased to
the output and stays in HBM; a loop over the live slots, as many trips
as there are live slots, moves their blocks through two VMEM buffers each
way, the next slot's read in flight while this one is computed.  A free
slot costs no DMA and no trip: its state is left as it was and its ``y``
is zero.  The arithmetic is in float32; the state keeps its dtype.

Each slot's small operands travel in one float32 block of ``P + 8`` rows
(``aux``): ``x`` transposed, ``(P, H)``, so a head's values are a column
that broadcasts across the state's lanes, then rows for ``dt``, the
decay ``exp(dt·A)``, ``B`` and ``C``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of ``aux`` after the ``P`` rows of ``x``
_DT, _DECAY, _B, _C = range(4)


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def pack_aux(x: jax.Array, dt: jax.Array, decay: jax.Array, b: jax.Array,
             c: jax.Array) -> jax.Array:
    """x (B, H, P), dt and decay (B, H), b and c (B, N) -> aux
    (B, P + 8, W) float32, W the lanes that hold max(H, N)."""
    bsz, h, p = x.shape
    n = b.shape[-1]
    w = _lanes(max(h, n))
    f32 = jnp.float32

    def row(v):
        return jnp.pad(v.astype(f32), ((0, 0), (0, w - v.shape[-1])))[:, None]

    xt = jnp.pad(x.astype(f32).transpose(0, 2, 1), ((0, 0), (0, 0), (0, w - h)))
    rows = [row(dt), row(decay), row(b), row(c)]
    rows.append(jnp.zeros((bsz, 8 - len(rows), w), f32))
    return jnp.concatenate([xt] + rows, axis=1)


def _kernel(layer_ref, ids_ref, n_ref, aux_hbm, state_hbm, y_ref, state_out,
            sin, sout, ain, dsc, sem_in, sem_aux, sem_out, *, h: int, p: int,
            n_state: int):
    layer = layer_ref[0]
    live = n_ref[0]

    def fetch(k, buf):
        slot = ids_ref[k]
        return (pltpu.make_async_copy(state_hbm.at[layer, slot], sin.at[buf],
                                      sem_in.at[buf]),
                pltpu.make_async_copy(aux_hbm.at[slot], ain.at[buf],
                                      sem_aux.at[buf]))

    def store(k, buf):
        return pltpu.make_async_copy(sout.at[buf],
                                     state_out.at[layer, ids_ref[k]],
                                     sem_out.at[buf])

    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live > 0)
    def _():
        for dma in fetch(0, 0):
            dma.start()

    def body(k, carry):
        buf = k % 2

        @pl.when(k + 1 < live)
        def _():
            for dma in fetch(k + 1, 1 - buf):
                dma.start()

        for dma in fetch(k, buf):
            dma.wait()

        @pl.when(k >= 2)
        def _():
            store(k - 2, buf).wait()

        aux = ain[buf]                                   # (P + 8, W)
        xdt = aux[:p] * aux[p + _DT:p + _DT + 1]          # (P, W)
        # the decay spread over P rows in VMEM, so that a head's is a
        # column like its x's (a value broadcast from one element in both
        # directions does not lower)
        dsc[...] = jnp.broadcast_to(aux[p + _DECAY:p + _DECAY + 1], xdt.shape)
        b = jnp.broadcast_to(aux[p + _B:p + _B + 1, :n_state], (p, n_state))
        for hi in range(h):
            s = sin[buf, hi].astype(jnp.float32)         # (P, N)
            s = s * dsc[:, hi:hi + 1] + xdt[:, hi:hi + 1] * b
            sout[buf, hi] = s.astype(sout.dtype)
        store(k, buf).start()
        # C as 8 equal rows, the fewest the MXU takes; row 0 is y
        c = jnp.broadcast_to(aux[p + _C:p + _C + 1, :n_state],
                             (8, n_state)).astype(sout.dtype)
        y = jax.lax.dot_general(
            c, sout[buf].reshape(h * p, n_state), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (8, H * P)
        y_ref[pl.ds(ids_ref[k], 1), :] = y[:1]
        return carry

    jax.lax.fori_loop(0, live, body, 0)

    @pl.when(live >= 2)
    def _():
        store(live - 2, live % 2).wait()

    @pl.when(live >= 1)
    def _():
        store(live - 1, (live - 1) % 2).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_update(state: jax.Array, layer: jax.Array, ids: jax.Array,
                      count: jax.Array, aux: jax.Array, *,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """One layer's decode update of the live slots, in place.

    state: (L, B, H, P, N), aliased to the returned state; layer: () int32;
    ids: (B,) int32, the live slots first; count: () int32, how many of
    ``ids`` are live; aux: (B, P + 8, W) float32 from :func:`pack_aux`.
    Returns (y (B, H * P) float32, zero on free slots; state)."""
    _, bsz, h, p, n = state.shape
    kernel = functools.partial(_kernel, h=h, p=p, n_state=n)
    block = (h, p, n)
    vmem = (4 * h * p * n * state.dtype.itemsize + 2 * aux[0].size * 4
            + bsz * h * p * 4)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[
                pltpu.VMEM((2,) + block, state.dtype),
                pltpu.VMEM((2,) + block, state.dtype),
                pltpu.VMEM((2,) + aux.shape[1:], jnp.float32),
                pltpu.VMEM((p, aux.shape[2]), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h * p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem * 2 + (8 << 20))),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), ids.astype(jnp.int32),
      jnp.reshape(count, (1,)).astype(jnp.int32), aux, state)
