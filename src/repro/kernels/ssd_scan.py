"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the SSD algorithm (DESIGN.md §6): grid = (batch, heads,
chunks) with the chunk axis sequential; the running state (P × N) lives in
VMEM scratch across chunk steps.  Per chunk (Q = chunk length, MXU-aligned
128 by default):

    da       = dt ⊙ A                     (Q,)
    L        = exp(segsum(da))            (Q, Q) lower-triangular decay
    y_diag   = ((C Bᵀ) ⊙ L) (x ⊙ dt)      intra-chunk, two MXU matmuls
    y_off    = exp(cumsum(da)) ⊙ (C · state)        carried-state term
    state    = exp(sum(da)) · state + (B ⊙ decay)ᵀ (x ⊙ dt)

All accumulation in fp32.  G=1 (single B/C group), the configuration used
by mamba2-780m.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_col_ref, dt_row_ref, a_ref, b_ref, c_ref, y_ref,
            state_out_ref, state_ref, *, q: int, nc: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                # (Q, P)
    a = a_ref[hi].astype(jnp.float32)                  # () from SMEM
    # dt arrives both as a column and as a row, so the inclusive prefix
    # sums of da are formed in either orientation by masked reductions
    # (no in-kernel transpose or cumsum)
    da_col = dt_col_ref[0, 0].astype(jnp.float32) * a  # (Q, 1)
    da_row = dt_row_ref[0, 0].astype(jnp.float32) * a  # (1, Q)
    b = b_ref[0].astype(jnp.float32)                   # (Q, N)
    c = c_ref[0].astype(jnp.float32)                   # (Q, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmask = row >= col
    da_cs = jnp.sum(jnp.where(lmask, jnp.broadcast_to(da_row, (q, q)), 0.0),
                    axis=1, keepdims=True)             # (Q, 1)
    da_cs_row = jnp.sum(
        jnp.where(row <= col, jnp.broadcast_to(da_col, (q, q)), 0.0),
        axis=0, keepdims=True)                         # (1, Q)
    # segsum: L[i, j] = exp(sum(da[j+1..i])) for i >= j
    l_decay = jnp.where(lmask, jnp.exp(da_cs - da_cs_row), 0.0)  # (Q, Q)

    xdt = x * dt_col_ref[0, 0].astype(jnp.float32)     # (Q, P)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    y = jax.lax.dot_general(cb * l_decay, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # carried-state contribution: exp(cumsum) ⊙ (C @ stateᵀ)
    state = state_ref[...]                             # (P, N)
    y += jnp.exp(da_cs) * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # (Q, P)

    # state update
    total = jnp.sum(da_col, axis=0, keepdims=True)     # (1, 1)
    decay_in = jnp.exp(total - da_cs)                  # (Q, 1)
    contrib = jax.lax.dot_general(
        xdt, b * decay_in, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (P, N)
    state_ref[...] = state * jnp.exp(total) + contrib

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        state_out_ref[0, 0] = state_ref[...].astype(state_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                    c: jax.Array, *, chunk: int = 128,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x: (B, L, H, P); dt: (B, L, H); a: (H,); b, c: (B, L, N).

    Returns (y (B, L, H, P), final_state (B, H, P, N)).

    L need not divide the chunk size: inputs are zero-padded up to the
    next chunk multiple.  Padded steps have dt = 0, so da = 0 — they decay
    the carried state by exp(0) = 1 and contribute x·dt = 0, i.e. they are
    exact identities on the recurrence; padded y rows are sliced off.

    The kernel sees x and y head-major, (B, H, L, P), so each block's last
    two dims are (chunk, P) as the TPU tiling requires; the chunk should
    be a multiple of 128 on the chip.
    """
    bb, l, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, l)
    l_pad = -(-l // q) * q
    if l_pad != l:
        x = jnp.pad(x, ((0, 0), (0, l_pad - l), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, l_pad - l), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, l_pad - l), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, l_pad - l), (0, 0)))
    nc = l_pad // q
    x_hm = x.transpose(0, 2, 1, 3)                     # (B, H, L, P)
    dt_hm = dt.transpose(0, 2, 1)                      # (B, H, L)

    kernel = functools.partial(_kernel, q=q, nc=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(bb, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, q), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bb, h, l_pad, p), x.dtype),
            jax.ShapeDtypeStruct((bb, h, p, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_hm, dt_hm[..., None], dt_hm[:, :, None, :], a, b, c)
    y = y.transpose(0, 2, 1, 3)                        # back to (B, L, H, P)
    return (y[:, :l] if l_pad != l else y), state
