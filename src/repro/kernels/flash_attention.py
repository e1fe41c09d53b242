"""Flash attention Pallas TPU kernel (blockwise online-softmax).

TPU-native adaptation of the flash-attention idea (DESIGN.md §6): the
(Sq × Sk) score matrix never leaves VMEM.  Grid = (batch·heads, q_blocks,
kv_blocks); the kv dimension is the innermost sequential ("arbitrary")
axis, with running max / normalizer / accumulator kept in VMEM scratch
across kv steps.  Block shapes are MXU-aligned: q/kv tiles are multiples
of 128 rows and the head dim rides the 128-lane axis; softmax statistics
are stored lane-replicated (qb, 128) for layout friendliness.

Supports causal and sliding-window masking.  Numerics: scores and the
accumulator are fp32 regardless of input dtype (matching the pure-jnp
reference to ~1e-2 in bf16, ~1e-5 in fp32).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            qb: int, kb: int, hd: int, causal: bool, window: int,
            nk: int, scale: float, kv_valid: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                  # (qb, hd)
    k = k_ref[0].astype(jnp.float32)                  # (kb, hd)
    v = v_ref[0].astype(jnp.float32)                  # (kb, hd)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # (qb, kb)

    q_pos = i * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    k_pos = j * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = jnp.ones((qb, kb), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    if kv_valid:
        # kv padded up to a block multiple: positions past the true length
        # contribute nothing (padded *q* rows need no mask — their output
        # is sliced off, and the online-softmax rescale keeps them finite)
        mask &= k_pos < kv_valid
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[:, 0][:, None]                      # (qb, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)                        # (qb, kb)
    alpha = jnp.exp(m_prev - m_new)                    # (qb, 1)
    l_new = alpha * l_ref[:, 0][:, None] + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, 0][:, None]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_block", "kv_block", "interpret"))
def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       q_block: int = 128, kv_block: int = 128,
                       interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, S, D) with equal head counts (GQA expanded by caller).

    Sequence lengths need not divide the block sizes: q/kv are zero-padded
    up to the next block multiple (the kernel masks padded kv positions;
    padded q rows are sliced off the output), so autotuned blocks work for
    arbitrary lengths.
    """
    bh, s, hd = q.shape
    sk = k.shape[1]
    qb = min(q_block, s)
    kb = min(kv_block, sk)
    s_pad = -(-s // qb) * qb
    sk_pad = -(-sk // kb) * kb
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0)))
    nq, nk = s_pad // qb, sk_pad // kb
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel, qb=qb, kb=kb, hd=hd, causal=causal, window=window,
        nk=nk, scale=scale, kv_valid=sk if sk_pad != sk else 0)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kb, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kb, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, hd), jnp.float32),
            pltpu.VMEM((qb, 128), jnp.float32),
            pltpu.VMEM((qb, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :s] if s_pad != s else out
