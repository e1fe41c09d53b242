"""Core transformer layers: norms, RoPE, GQA/SWA/MLA attention, SwiGLU.

All functions are pure: ``params`` are dict pytrees (built from the
ParamDef trees in each ``make_*_defs``), activations are jnp arrays.

Activation sharding is injected through :func:`constrain`, which consults
the active sharding context (set by the distributed layer); without a
context it is the identity, so models run unmodified on one device.

Attention memory discipline: training/prefill attention is *blockwise* —
a ``lax.scan`` over query blocks so the full (S × S) score matrix is never
materialized (full-row softmax per block keeps it numerically exact).
Sliding-window layers slice only the in-window KV per query block, making
SWA genuinely sub-quadratic.  Decode uses ring-buffer KV caches.
"""
from __future__ import annotations

import contextvars
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models.config import MLACfg, ModelConfig
from repro.models.spec import ParamDef, pdef

# ---------------------------------------------------------------------------
# activation-sharding context
# ---------------------------------------------------------------------------

_SHARD_CTX: contextvars.ContextVar[Callable[[jax.Array, tuple], jax.Array] | None] = \
    contextvars.ContextVar("wrath_shard_ctx", default=None)
# (mesh, rules) for code that needs explicit collectives (shard_map MoE)
_MESH_CTX: contextvars.ContextVar[Any | None] = \
    contextvars.ContextVar("wrath_mesh_ctx", default=None)


def set_shard_fn(fn: Callable[[jax.Array, tuple], jax.Array] | None,
                 mesh: Any | None = None):
    token2 = _MESH_CTX.set(mesh)
    return _SHARD_CTX.set(fn), token2


def reset_shard_fn(token) -> None:
    t1, t2 = token if isinstance(token, tuple) else (token, None)
    _SHARD_CTX.reset(t1)
    if t2 is not None:
        _MESH_CTX.reset(t2)


def current_mesh():
    return _MESH_CTX.get()


def constrain(x: jax.Array, axes: tuple[str | None, ...]) -> jax.Array:
    fn = _SHARD_CTX.get()
    return fn(x, axes) if fn is not None else x


# ---------------------------------------------------------------------------
# norms & embeddings
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    # mean-square via an f32-ACCUMULATING einsum: no materialized f32 copy
    # of x (a full f32 activation would get stacked into the layer-scan
    # residuals by XLA's convert hoisting, doubling activation memory)
    ms = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32) / x.shape[-1]
    scale = jax.lax.rsqrt(ms + eps)[..., None].astype(x.dtype)
    return x * scale * (1.0 + w).astype(x.dtype)


def make_norm_def(d: int) -> ParamDef:
    # stored as (w - 1): init zeros => effective scale 1.0
    return pdef((d, "d_model"), init="zeros", dtype=jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) or (B, S, D); positions: (S,) or (B, S)."""
    squeeze = x.ndim == 3
    if squeeze:                                        # (B, S, D) -> (B, S, 1, D)
        x = x[:, :, None, :]
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # ((B,)S, D/2)
    angles = angles[..., None, :]                      # head axis: ((B,)S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = out.astype(x.dtype)
    return out[:, :, 0, :] if squeeze else out


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------


def make_ffn_defs(d_model: int, d_ff: int) -> dict[str, ParamDef]:
    return {
        "w1": pdef((d_model, "d_model"), (d_ff, "d_ff")),
        "w3": pdef((d_model, "d_model"), (d_ff, "d_ff")),
        "w2": pdef((d_ff, "d_ff"), (d_model, "d_model")),
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])
    h = constrain(h, ("batch", "seq", "d_ff"))
    return h @ params["w2"]


# ---------------------------------------------------------------------------
# attention parameter trees
# ---------------------------------------------------------------------------


def make_attention_defs(cfg: ModelConfig, *, cross: bool = False) -> dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    return {
        "wq": pdef((d, "d_model"), (h * hd, "heads")),
        "wk": pdef((d, "d_model"), (kv * hd, "kv_heads")),
        "wv": pdef((d, "d_model"), (kv * hd, "kv_heads")),
        "wo": pdef((h * hd, "heads"), (d, "d_model")),
    }


def make_mla_defs(cfg: ModelConfig) -> dict[str, Any]:
    m: MLACfg = cfg.mla  # type: ignore[assignment]
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq_a": pdef((d, "d_model"), (m.q_lora_rank, None)),
        "q_norm": pdef((m.q_lora_rank, None), init="zeros", dtype=jnp.float32),
        "wq_b": pdef((m.q_lora_rank, None), (h * m.qk_head_dim, "heads")),
        "wkv_a": pdef((d, "d_model"), (m.kv_lora_rank, None)),
        "kv_norm": pdef((m.kv_lora_rank, None), init="zeros", dtype=jnp.float32),
        "wkv_b": pdef((m.kv_lora_rank, None),
                      (h * (m.qk_nope_head_dim + m.v_head_dim), "heads")),
        "wk_rope": pdef((d, "d_model"), (m.qk_rope_head_dim, None)),
        "wo": pdef((h * m.v_head_dim, "heads"), (d, "d_model")),
    }


# ---------------------------------------------------------------------------
# blockwise multi-head attention (training / prefill)
# ---------------------------------------------------------------------------


def _pick_q_block(s: int) -> int:
    for qb in (512, 256, 128, 64):
        if s % qb == 0 and s > qb:
            return qb
    return s


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool, window: int = 0, q_offset: jax.Array | int = 0,
        kv_len: jax.Array | None = None) -> jax.Array:
    """Dense attention with GQA and optional sliding window.

    q: (B, Sq, H, D); k, v: (B, Sk, KV, D).  Returns (B, Sq, H, D).
    ``q_offset``: absolute position of q[0] (decode / blockwise).
    ``kv_len``: number of valid kv positions (ring-buffer decode).
    """
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    dv = v.shape[-1]                                   # may differ (MLA)
    g = h // kvh
    qh = q.reshape(b, sq, kvh, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    qpos = (jnp.arange(sq) + q_offset)[:, None]        # (Sq, 1)
    kpos = jnp.arange(sk)[None, :]                     # (1, Sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask &= kpos < kv_len
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return out.reshape(b, sq, h, dv)


def blockwise_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0) -> jax.Array:
    """Scan over query blocks; O(qb·S) live scores instead of O(S²).

    For sliding-window attention only the (window + qb)-wide KV slice is
    read per block, so SWA cost is O(S·window).
    """
    b, s, h, d = q.shape
    dv = v.shape[-1]
    qb = _pick_q_block(s)
    if qb == s:
        return mha(q, k, v, causal=causal, window=window)
    nq = s // qb
    qblocks = q.reshape(b, nq, qb, h, d).swapaxes(0, 1)    # (nq, B, qb, H, D)

    # flash-style rematerialization: checkpoint the per-block body so the
    # O(qb·S) score/probability blocks are recomputed in the backward pass
    # instead of being stacked as scan residuals (the dominant activation
    # cost of non-kernel attention).
    if window and window + qb <= s:
        ctx = window + qb

        def body(carry, inp):
            i, qi = inp
            start = jnp.clip(i * qb + qb - ctx, 0, s - ctx)
            ki = jax.lax.dynamic_slice_in_dim(k, start, ctx, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, ctx, axis=1)
            out = mha(qi, ki, vi, causal=causal, window=window,
                      q_offset=i * qb - start)
            return carry, out
    else:
        def body(carry, inp):
            i, qi = inp
            out = mha(qi, k, v, causal=causal, window=window, q_offset=i * qb)
            return carry, out

    body = jax.checkpoint(body, prevent_cse=False)
    _, outs = jax.lax.scan(body, None, (jnp.arange(nq), qblocks))
    return outs.swapaxes(0, 1).reshape(b, s, h, dv)


# ---------------------------------------------------------------------------
# head padding (TP-mesh divisibility; see ModelConfig.head_pad)
# ---------------------------------------------------------------------------


def _pad_heads(q: jax.Array, k: jax.Array, v: jax.Array,
               cfg: ModelConfig) -> tuple[jax.Array, jax.Array, jax.Array, int]:
    """Pad q heads to cfg.head_pad and expand kv to the same count (MHA
    layout) so the head dim divides the model mesh axis.  Returns original
    head count for the caller to slice the output back."""
    h = q.shape[-2]
    hp = cfg.head_pad
    if not hp or hp <= h:
        return q, k, v, h
    kvh = k.shape[-2]
    if kvh != h:                              # GQA -> full MHA expansion
        k = jnp.repeat(k, h // kvh, axis=-2)
        v = jnp.repeat(v, h // kvh, axis=-2)
    pad = [(0, 0)] * q.ndim
    pad[-2] = (0, hp - h)
    q = jnp.pad(q, pad)
    k = jnp.pad(k, pad)
    v = jnp.pad(v, pad)
    return q, k, v, h


# ---------------------------------------------------------------------------
# full attention blocks (train path)
# ---------------------------------------------------------------------------


def attention_train(params: dict, x: jax.Array, cfg: ModelConfig, *,
                    window: int = 0, bidirectional: bool = False,
                    kv_source: jax.Array | None = None,
                    positions: jax.Array | None = None,
                    return_kv: bool = False):
    """Self- (or cross-) attention over a full sequence.

    kv_source: if given (encoder output), cross-attention without RoPE.
    return_kv: also return the (roped) K/V for prefill cache capture.
    """
    b, s, _ = x.shape
    hd, h, kv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    src = x if kv_source is None else kv_source
    sk = src.shape[1]
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (src @ params["wk"]).reshape(b, sk, kv, hd)
    v = (src @ params["wv"]).reshape(b, sk, kv, hd)
    if kv_source is None:
        pos = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos if sk == s else jnp.arange(sk), cfg.rope_theta)
    kv_for_cache = {"k": k, "v": v}
    q, k, v, h_orig = _pad_heads(q, k, v, cfg)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "heads" if cfg.head_pad else "kv_heads",
                      None))
    v = constrain(v, ("batch", "seq", "heads" if cfg.head_pad else "kv_heads",
                      None))
    causal = (kv_source is None) and not bidirectional
    out = blockwise_mha(q, k, v, causal=causal, window=window)
    out = out[..., :h_orig, :]
    out = constrain(out, ("batch", "seq", "heads", None))
    out = out.reshape(b, s, h * hd) @ params["wo"]
    if return_kv:
        return out, kv_for_cache
    return out


def mla_train(params: dict, x: jax.Array, cfg: ModelConfig, *,
              return_cache: bool = False):
    """DeepSeek-V3 multi-head latent attention (training path)."""
    m: MLACfg = cfg.mla  # type: ignore[assignment]
    b, s, _ = x.shape
    h = cfg.n_heads
    pos = jnp.arange(s)
    cq = rms_norm(x @ params["wq_a"], params["q_norm"], cfg.norm_eps)
    q = (cq @ params["wq_b"]).reshape(b, s, h, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    ckv = rms_norm(x @ params["wkv_a"], params["kv_norm"], cfg.norm_eps)
    kvu = (ckv @ params["wkv_b"]).reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvu, [m.qk_nope_head_dim], axis=-1)
    k_rope = apply_rope(x @ params["wk_rope"], pos, cfg.rope_theta)  # (B,S,rope)
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_head_dim))

    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope], axis=-1)
    q_full = constrain(q_full, ("batch", "seq", "heads", None))
    k_full = constrain(k_full, ("batch", "seq", "heads", None))
    out = blockwise_mha(q_full, k_full, v, causal=True)
    out = constrain(out, ("batch", "seq", "heads", None))
    out = out.reshape(b, s, h * m.v_head_dim) @ params["wo"]
    if return_cache:
        k_rope_flat = apply_rope(x @ params["wk_rope"], pos, cfg.rope_theta)
        return out, {"ckv": ckv, "k_rope": k_rope_flat}
    return out


# ---------------------------------------------------------------------------
# decode (single new token against a ring-buffer cache)
# ---------------------------------------------------------------------------


def attention_decode(params: dict, x: jax.Array, cache: dict, cfg: ModelConfig, *,
                     window: int = 0,
                     cross_memory: dict | None = None) -> tuple[jax.Array, dict]:
    """x: (B, 1, d).  cache: {"k","v": (B, Smax, KV, hd), "len": (B,) or ()}.

    Ring-buffer semantics: the new KV overwrites position ``len % Smax``.
    Cross-attention (enc-dec) passes ``cross_memory`` = {"k","v"} instead;
    the cache is untouched.
    """
    b = x.shape[0]
    hd, h, kvh = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    if cross_memory is not None:
        k, v = cross_memory["k"], cross_memory["v"]
        out = mha(q, k, v, causal=False)
        return out.reshape(b, 1, h * hd) @ params["wo"], cache

    smax = cache["k"].shape[1]
    cur = cache["len"]                                  # scalar int32
    k_new = (x @ params["wk"]).reshape(b, 1, kvh, hd)
    v_new = (x @ params["wv"]).reshape(b, 1, kvh, hd)
    posq = jnp.full((1,), cur, dtype=jnp.int32)
    q = apply_rope(q, posq, cfg.rope_theta)
    k_new = apply_rope(k_new, posq, cfg.rope_theta)
    slot = jnp.mod(cur, smax)
    with jax.named_scope("kv_write"):
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    n_valid = jnp.minimum(cur + 1, smax)
    # decode scores over the whole buffer; invalid slots masked via n_valid.
    # window masking is implicit: the swa buffer is only `window` wide.
    g = h // kvh
    qh = q.reshape(b, 1, kvh, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qh, ck,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
    kpos = jnp.arange(smax)[None, :]
    mask = kpos < n_valid
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, cv).reshape(b, 1, h * hd)
    new_cache = {"k": ck, "v": cv, "len": cur + 1}
    return out @ params["wo"], new_cache


def mla_decode(params: dict, x: jax.Array, cache: dict,
               cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """Absorbed MLA decode: scores/outputs computed against the compressed
    latent cache (c_kv, k_rope) without materializing per-head K/V."""
    m: MLACfg = cfg.mla  # type: ignore[assignment]
    b = x.shape[0]
    h = cfg.n_heads
    smax = cache["ckv"].shape[1]
    cur = cache["len"]
    posq = jnp.full((1,), cur, dtype=jnp.int32)

    cq = rms_norm(x @ params["wq_a"], params["q_norm"], cfg.norm_eps)
    q = (cq @ params["wq_b"]).reshape(b, 1, h, m.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, posq, cfg.rope_theta)

    ckv_new = rms_norm(x @ params["wkv_a"], params["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope(x @ params["wk_rope"], posq, cfg.rope_theta)
    slot = jnp.mod(cur, smax)
    with jax.named_scope("kv_write"):
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv_new[:, None].astype(cache["ckv"].dtype)
            if ckv_new.ndim == 2 else ckv_new.astype(cache["ckv"].dtype),
            slot, axis=1)
        krope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), slot,
            axis=1)

    # absorb wkv_b's K half into q_nope:  q_abs (B,1,H,kv_lora)
    wkv_b = params["wkv_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_k = wkv_b[:, :, :m.qk_nope_head_dim]              # (r, H, nope)
    w_v = wkv_b[:, :, m.qk_nope_head_dim:]              # (r, H, v)
    q_abs = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_k)
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, krope,
                           preferred_element_type=jnp.float32))
    scores = scores / math.sqrt(m.qk_head_dim)
    n_valid = jnp.minimum(cur + 1, smax)
    mask = jnp.arange(smax)[None, :] < n_valid
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqs,bsr->bqhr", p, ckv)          # (B,1,H,r)
    out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_v).reshape(b, 1, h * m.v_head_dim)
    new_cache = {"ckv": ckv, "k_rope": krope, "len": cur + 1}
    return out @ params["wo"], new_cache
