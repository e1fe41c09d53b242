"""Mamba-2 SSD (state-space duality) block — arXiv:2405.21060.

Training path: the chunked SSD algorithm — intra-chunk "attention-like"
quadratic term + inter-chunk linear state recurrence (a ``lax.scan`` over
chunks).  Decode path: O(1) per-token state update.

Block structure (Mamba-2): in_proj -> (z, x, B, C, dt); depthwise causal
conv over (x, B, C); SSD core; gated RMSNorm; out_proj.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_decode import pack_aux, ssd_decode_update
from repro.models.config import ModelConfig, SSMCfg
from repro.models.layers import constrain, layer_slice, rms_norm
from repro.models.spec import pdef


def ssm_dims(cfg: ModelConfig) -> dict[str, int]:
    s: SSMCfg = cfg.ssm  # type: ignore[assignment]
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.n_groups * s.d_state
    return {
        "d_inner": d_inner,
        "n_heads": n_heads,
        "conv_dim": conv_dim,
        "d_in_proj": 2 * d_inner + 2 * s.n_groups * s.d_state + n_heads,
    }


def make_ssd_defs(cfg: ModelConfig) -> dict:
    s: SSMCfg = cfg.ssm  # type: ignore[assignment]
    dims = ssm_dims(cfg)
    return {
        "in_proj": pdef((cfg.d_model, "d_model"), (dims["d_in_proj"], "heads")),
        "conv_w": pdef((s.conv_width, None), (dims["conv_dim"], "heads"),
                       scale=0.5),
        "conv_b": pdef((dims["conv_dim"], "heads"), init="zeros"),
        "a_log": pdef((dims["n_heads"], "heads"), init="ones", dtype=jnp.float32),
        "d_skip": pdef((dims["n_heads"], "heads"), init="ones", dtype=jnp.float32),
        "dt_bias": pdef((dims["n_heads"], "heads"), init="zeros", dtype=jnp.float32),
        "norm": pdef((dims["d_inner"], "heads"), init="zeros", dtype=jnp.float32),
        "out_proj": pdef((dims["d_inner"], "heads"), (cfg.d_model, "d_model")),
    }


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., Q) -> (..., Q, Q) lower-tri pairwise cumulative sums:
    out[..., i, j] = sum(a[..., j+1 : i+1]) for i >= j."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int,
             initial_state: jax.Array | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD.

    x:  (B, L, H, P) values
    dt: (B, L, H)    softplus'd step sizes
    a:  (H,)         negative decay rates (A = -exp(a_log))
    b:  (B, L, G, N) input projections  (broadcast G -> H)
    c:  (B, L, G, N) output projections
    Returns (y (B, L, H, P), final_state (B, H, P, N)).
    """
    bb, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert l % chunk == 0, f"L={l} not divisible by chunk={chunk}"
    nc = l // chunk
    rep = h // g

    xc = x.reshape(bb, nc, chunk, h, p)
    dtc = dt.reshape(bb, nc, chunk, h)
    bc = jnp.repeat(b.reshape(bb, nc, chunk, g, n), rep, axis=3)
    cc = jnp.repeat(c.reshape(bb, nc, chunk, g, n), rep, axis=3)

    da = dtc * a[None, None, None, :]                    # (B,nc,Q,H)
    da_cs = jnp.cumsum(da, axis=2)                       # within-chunk cumsum
    # intra-chunk (diagonal blocks): attention-like with decay mask
    lmask = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))   # (B,nc,H,Q,Q)
    xdt = xc * dtc[..., None]
    y_diag = jnp.einsum("bcqhn,bckhn,bchqk,bckhp->bcqhp", cc, bc, lmask, xdt)

    # per-chunk end states
    decay_states = jnp.exp(da_cs[:, :, -1:, :] - da_cs)  # (B,nc,Q,H)
    states = jnp.einsum("bckhn,bckh,bckhp->bchpn", bc, decay_states, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(da_cs[:, :, -1, :])            # (B,nc,H)
    s0 = (initial_state if initial_state is not None
          else jnp.zeros((bb, h, p, n), x.dtype))

    def step(s_prev, inp):
        st, dec = inp                                    # (B,H,P,N), (B,H)
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    (s_final, s_prevs) = jax.lax.scan(
        step, s0.astype(jnp.float32),
        (states.astype(jnp.float32).transpose(1, 0, 2, 3, 4),
         chunk_decay.transpose(1, 0, 2)))
    s_prevs = s_prevs.transpose(1, 0, 2, 3, 4).astype(x.dtype)  # (B,nc,H,P,N)

    # off-diagonal contribution from carried state
    state_decay = jnp.exp(da_cs)                         # (B,nc,Q,H)
    y_off = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp", cc, s_prevs, state_decay)
    y = (y_diag + y_off).reshape(bb, l, h, p)
    return y, s_final.astype(x.dtype)


def _causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array,
                 state: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d.  x: (B, L, C); w: (W, C).

    Returns (y (B,L,C), new_state (B, W-1, C)) — state carries the last
    W-1 inputs for decode continuation.
    """
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)             # (B, L+W-1, C)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(width))
    new_state = xp[:, -(width - 1):] if width > 1 else state
    return jax.nn.silu(y + bias[None, None]), new_state


def ssd_block_train(params: dict, x: jax.Array, cfg: ModelConfig, *,
                    return_state: bool = False):
    s: SSMCfg = cfg.ssm  # type: ignore[assignment]
    dims = ssm_dims(cfg)
    bsz, l, _ = x.shape
    h, p, n, g = dims["n_heads"], s.head_dim, s.d_state, s.n_groups

    zxbcdt = x @ params["in_proj"]
    z, xin, bc_in, dt_raw = jnp.split(
        zxbcdt, [dims["d_inner"], 2 * dims["d_inner"],
                 2 * dims["d_inner"] + 2 * g * n], axis=-1)
    conv_in = jnp.concatenate([xin, bc_in], axis=-1)
    conv_out, _ = _causal_conv(conv_in, params["conv_w"], params["conv_b"])
    xin, b_in, c_in = jnp.split(conv_out, [dims["d_inner"],
                                           dims["d_inner"] + g * n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None])
    a = -jnp.exp(params["a_log"])
    xh = xin.reshape(bsz, l, h, p)
    xh = constrain(xh, ("batch", "seq", "heads", None))
    y, final_state = ssd_scan(xh, dt.astype(x.dtype), a.astype(x.dtype),
                              b_in.reshape(bsz, l, g, n),
                              c_in.reshape(bsz, l, g, n),
                              chunk=min(s.chunk, l))
    y = y + params["d_skip"].astype(x.dtype)[None, None, :, None] * xh
    y = y.reshape(bsz, l, dims["d_inner"])
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = y @ params["out_proj"]
    if return_state:
        conv_state = conv_in[:, -(s.conv_width - 1):]
        return out, {"conv": conv_state, "state": final_state}
    return out


class Live(NamedTuple):
    """The decode batch's occupied slots: ``mask`` (B,) bool, and ``ids``
    (B,) int32 holding the ``count`` live slots first, in slot order."""
    mask: jax.Array
    ids: jax.Array
    count: jax.Array

    @classmethod
    def of(cls, mask: jax.Array) -> "Live":
        return cls(mask, jnp.flatnonzero(mask, size=mask.shape[0],
                                         fill_value=0).astype(jnp.int32),
                   jnp.sum(mask, dtype=jnp.int32))


def state_update_xla(state, layer, live, xh, dt, a, b, c):
    """The SSD decode update of layer ``layer`` of the stacked state, in
    the compute dtype; a free slot's state is kept and its ``y`` is 0."""
    old = layer_slice(state, layer)                      # (B,H,P,N)
    h = old.shape[1]
    bh = jnp.repeat(b, h // b.shape[1], axis=1)          # (B,H,N)
    ch = jnp.repeat(c, h // c.shape[1], axis=1)
    decay = jnp.exp(dt * a[None]).astype(xh.dtype)       # (B,H)
    upd = jnp.einsum("bhp,bhn,bh->bhpn", xh, bh, dt.astype(xh.dtype))
    new = old * decay[..., None, None] + upd
    new = jnp.where(live.mask[:, None, None, None], new, old)
    y = jnp.einsum("bhpn,bhn->bhp", new, ch)
    y = jnp.where(live.mask[:, None, None], y, 0)
    return y, jax.lax.dynamic_update_index_in_dim(
        state, new.astype(state.dtype), layer, 0)


def state_update_kernel(state, layer, live, xh, dt, a, b, c, *,
                        interpret: bool = False):
    """The same update by ``kernels.ssd_decode`` (one group of B and C):
    the live slots' state only, read and written once each, in place."""
    bsz, h, p = xh.shape
    aux = pack_aux(xh, dt, jnp.exp(dt * a[None]), b[:, 0], c[:, 0])
    y, state = ssd_decode_update(state, layer, live.ids, live.count, aux,
                                 interpret=interpret)
    return y.reshape(bsz, h, p).astype(xh.dtype), state


def ssd_block_decode(params: dict, x: jax.Array, cache: dict,
                     layer: jax.Array | int, cfg: ModelConfig, live: Live
                     ) -> tuple[jax.Array, dict]:
    """Single-token update of layer ``layer`` of the stacked cache
    {"conv": (L, B, W-1, conv_dim), "state": (L, B, H, P, N)}, for the
    slots ``live`` holds; a free slot's state and window are kept.

    Lowered for the TPU, with one group of B and C, the state is updated
    by the Pallas kernel ``kernels.ssd_decode``, which touches only the
    live slots' blocks; elsewhere by the same arithmetic in XLA over
    every slot, masked."""
    s: SSMCfg = cfg.ssm  # type: ignore[assignment]
    dims = ssm_dims(cfg)
    bsz = x.shape[0]
    h, p, n, g = dims["n_heads"], s.head_dim, s.d_state, s.n_groups

    zxbcdt = x @ params["in_proj"]                       # (B, 1, ·)
    z, xin, bc_in, dt_raw = jnp.split(
        zxbcdt, [dims["d_inner"], 2 * dims["d_inner"],
                 2 * dims["d_inner"] + 2 * g * n], axis=-1)
    conv_in = jnp.concatenate([xin, bc_in], axis=-1)
    conv = layer_slice(cache["conv"], layer)
    conv_out, conv_state = _causal_conv(conv_in, params["conv_w"],
                                        params["conv_b"], state=conv)
    conv_state = jnp.where(live.mask[:, None, None], conv_state, conv)
    xin, b_in, c_in = jnp.split(conv_out, [dims["d_inner"],
                                           dims["d_inner"] + g * n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None])[:, 0]   # (B,H)
    a = -jnp.exp(params["a_log"])                        # (H,)
    xh = xin.reshape(bsz, h, p)
    args = (cache["state"], layer, live, xh, dt, a,
            b_in.reshape(bsz, g, n), c_in.reshape(bsz, g, n))
    if g == 1:
        y, state = jax.lax.platform_dependent(
            *args, tpu=state_update_kernel, default=state_update_xla)
    else:
        y, state = state_update_xla(*args)
    y = y + params["d_skip"].astype(x.dtype)[None, :, None] * xh
    y = y.reshape(bsz, 1, dims["d_inner"])
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    return y @ params["out_proj"], {
        "conv": jax.lax.dynamic_update_index_in_dim(
            cache["conv"], conv_state.astype(cache["conv"].dtype), layer, 0),
        "state": state}
