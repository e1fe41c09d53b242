"""Mamba-2 (SSD) mixer, pre-norm, with its residual scaled by
``residual_multiplier``, read from granitemoehybrid's keys:
``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``mamba_expand`` and ``mamba_chunk_size``.  The head count is
``mamba_expand * hidden_size / mamba_d_head``; a file's ``mamba_n_heads``
records it and is not read.  ``mamba_conv_bias`` may only be true, as
every Mamba-2 config states it (``layers.CHOICES``).

Weights follow the paper's code (arXiv:2405.21060): A drawn uniformly
from [1, 16] (stored as its log), dt from a log-uniform [1e-3, 1e-1]
(stored as the inverse softplus of dt), D one, the depthwise convolution
uniform over +-1/sqrt(width), and ``out_proj`` scaled down by
sqrt(layers).  The reference runs token by token.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench.layers import BF16, F32, normal
from bench.reference import HIGHEST, mm, rms_norm

KEYS = 6
NORMS = frozenset({"ln", "norm"})
BLOCK = ("mixer", "ssd")
LAYOUT = {"ln": ("ln1",), "in_proj": ("ssd", "in_proj"),
          "conv_w": ("ssd", "conv_w"), "conv_b": ("ssd", "conv_b"),
          "A_log": ("ssd", "a_log"), "D": ("ssd", "d_skip"),
          "dt_bias": ("ssd", "dt_bias"), "norm": ("ssd", "norm"),
          "out_proj": ("ssd", "out_proj")}


def dims(m: dict) -> dict:
    d_inner = m["mamba_expand"] * m["hidden_size"]
    heads = d_inner // m["mamba_d_head"]
    gn = m["mamba_n_groups"] * m["mamba_d_state"]
    return {"d_inner": d_inner, "heads": heads, "gn": gn,
            "conv_dim": d_inner + 2 * gn,
            "d_in_proj": 2 * d_inner + 2 * gn + heads}


def init(keys, m: dict, n: int) -> dict:
    d, std, W = m["hidden_size"], m["initializer_range"], m["mamba_d_conv"]
    s = dims(m)
    H = s["heads"]
    dt = jnp.exp(jax.random.uniform(keys[3], (n, H), F32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    bound = 1.0 / math.sqrt(W)
    return {
        "ln": jnp.ones((n, d), F32),
        "in_proj": normal(keys[0], (n, d, s["d_in_proj"]), std),
        "conv_w": jax.random.uniform(keys[1], (n, W, s["conv_dim"]), F32,
                                     -bound, bound).astype(BF16),
        "conv_b": jax.random.uniform(keys[2], (n, s["conv_dim"]), F32,
                                     -bound, bound).astype(BF16),
        "A_log": jnp.log(jax.random.uniform(keys[4], (n, H), F32, 1.0, 16.0)),
        "D": jnp.ones((n, H), F32),
        # inverse softplus: softplus(dt_bias) == dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm": jnp.ones((n, s["d_inner"]), F32),
        "out_proj": normal(keys[5], (n, s["d_inner"], d),
                           std / math.sqrt(m["num_hidden_layers"])),
    }


def program_sizes(m: dict, cfg) -> dict:
    from repro.models import SSMCfg

    return {"ssm": dataclasses.replace(
        cfg.ssm or SSMCfg(), d_state=m["mamba_d_state"],
        head_dim=m["mamba_d_head"], expand=m["mamba_expand"],
        conv_width=m["mamba_d_conv"], n_groups=m["mamba_n_groups"],
        chunk=m["mamba_chunk_size"])}


def forward(x: jax.Array, lw: dict, m: dict, mode: str) -> jax.Array:
    b, s, _ = x.shape
    ds = dims(m)
    di, H, gn, P = ds["d_inner"], ds["heads"], ds["gn"], m["mamba_d_head"]
    G, N, W = m["mamba_n_groups"], m["mamba_d_state"], m["mamba_d_conv"]
    h = rms_norm(x, lw["ln"], m["rms_norm_eps"])
    zxbcdt = mm(h, lw["in_proj"], mode)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * gn]
    dt = zxbcdt[..., 2 * di + 2 * gn:]
    # depthwise causal convolution: the last tap meets the current token
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    cw = lw["conv_w"].astype(F32)
    conv = sum(pad[:, i:i + s] * cw[i] for i in range(W))
    xbc = jax.nn.silu(conv + lw["conv_b"].astype(F32))
    xs = xbc[..., :di].reshape(b, s, H, P)
    bs = jnp.repeat(xbc[..., di:di + gn].reshape(b, s, G, N), H // G, axis=2)
    cs = jnp.repeat(xbc[..., di + gn:].reshape(b, s, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                 # (B, S, H)
    a = -jnp.exp(lw["A_log"])                                 # (H,)

    def step(state, inp):
        xt, bt, ct, dtt = inp                                 # (B,H,P) (B,H,N) (B,H,N) (B,H)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HIGHEST)

    seq = (xs.swapaxes(0, 1), bs.swapaxes(0, 1), cs.swapaxes(0, 1),
           dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), F32), seq)
    y = y.swapaxes(0, 1) + lw["D"][:, None] * xs              # (B, S, H, P)
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = rms_norm(y, lw["norm"], m["rms_norm_eps"])
    return x + m.get("residual_multiplier", 1.0) * mm(y, lw["out_proj"], mode)


def matmul_params(m: dict) -> int:
    s = dims(m)
    return m["hidden_size"] * s["d_in_proj"] + s["d_inner"] * m["hidden_size"]


def other_params(m: dict) -> tuple[int, int]:
    """The convolution's taps and bias in bf16; A, D, dt's bias and the
    two norms in f32."""
    s = dims(m)
    return ((m["mamba_d_conv"] + 1) * s["conv_dim"],
            m["hidden_size"] + 3 * s["heads"] + s["d_inner"])


def decode(m: dict, occupied: int, position: int) -> tuple[int, int]:
    """Decay, update and read-out of the state; the state and the
    convolution's window read and written (bf16)."""
    s = dims(m)
    state = s["heads"] * m["mamba_d_head"] * m["mamba_d_state"]
    conv = (m["mamba_d_conv"] - 1) * s["conv_dim"]
    return 5 * state * occupied, occupied * 2 * (state + conv) * 2


def train_flops(m: dict, seq_len: int) -> int:
    return 0
