"""SwiGLU MLP after a mixer, pre-norm, with its residual scaled by
``residual_multiplier``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.layers import F32, normal
from bench.reference import mm, rms_norm

KEYS = 3
NORMS = frozenset({"ln_mlp"})
BLOCK = ("ffn", "dense")
LAYOUT = {"ln_mlp": ("ln2",), "gate": ("ffn", "w1"), "up": ("ffn", "w3"),
          "down": ("ffn", "w2")}


def init(keys, m: dict, n: int) -> dict:
    d, ff, std = m["hidden_size"], m["intermediate_size"], m["initializer_range"]
    return {"ln_mlp": jnp.ones((n, d), F32),
            "gate": normal(keys[0], (n, d, ff), std),
            "up": normal(keys[1], (n, d, ff), std),
            "down": normal(keys[2], (n, ff, d), std)}


def program_sizes(m: dict, cfg) -> dict:
    return {"d_ff": m["intermediate_size"]}


def forward(x: jax.Array, lw: dict, m: dict, mode: str) -> jax.Array:
    h = rms_norm(x, lw["ln_mlp"], m["rms_norm_eps"])
    return x + m.get("residual_multiplier", 1.0) * mm(
        jax.nn.silu(mm(h, lw["gate"], mode)) * mm(h, lw["up"], mode),
        lw["down"], mode)


def matmul_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def other_params(m: dict) -> tuple[int, int]:
    return 0, m["hidden_size"]


def decode(m: dict, occupied: int, position: int) -> tuple[int, int]:
    return 0, 0


def train_flops(m: dict, seq_len: int) -> int:
    return 0
