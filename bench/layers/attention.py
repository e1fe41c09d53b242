"""Causal GQA attention (granite), pre-norm, with its residual.

Scores are scaled by ``attention_multiplier`` and the block's output by
``residual_multiplier`` before it joins the residual, as the file states
them.  ``position_embedding_type`` is ``"rope"`` (rotate-half rotary, the
default) or ``"nope"`` (no position embedding; ``layers.CHOICES``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.layers import F32, normal
from bench.reference import HIGHEST, mm, rms_norm, rope

KEYS = 4
NORMS = frozenset({"ln_attn"})
BLOCK = ("mixer", "attn")
LAYOUT = {"ln_attn": ("ln1",), "q": ("attn", "wq"), "k": ("attn", "wk"),
          "v": ("attn", "wv"), "o": ("attn", "wo")}


def init(keys, m: dict, n: int) -> dict:
    d, H, KV, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    std = m["initializer_range"]
    return {"ln_attn": jnp.ones((n, d), F32),
            "q": normal(keys[0], (n, d, H * hd), std),
            "k": normal(keys[1], (n, d, KV * hd), std),
            "v": normal(keys[2], (n, d, KV * hd), std),
            "o": normal(keys[3], (n, H * hd, d), std)}


def program_sizes(m: dict, cfg) -> dict:
    return {"n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "head_dim": m["head_dim"], "rope_theta": m["rope_theta"]}


def forward(x: jax.Array, lw: dict, m: dict, mode: str) -> jax.Array:
    b, s, _ = x.shape
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    h = rms_norm(x, lw["ln_attn"], m["rms_norm_eps"])
    q = mm(h, lw["q"], mode).reshape(b, s, H, hd)
    k = mm(h, lw["k"], mode).reshape(b, s, KV, hd)
    v = mm(h, lw["v"], mode).reshape(b, s, KV, hd)
    if m.get("position_embedding_type", "rope") == "rope":
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)       # query head i reads kv head i // (H/KV)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
              * m["attention_multiplier"])
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST).reshape(b, s, H * hd)
    return x + m.get("residual_multiplier", 1.0) * mm(o, lw["o"], mode)


def matmul_params(m: dict) -> int:
    d, H, KV, hd = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return 2 * d * H * hd + 2 * d * KV * hd


def other_params(m: dict) -> tuple[int, int]:
    return 0, m["hidden_size"]


def decode(m: dict, occupied: int, position: int) -> tuple[int, int]:
    """Scores and values over the ``position + 1`` live positions; the
    live K and V read, the new position's written (bf16).  A rotary
    embedding's elementwise work is not counted, as a norm's is not."""
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    ctx = position + 1
    return 4 * H * hd * ctx * occupied, occupied * 2 * KV * hd * 2 * ctx


def train_flops(m: dict, seq_len: int) -> int:
    """Causal attention's three passes over half the positions on average."""
    return 6 * m["num_attention_heads"] * m["head_dim"] * seq_len
