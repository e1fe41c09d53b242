"""Operations and bytes a step needs, from the model's shapes alone.

Counts follow the algorithm, not the program: a matrix product of an
(m, k) by a (k, n) operand is 2mkn operations; attention over c cached
positions is 4 * heads * head_dim * c per token per layer; a decode step
needs every parameter once, the live cache or recurrent state it reads,
and the state it writes.  Padded slots, the full-buffer attention read and
copies of the cache are not needed work and are not counted.
"""
from __future__ import annotations

from bench.weights import ssm_dims

BF16_BYTES = 2
F32_BYTES = 4


def matmul_params(m: dict) -> int:
    """Parameters that enter a matrix product, the unembedding included
    (the embedding lookup is a gather and is not counted again)."""
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    if m["family"] == "dense":
        H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
        per = 2 * d * H * hd + 2 * d * KV * hd + 3 * d * m["intermediate_size"]
    elif m["family"] == "ssm":
        s = ssm_dims(m)
        per = d * s["d_in_proj"] + s["d_inner"] * d
    else:
        raise ValueError(m["family"])
    return L * per + d * V


def param_bytes(m: dict) -> int:
    """Bytes of the served weights: matrices and the embedding in bf16,
    norm scales and SSM scalars in f32."""
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    mats = matmul_params(m)
    if m["family"] == "dense":
        f32 = L * 2 * d + d
        bf16 = mats
    else:
        s = ssm_dims(m)
        W = m["conv_kernel"]
        f32 = L * (d + 3 * s["heads"] + s["d_inner"]) + d
        bf16 = mats + L * (W + 1) * s["conv_dim"]
    return bf16 * BF16_BYTES + f32 * F32_BYTES


def decode_step(m: dict, occupied: int, position: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step with ``occupied`` requests
    that all write cache position ``position``."""
    L = m["num_hidden_layers"]
    flops = 2.0 * matmul_params(m) * occupied
    nbytes = float(param_bytes(m))
    if m["family"] == "dense":
        H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
        ctx = position + 1
        flops += 4.0 * H * hd * ctx * L * occupied
        per_pos = 2 * KV * hd * BF16_BYTES * L          # one position's K and V
        nbytes += occupied * per_pos * (position + 1)   # read the old, write the new
    else:
        s = ssm_dims(m)
        state = s["heads"] * m["head_dim"] * m["state_size"]
        flops += 5.0 * state * L * occupied             # decay, update, read-out
        conv = (m["conv_kernel"] - 1) * s["conv_dim"]
        nbytes += occupied * 2 * (state + conv) * BF16_BYTES * L
    return flops, nbytes


def step_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward operations per token, with no recompute:
    6 per matmul parameter, and causal attention's 6 * heads * head_dim *
    seq per layer (three passes over half the positions on average)."""
    flops = 6.0 * matmul_params(m)
    if m["family"] == "dense":
        flops += 6.0 * m["num_hidden_layers"] * m["num_attention_heads"] * \
            m["head_dim"] * seq_len
    return flops
