"""Operations and bytes a step needs, from the model's shapes alone.

Counts follow the algorithm, not the program: a matrix product of an
(m, k) by a (k, n) operand is 2mkn operations; attention over c cached
positions is 4 * heads * head_dim * c per token per layer; a decode step
needs every parameter once, the live cache or recurrent state it reads,
and the state it writes.  Padded slots, the full-buffer attention read and
copies of the cache are not needed work and are not counted.

Each layer kind counts its own part (``bench/layers/<kind>.py``); a count
here is the sum over the model's layers, in whole numbers, so the order of
the sum changes nothing.
"""
from __future__ import annotations

from bench.layers import kind, kind_counts

BF16_BYTES = 2
F32_BYTES = 4


def _per_kind(m: dict):
    return [(kind(k), n) for k, n in kind_counts(m).items()]


def matmul_params(m: dict) -> int:
    """Parameters that enter a matrix product, the unembedding included
    (the embedding lookup is a gather and is not counted again)."""
    return (sum(n * k.matmul_params(m) for k, n in _per_kind(m))
            + m["hidden_size"] * m["vocab_size"])


def param_bytes(m: dict) -> int:
    """Bytes of the served weights: matrices and the embedding in bf16,
    norm scales and SSM scalars in f32, the convolution's taps in bf16."""
    bf16, f32 = matmul_params(m), m["hidden_size"]          # final norm
    for k, n in _per_kind(m):
        b, f = k.other_params(m)
        bf16, f32 = bf16 + n * b, f32 + n * f
    return bf16 * BF16_BYTES + f32 * F32_BYTES


def decode_step(m: dict, occupied: int, position: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step with ``occupied`` requests
    that all write cache position ``position``."""
    flops, nbytes = 2 * matmul_params(m) * occupied, param_bytes(m)
    for k, n in _per_kind(m):
        f, b = k.decode(m, occupied, position)
        flops, nbytes = flops + n * f, nbytes + n * b
    return float(flops), float(nbytes)


def step_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward operations per token, with no recompute:
    6 per matmul parameter, and each kind's own (causal attention's
    6 * heads * head_dim * seq per layer)."""
    return float(6 * matmul_params(m)
                 + sum(n * k.train_flops(m, seq_len) for k, n in _per_kind(m)))
