"""Model FLOP/s utilization of training: forward and backward operations
per token from shapes, with no recompute (``bench/cost.py``), times the
window's ``train_tokens_per_s``, over the chip's peak bf16 FLOP/s.  Moves
``train_tokens_per_s``."""


def read(run: dict):
    if run.get("plane") != "train" or not run.get("tokens_per_s"):
        return None
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] / run["peaks"]["bf16_flops"]
