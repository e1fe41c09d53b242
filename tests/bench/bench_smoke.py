"""Smoke-size cells for the benchmark's CPU tests: the committed
configuration and traffic files with their sizes cut down."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import Cell, CompileClock, load_json  # noqa: E402

#: small widths; the init scale keeps logits about as wide as at full size
SMOKE_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
               "initializer_range": 0.11,
               # the program scales attention scores by 1/sqrt(head_dim)
               "attention_multiplier": 0.25}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


class NoTracer:
    enabled = False
    stop_s = 0.0

    def start(self):
        pass

    def stop(self):
        pass

    def reduce(self):
        return None


def smoke_cell(config: str, traffic: str) -> Cell:
    """A cell of a committed configuration and traffic mix, at smoke size."""
    cell = Cell(name=f"{config}.{traffic}", chips=1,
                config=copy.deepcopy(load_json(ROOT / "bench/configs" / f"{config}.json")),
                traffic=load_json(ROOT / "bench/traffic" / f"{traffic}.json"),
                end_to_end=[], per_layer=[])
    cell.config["model"].update(SMOKE_MODEL)
    if cell.config["model"]["family"] == "ssm":
        del cell.config["model"]["attention_multiplier"]
        cell.config["model"]["state_size"] = 16
    if cell.config["plane"] == "serve":
        cell.config["serve"] = {"max_batch": 4, "max_len": 32, "replicas": 1}
        cell.config["limits"]["served_tokens_compared"] = 8
        cell.traffic.update(batch=4,
                            prompt={"median": 4, "sigma": 0.6, "min": 2, "max": 8},
                            output={"median": 6, "sigma": 0.6, "min": 2, "max": 12})
    else:
        cell.traffic["seq_len"] = 32
    return cell


def run_cell(cell, *, seed: int = 2**31 + 3, seconds: float = 2.0) -> dict:
    """One run through the harness's plane function, without its chip check."""
    import jax

    from bench import cells

    fn = cells.run_serve if cell.config["plane"] == "serve" else cells.run_train
    with CompileClock() as clock:
        out = fn(cell, seed=seed, seconds=seconds, devices=jax.devices()[:1],
                 clock=clock, tracer=NoTracer(), peaks=PEAKS)
    out["correct"] = all(c["ok"] for c in out["checks"].values())
    return out


def committed(name: str) -> dict:
    return load_json(ROOT / name)
