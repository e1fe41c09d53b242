"""The program's own configuration for a configuration file."""
from __future__ import annotations

import dataclasses

from repro.configs import get_config

from bench import weights
from bench.harness import BenchError


def program_config(config: dict):
    """The program's ``ModelConfig`` for this configuration file: the
    program's own entry, with the sizes the file states."""
    m = config["model"]
    cfg = get_config(config["program_config"])
    sizes = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
             "vocab_size": m["vocab_size"], "norm_eps": m["rms_norm_eps"]}
    if m["family"] == "dense":
        sizes.update(n_heads=m["num_attention_heads"],
                     n_kv_heads=m["num_key_value_heads"],
                     head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                     rope_theta=m["rope_theta"])
    elif m["family"] == "ssm":
        heads = weights.ssm_dims(m)["heads"]
        sizes.update(n_heads=heads, n_kv_heads=heads, head_dim=m["head_dim"],
                     ssm=dataclasses.replace(
                         cfg.ssm, d_state=m["state_size"],
                         head_dim=m["head_dim"], expand=m["expand"],
                         conv_width=m["conv_kernel"], n_groups=m["n_groups"]))
    else:
        raise BenchError(f"unknown family {m['family']!r}")
    return cfg.scaled(**sizes)
