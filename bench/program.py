"""The program's own configuration for a configuration file."""
from __future__ import annotations

import dataclasses

from repro.configs import get_config

from bench.layers import kind, kind_counts, layer_parts

#: ``model`` keys that reach the program's field of the same name where it
#: has one: what granite-family layers read beyond their sizes
PASSED = ("embedding_multiplier", "attention_multiplier",
          "residual_multiplier", "logits_scaling", "position_embedding_type")


def _period(seq: list) -> tuple:
    """The shortest prefix whose repetition is ``seq``."""
    for p in range(1, len(seq) + 1):
        if all(seq[i] == seq[i % p] for i in range(len(seq))):
            return tuple(seq[:p])
    return tuple(seq)


def block_kinds(m: dict) -> list[tuple[str, str]]:
    """Each layer's (mixer, ffn) in the program's names."""
    out = []
    for parts in layer_parts(m):
        block = {"mixer": None, "ffn": "none"}
        for k in parts:
            slot, name = kind(k).BLOCK
            block[slot] = name
        out.append((block["mixer"], block["ffn"]))
    return out


def program_config(config: dict):
    """The program's ``ModelConfig`` for this configuration file: the
    program's own entry, with each ``PASSED`` key of the file that names
    one of its fields, the sizes each layer kind maps, and the layer
    pattern as the shortest period of the stack.  The program's own
    settings (its precision, family, pattern) are never read from a file."""
    m = config["model"]
    cfg = get_config(config["program_config"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    sizes = {k: m[k] for k in PASSED if k in m and k in fields}
    sizes.update(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                 vocab_size=m["vocab_size"], norm_eps=m["rms_norm_eps"],
                 pattern=_period(block_kinds(m)))
    for k in kind_counts(m):
        sizes.update(kind(k).program_sizes(m, cfg))
    return cfg.scaled(**sizes)
