"""A model as a list of layers, each made of parts of named kinds, one
file per kind, found by name.

A configuration's ``model`` may state ``layer_types``, one entry per
layer, spelled as granitemoehybrid's ``config.json`` spells them
(``"attention"``, ``"mamba"``).  A file without the key reads its
``family``: ``dense`` is every layer attention, ``ssm`` every layer
mamba.  Each mixer is followed by an MLP (kind ``mlp``) where
``intermediate_size`` is above 0.

``bench/layers/<kind>.py`` gives, for its kind:

* ``KEYS``, ``NORMS``, ``init(keys, m, n)``: how many PRNG keys its draw
  takes, which of its weights are norm scales, and its weights for ``n``
  layers, each leaf ``(n, ...)``;
* ``BLOCK``, ``LAYOUT``, ``program_sizes(m, cfg)``: its place in the
  program's block kind (``("mixer", name)`` or ``("ffn", name)``), where
  each of its weights sits in the program's block tree, and the fields of
  the program's ``ModelConfig`` it sets;
* ``forward(x, lw, m, mode)``: its float32 reference, residual included;
* ``matmul_params(m)``, ``other_params(m)``, ``decode(m, occupied,
  position)``, ``train_flops(m, seq_len)``: per layer, the parameters
  that enter a matrix product, the (bf16, f32) counts of the others, the
  operations and bytes a decode step needs beyond its matrix products and
  weights, and the training operations per token beyond them.

A weight's name is unique over all kinds, so the benchmark's tree keeps
one flat ``layers`` dict: kind ``k``'s weights are stacked over the layers
that hold it, in layer order.
"""
from __future__ import annotations

import functools
import importlib
from collections import Counter

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32

#: a file without ``layer_types``: the kind of every layer, by ``family``
FAMILY_LAYER = {"dense": "attention", "ssm": "mamba"}
#: keys that choose a layer's path, and the values a file may state
CHOICES = {"position_embedding_type": ("rope", "nope"),
           "mamba_conv_bias": (True,)}


def normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def layer_types(m: dict) -> list[str]:
    """The mixer kind of each layer; a path the layers do not have is
    refused here, once."""
    for key, allowed in CHOICES.items():
        if key in m and m[key] not in allowed:
            raise ValueError(f"{key} {m[key]!r} is not one of {allowed}")
    if "layer_types" in m:
        types = list(m["layer_types"])
    else:
        types = [FAMILY_LAYER[m["family"]]] * m["num_hidden_layers"]
    if len(types) != m["num_hidden_layers"]:
        raise ValueError(f"{len(types)} layer_types for "
                         f"{m['num_hidden_layers']} layers")
    return types


def layer_parts(m: dict) -> list[tuple[str, ...]]:
    """Each layer's kinds in order: its mixer, then ``mlp`` where the
    model has one."""
    mlp = m.get("intermediate_size", 0) > 0
    return [(t, "mlp") if mlp else (t,) for t in layer_types(m)]


def kind_counts(m: dict) -> Counter:
    """How many layers hold each kind, the kinds in the order they first
    appear in the stack (the order of the weight draw's keys)."""
    return Counter(k for parts in layer_parts(m) for k in parts)


def rows(m: dict) -> dict[str, dict[int, int]]:
    """Per kind, layer index -> its row in the kind's stacked weights."""
    out: dict[str, dict[int, int]] = {}
    for i, parts in enumerate(layer_parts(m)):
        for k in parts:
            row = out.setdefault(k, {})
            row[i] = len(row)
    return out


@functools.cache
def kind(name: str):
    """``bench/layers/<name>.py``."""
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as err:
        raise ValueError(f"no layer kind {name!r} in bench/layers") from err


def frozen(m: dict) -> tuple:
    """The model's scalar keys and lists, hashable (a jit's static key)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()
                        if isinstance(v, (int, float, str, list))))


def norm_names(m: dict) -> frozenset:
    """Names of every norm scale of the model, ``ln_f`` included."""
    return frozenset({"ln_f"}).union(*(kind(k).NORMS for k in kind_counts(m)))
