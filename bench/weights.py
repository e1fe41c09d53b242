"""Seeded weights, made on the device in one jitted call, in the types they
are served in, and laid out the way the model's published description
names them.  ``to_program`` hands the same arrays to the program in its own
tree; the plain reference reads the benchmark's layout.

Initialisation follows the published configs' ``initializer_range``:
matrices and the embedding are normal with that standard deviation, norm
scales are one.  Mamba-2's SSM parameters follow its paper's code: A drawn
uniformly from [1, 16] (stored as its log), dt from a log-uniform
[1e-3, 1e-1] (stored as the inverse softplus of dt), D one, the
depthwise convolution uniform over +-1/sqrt(width), and ``out_proj``
scaled down by sqrt(layers).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

BF16, F32 = jnp.bfloat16, jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, however many bits it has."""
    word = int(np.random.SeedSequence([int(seed)]).generate_state(1)[0])
    return jax.random.PRNGKey(word >> 1)


def ssm_dims(m: dict) -> dict:
    d_inner = m["expand"] * m["hidden_size"]
    heads = d_inner // m["head_dim"]
    gn = m["n_groups"] * m["state_size"]
    return {"d_inner": d_inner, "heads": heads, "gn": gn,
            "conv_dim": d_inner + 2 * gn,
            "d_in_proj": 2 * d_inner + 2 * gn + heads}


def _normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _dense_init(key, m: dict) -> dict:
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    ff, std = m["intermediate_size"], m["initializer_range"]
    k = jax.random.split(key, 8)
    return {
        "embed": _normal(k[0], (V, d), std),
        "ln_f": jnp.ones((d,), F32),
        "layers": {
            "ln_attn": jnp.ones((L, d), F32),
            "q": _normal(k[1], (L, d, H * hd), std),
            "k": _normal(k[2], (L, d, KV * hd), std),
            "v": _normal(k[3], (L, d, KV * hd), std),
            "o": _normal(k[4], (L, H * hd, d), std),
            "ln_mlp": jnp.ones((L, d), F32),
            "gate": _normal(k[5], (L, d, ff), std),
            "up": _normal(k[6], (L, d, ff), std),
            "down": _normal(k[7], (L, ff, d), std),
        },
    }


def _ssm_init(key, m: dict) -> dict:
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    std, W = m["initializer_range"], m["conv_kernel"]
    s = ssm_dims(m)
    H = s["heads"]
    k = jax.random.split(key, 7)
    dt = jnp.exp(jax.random.uniform(k[4], (L, H), F32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    bound = 1.0 / math.sqrt(W)
    return {
        "embed": _normal(k[0], (V, d), std),
        "ln_f": jnp.ones((d,), F32),
        "layers": {
            "ln": jnp.ones((L, d), F32),
            "in_proj": _normal(k[1], (L, d, s["d_in_proj"]), std),
            "conv_w": jax.random.uniform(k[2], (L, W, s["conv_dim"]), F32,
                                         -bound, bound).astype(BF16),
            "conv_b": jax.random.uniform(k[3], (L, s["conv_dim"]), F32,
                                         -bound, bound).astype(BF16),
            "A_log": jnp.log(jax.random.uniform(k[5], (L, H), F32, 1.0, 16.0)),
            "D": jnp.ones((L, H), F32),
            # inverse softplus: softplus(dt_bias) == dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((L, s["d_inner"]), F32),
            "out_proj": _normal(k[6], (L, s["d_inner"], d),
                                std / math.sqrt(L)),
        },
    }


_INIT = {"dense": _dense_init, "ssm": _ssm_init}


def make_weights(model: dict, seed: int) -> dict:
    """All weights of ``model`` from ``seed``, in one jitted call."""
    fn = jax.jit(functools.partial(_init_from, _INIT[model["family"]],
                                   _frozen(model)))
    return fn(seed_key(seed))


def _frozen(model: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str))))


def _init_from(init, frozen, key):
    return init(key, dict(frozen))


def program_weights(model: dict, seed: int) -> dict:
    """``to_program(make_weights(model, seed), ...)`` in one jitted call."""
    init, frozen = _INIT[model["family"]], _frozen(model)
    fn = jax.jit(lambda key: to_program(_init_from(init, frozen, key),
                                        model["family"]))
    return fn(seed_key(seed))


#: where each of the benchmark's layer weights sits in the program's tree
_LAYOUT = {
    "dense": {"ln_attn": ("ln1",), "q": ("attn", "wq"), "k": ("attn", "wk"),
              "v": ("attn", "wv"), "o": ("attn", "wo"), "ln_mlp": ("ln2",),
              "gate": ("ffn", "w1"), "up": ("ffn", "w3"), "down": ("ffn", "w2")},
    "ssm": {"ln": ("ln1",), "in_proj": ("ssd", "in_proj"),
            "conv_w": ("ssd", "conv_w"), "conv_b": ("ssd", "conv_b"),
            "A_log": ("ssd", "a_log"), "D": ("ssd", "d_skip"),
            "dt_bias": ("ssd", "dt_bias"), "norm": ("ssd", "norm"),
            "out_proj": ("ssd", "out_proj")},
}
#: norm scales, which the program stores as (scale - 1)
NORMS = frozenset({"ln_f", "ln_attn", "ln_mlp", "ln", "norm"})


def to_program(w: dict, family: str) -> dict:
    """The program's parameter tree over the same arrays."""
    layer: dict = {}
    for name, path in _LAYOUT[family].items():
        a = w["layers"][name]
        node = layer
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = a - 1.0 if name in NORMS else a
    return {"embed": w["embed"], "final_norm": w["ln_f"] - 1.0,
            "segments": [{"0": layer}]}


def flat(w: dict) -> dict:
    """The benchmark's tree as {name: leaf}, layer weights as ``layers.<name>``."""
    out = {"embed": w["embed"], "ln_f": w["ln_f"]}
    out.update({f"layers.{k}": v for k, v in w["layers"].items()})
    return out


def flat_from_program(tree: dict, family: str) -> dict:
    """A tree shaped like the program's parameters (its weights, or
    anything that mirrors them), as {benchmark name: leaf}.  Norm leaves
    keep the program's (scale - 1) form."""
    layer = tree["segments"][0]["0"]
    out = {"embed": tree["embed"], "ln_f": tree["final_norm"]}
    for name, path in _LAYOUT[family].items():
        node = layer
        for key in path:
            node = node[key]
        out[f"layers.{name}"] = node
    return out


def check_layout(tree: dict, like: dict) -> None:
    """Raise unless ``tree`` has ``like``'s structure, shapes and dtypes."""
    got = jax.tree_util.tree_structure(tree)
    want = jax.tree_util.tree_structure(like)
    if got != want:
        raise ValueError(f"weight tree {got} is not the program's {want}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(tree),
                            jax.tree.leaves(like)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"{jax.tree_util.keystr(path)}: "
                             f"{a.shape} {a.dtype} is not the program's "
                             f"{b.shape} {b.dtype}")
