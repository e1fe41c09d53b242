"""Seeded weights, made on the device in one jitted call, in the types they
are served in, and laid out the way the model's published description
names them.  ``to_program`` hands the same arrays to the program in its own
tree; the plain reference reads the benchmark's layout.

Each layer kind draws its own weights (``bench/layers/<kind>.py``):
matrices and the embedding are normal with the published configs'
``initializer_range``, norm scales are one.  The seed's key is split once,
into the embedding's key and each kind's keys, kind by kind in the order
the kinds first appear in the stack; each kind draws all of its layers at
once, stacked in layer order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.layers import (F32, frozen, kind, kind_counts, layer_parts,
                          norm_names, normal, rows)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, however many bits it has."""
    word = int(np.random.SeedSequence([int(seed)]).generate_state(1)[0])
    return jax.random.PRNGKey(word >> 1)


def _init(frozen_m: tuple, key) -> dict:
    m = dict(frozen_m)
    counts = kind_counts(m)
    keys = jax.random.split(key, 1 + sum(kind(k).KEYS for k in counts))
    layers: dict = {}
    at = 1
    for k, n in counts.items():
        mod = kind(k)
        layers.update(mod.init(keys[at:at + mod.KEYS], m, n))
        at += mod.KEYS
    return {"embed": normal(keys[0], (m["vocab_size"], m["hidden_size"]),
                            m["initializer_range"]),
            "ln_f": jnp.ones((m["hidden_size"],), F32),
            "layers": layers}


def make_weights(model: dict, seed: int) -> dict:
    """All weights of ``model`` from ``seed``, in one jitted call."""
    return jax.jit(functools.partial(_init, frozen(model)))(seed_key(seed))


def program_weights(model: dict, cfg, seed: int) -> dict:
    """``to_program(make_weights(model, seed), model, cfg)`` in one jitted
    call; ``cfg`` is the program's ``ModelConfig``."""
    fz = frozen(model)
    return jax.jit(lambda key: to_program(_init(fz, key), model, cfg))(
        seed_key(seed))


def slots(m: dict, cfg) -> list[list[list[int]]]:
    """Per scan segment of the program (``cfg.scan_segments()``), per unit
    position, the layers that sit there, one per repeat."""
    parts = layer_parts(m)
    out, first = [], 0
    for unit, repeats in cfg.scan_segments():
        seg = []
        for u in range(len(unit)):
            layers = [first + r * len(unit) + u for r in range(repeats)]
            if any(parts[i] != parts[layers[0]] for i in layers):
                raise ValueError(f"layers {layers} share a scan slot but "
                                 "not their kinds")
            seg.append(layers)
        out.append(seg)
        first += len(unit) * repeats
    if first != len(parts):
        raise ValueError(f"the program's segments hold {first} layers, "
                         f"the model {len(parts)}")
    return out


def _rows_of(stack: jax.Array, picked: list[int]) -> jax.Array:
    """The rows ``picked`` of a kind's stack; the stack itself where they
    are all of it, in order, so that a one-kind model's weights are not
    gathered into a second copy."""
    if picked == list(range(stack.shape[0])):
        return stack
    return stack[np.asarray(picked)]


def to_program(w: dict, m: dict, cfg) -> dict:
    """The program's parameter tree over the same arrays: layer i's
    weights go to the segment, unit position and repeat at which
    ``cfg.scan_segments()`` runs it."""
    parts, at, norms = layer_parts(m), rows(m), norm_names(m)
    segments = []
    for seg in slots(m, cfg):
        tree = {}
        for u, layers in enumerate(seg):
            block: dict = {}
            for k in parts[layers[0]]:
                picked = [at[k][i] for i in layers]
                for name, path in kind(k).LAYOUT.items():
                    a = _rows_of(w["layers"][name], picked)
                    node = block
                    for key in path[:-1]:
                        node = node.setdefault(key, {})
                    node[path[-1]] = a - 1.0 if name in norms else a
            tree[str(u)] = block
        segments.append(tree)
    return {"embed": w["embed"], "final_norm": w["ln_f"] - 1.0,
            "segments": segments}


def flat(w: dict) -> dict:
    """The benchmark's tree as {name: leaf}, layer weights as ``layers.<name>``."""
    out = {"embed": w["embed"], "ln_f": w["ln_f"]}
    out.update({f"layers.{k}": v for k, v in w["layers"].items()})
    return out


def flat_from_program(tree: dict, m: dict, cfg) -> dict:
    """A tree shaped like the program's parameters (its weights, or
    anything that mirrors them), as {benchmark name: leaf}, each layer
    weight stacked in layer order.  Norm leaves keep the program's
    (scale - 1) form."""
    parts = layer_parts(m)
    pieces: dict[str, list] = {}
    for seg, seg_slots in zip(tree["segments"], slots(m, cfg)):
        for u, layers in enumerate(seg_slots):
            for k in parts[layers[0]]:
                for name, path in kind(k).LAYOUT.items():
                    node = seg[str(u)]
                    for key in path:
                        node = node[key]
                    pieces.setdefault(name, []).append((layers, node))
    out = {"embed": tree["embed"], "ln_f": tree["final_norm"]}
    for name, got in pieces.items():
        if len(got) == 1:
            out[f"layers.{name}"] = got[0][1]
            continue
        order = np.argsort([i for layers, _ in got for i in layers])
        out[f"layers.{name}"] = jnp.concatenate([a for _, a in got])[order]
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
