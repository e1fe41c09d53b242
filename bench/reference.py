"""Plain references: the models' forward passes (and for training, the loss,
its gradient and AdamW) in straightforward ``jax.numpy`` and float32, with
no kernels, cache or batching, from the published descriptions.  Nothing
here imports the program.

A model is its list of layers (``bench/layers``): each layer applies its
kinds' reference functions in turn, found by name.  Granite's multipliers
apply as the configuration file states them: the embedding times
``embedding_multiplier``, attention scores times ``attention_multiplier``,
each block's output times ``residual_multiplier`` before it joins the
residual, and the logits over ``logits_scaling`` (each 1 where the file
does not state it).  Where the program departs from the published model,
the file says so (``departures``) and the reference follows the file.

A matrix product goes through ``mm``: ``"f32"`` is float32 at the highest
precision; ``"fp8"`` rounds both operands to scaled float8 e4m3 first (the
weights per tensor, the activations per row), the precision below the
configurations' bfloat16, and serves as the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.layers import frozen, kind, layer_parts, norm_names, rows
from bench.weights import flat

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _fp8(x: jax.Array, axis) -> jax.Array:
    """``x`` rounded to scaled float8 e4m3.  The gradient passes the
    rounding unchanged (straight through), so a backward pass multiplies
    by the rounded operands without flushing small cotangents to zero."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x), axis=axis, keepdims=True))
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm(a: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    a, w = a.astype(F32), w.astype(F32)
    if mode == "fp8":
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=HIGHEST)


def rms_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotate-half form; x: (B, S, H, D)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _layer_at(layers: dict, i) -> dict:
    return jax.tree.map(lambda a: a[i], layers)


def _kind_weights(layers: dict, name: str) -> dict:
    """The stacked weights of kind ``name`` out of the flat ``layers``."""
    return {k: layers[k] for k in kind(name).LAYOUT}


@functools.lru_cache(maxsize=None)
def _jitted(frozen_m: tuple, mode: str):
    m = dict(frozen_m)

    @jax.jit
    def embed(w_embed, tokens):
        return w_embed[tokens].astype(F32) * m.get("embedding_multiplier", 1.0)

    @functools.partial(jax.jit, static_argnums=(0,))
    def one_part(name, x, stacked, row):
        return kind(name).forward(x, _layer_at(stacked, row), m, mode)

    @jax.jit
    def head(x, ln_f, w_embed, read):
        """Per position: the best logit, and the logit of ``read``."""
        h = rms_norm(x, ln_f, m["rms_norm_eps"])
        logits = mm(h, w_embed.T, mode) / m.get("logits_scaling", 1.0)
        best = jnp.max(logits, -1)
        at = jnp.take_along_axis(logits, read[..., None], -1)[..., 0]
        return best, at, jnp.argmax(logits, -1)

    return embed, one_part, head


def logits_summary(w: dict, m: dict, tokens: jax.Array, read: jax.Array,
                   mode: str = "f32") -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the model over ``tokens`` (B, S) layer by layer, each layer's
    kinds in turn.  Returns, per position, the best logit, the logit of
    the token ``read`` names, and the first token."""
    embed, one_part, head = _jitted(frozen(m), mode)
    x = embed(w["embed"], tokens)
    at = rows(m)
    for i, parts in enumerate(layer_parts(m)):
        for name in parts:
            x = one_part(name, x, _kind_weights(w["layers"], name), at[name][i])
    return head(x, w["ln_f"], w["embed"], read)


# ---------------------------------------------------------------------------
# training: loss, gradient, AdamW
# ---------------------------------------------------------------------------


def lm_loss(w: dict, inputs: jax.Array, targets: jax.Array, m: dict,
            z_loss: float, mode: str = "f32") -> jax.Array:
    """Mean over tokens of cross entropy plus ``z_loss`` * logsumexp^2."""
    x = w["embed"][inputs].astype(F32) * m.get("embedding_multiplier", 1.0)
    at = rows(m)
    for i, parts in enumerate(layer_parts(m)):
        for name in parts:
            layer = jax.checkpoint(functools.partial(kind(name).forward, m=m,
                                                     mode=mode))
            x = layer(x, _layer_at(_kind_weights(w["layers"], name), at[name][i]))
    h = rms_norm(x, w["ln_f"], m["rms_norm_eps"])
    logits = mm(h, w["embed"].T, mode) / m.get("logits_scaling", 1.0)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - gold + z_loss * lse ** 2)


@functools.lru_cache(maxsize=None)
def _row_grad(frozen: tuple, z_loss: float, mode: str):
    m = dict(frozen)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def acc_grad(acc, w, inputs, targets, weight):
        loss, g = jax.value_and_grad(lm_loss)(w, inputs, targets, m,
                                                 z_loss, mode)
        return jax.tree.map(lambda a, b: a + weight * b, acc, g), loss

    return acc_grad


def loss_and_grad(w: dict, batch: dict, m: dict, z_loss: float,
                  mode: str = "f32"):
    """Mean loss and gradient over the batch's rows, one row at a time."""
    acc_grad = _row_grad(frozen(m), float(z_loss), mode)
    rows = batch["inputs"].shape[0]
    acc = jax.tree.map(jnp.zeros_like, w)
    losses = []
    for r in range(rows):
        acc, loss = acc_grad(acc, w, batch["inputs"][r:r + 1],
                             batch["targets"][r:r + 1], 1.0 / rows)
        losses.append(loss)
    return float(jnp.mean(jnp.stack(losses))), acc


def lr_at(count: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio`` of the peak."""
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * frac))
    return opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos)


@jax.jit
def norms(tree):
    """The Frobenius norm of each leaf, in float32."""
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))), tree)


@jax.jit
def diff_norms(a, b):
    """Per leaf, the Frobenius norm of ``a - b``, in float32."""
    return jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32) - y.astype(F32)))), a, b)


def leaf_norms(tree: dict) -> dict:
    """Norm of each leaf of a tree in the benchmark's layout, by name."""
    return {k: float(v) for k, v in flat(norms(tree)).items()}


def change_norms(after: dict, before: dict) -> dict:
    """Per leaf of the benchmark's layout, the norm of ``after - before``."""
    return {k: float(v) for k, v in flat(diff_norms(after, before)).items()}


@functools.partial(jax.jit, donate_argnums=(0, 2, 3),
                   static_argnames=("decay_mask",))
def _adamw(w, g, mu, nu, scale, lr, b1c, b2c, b1, b2, eps, wd, *, decay_mask):
    def upd(p, gg, m_, v_, decay):
        gg = gg * scale
        m_ = b1 * m_ + (1 - b1) * gg
        v_ = b2 * v_ + (1 - b2) * gg * gg
        step = (m_ / b1c) / (jnp.sqrt(v_ / b2c) + eps)
        if decay:
            step = step + wd * p
        return p - lr * step, m_, v_

    flat_w, td = jax.tree.flatten(w)
    out = [upd(p, gg, m_, v_, dm) for p, gg, m_, v_, dm in
           zip(flat_w, jax.tree.leaves(g), jax.tree.leaves(mu),
               jax.tree.leaves(nu), decay_mask)]
    return tuple(jax.tree.unflatten(td, [o[i] for o in out]) for i in range(3))


def adamw_steps(w: dict, batches: list[dict], m: dict, opt: dict, *,
                mode: str = "f32"):
    """AdamW with global-norm clipping, one step per batch, in float32.

    Weight decay applies to the matrices and the embedding, not to norm
    scales.  Returns the loss before each step, the norm of each leaf of
    the first gradient as clipped for the optimizer, and the weights after
    the last step.  ``w`` is consumed."""
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    norms_ = norm_names(m)
    decay_mask = tuple(getattr(path[-1], "key", None) not in norms_
                       for path, _ in jax.tree_util.tree_leaves_with_path(w))
    losses, first_grad = [], None
    for count, batch in enumerate(batches, start=1):
        loss, g = loss_and_grad(w, batch, m, opt["z_loss"], mode)
        losses.append(loss)
        gnorm = math.sqrt(sum(v * v for v in leaf_norms(g).values()))
        scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
        if first_grad is None:
            first_grad = {k: v * scale for k, v in leaf_norms(g).items()}
        w, mu, nu = _adamw(w, g, mu, nu, scale, lr_at(count, opt),
                           1 - opt["b1"] ** count, 1 - opt["b2"] ** count,
                           opt["b1"], opt["b2"], opt["eps"],
                           opt["weight_decay"], decay_mask=decay_mask)
        del g
    return losses, first_grad, w
