"""Model assembly: TransformerLM over per-layer block kinds.

Layers are grouped into scan *segments* (``ModelConfig.scan_segments``):
each segment stacks its parameters along a leading axis and is executed
with ``jax.lax.scan`` so compile time and HLO size are O(#segments), not
O(#layers).  Within a segment's scan body the (mixer, ffn) unit is applied
position by position (unit lengths are tiny: 1–6).

Public API (all pure functions, bound to a ModelConfig):

* ``param_defs(cfg)``                       — ParamDef tree
* ``forward_train(params, batch, cfg)``     — logits (+ aux losses)
* ``loss_fn(params, batch, cfg)``           — scalar fp32 loss (chunked CE)
* ``cache_defs(cfg, batch, seq_len)``       — decode-state ParamDef tree
* ``decode_step(params, state, batch, cfg)``— one-token serve step
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import griffin, moe as moe_mod, ssm
from repro.models.config import BlockKind, ModelConfig
from repro.models.layers import (
    attention_decode,
    attention_train,
    constrain,
    make_attention_defs,
    make_ffn_defs,
    make_mla_defs,
    make_norm_def,
    mla_decode,
    mla_train,
    rms_norm,
)
from repro.models.spec import ParamDef, pdef, stack_defs

# ---------------------------------------------------------------------------
# per-block parameter trees
# ---------------------------------------------------------------------------


def block_defs(cfg: ModelConfig, kind: BlockKind, *, cross: bool = False) -> dict:
    mixer, ffn = kind
    d: dict[str, Any] = {"ln1": make_norm_def(cfg.d_model)}
    if mixer in ("attn", "swa", "bidir"):
        d["attn"] = make_attention_defs(cfg)
    elif mixer == "mla":
        d["attn"] = make_mla_defs(cfg)
    elif mixer == "ssd":
        d["ssd"] = ssm.make_ssd_defs(cfg)
    elif mixer == "rglru":
        d["rglru"] = griffin.make_rglru_defs(cfg)
    else:  # pragma: no cover
        raise ValueError(mixer)
    if cross:
        d["ln_x"] = make_norm_def(cfg.d_model)
        d["cross"] = make_attention_defs(cfg, cross=True)
    if ffn == "dense":
        d["ln2"] = make_norm_def(cfg.d_model)
        d["ffn"] = make_ffn_defs(cfg.d_model, cfg.d_ff)
    elif ffn == "moe":
        d["ln2"] = make_norm_def(cfg.d_model)
        d["moe"] = moe_mod.make_moe_defs(cfg)
    return d


def _apply_ffn(params: dict, x: jax.Array, cfg: ModelConfig,
               kind: BlockKind) -> tuple[jax.Array, jax.Array]:
    _, ffn = kind
    aux = jnp.zeros((), jnp.float32)
    if ffn == "none":
        return x, aux
    with jax.named_scope("ffn"):
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        if ffn == "dense":
            from repro.models.layers import swiglu
            y = swiglu(params["ffn"], h)
        else:
            y, aux = moe_mod.moe_ffn(params["moe"], h, cfg)
        return x + y, aux


def _mixer_scope(mixer: str) -> str:
    """The named scope of a mixer's ops: ``attn`` for every attention
    kind, else the mixer's own name (``ssd``, ``rglru``)."""
    return mixer if mixer in ("ssd", "rglru") else "attn"


def block_train(params: dict, x: jax.Array, cfg: ModelConfig, kind: BlockKind,
                *, enc_out: jax.Array | None = None,
                bidirectional: bool = False) -> tuple[jax.Array, jax.Array]:
    mixer, _ = kind
    with jax.named_scope(_mixer_scope(mixer)):
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        if mixer in ("attn", "bidir"):
            y = attention_train(params["attn"], h, cfg,
                                bidirectional=bidirectional or mixer == "bidir")
        elif mixer == "swa":
            y = attention_train(params["attn"], h, cfg, window=cfg.window)
        elif mixer == "mla":
            y = mla_train(params["attn"], h, cfg)
        elif mixer == "ssd":
            y = ssm.ssd_block_train(params["ssd"], h, cfg)
        else:
            y = griffin.rglru_block_train(params["rglru"], h, cfg)
        x = x + y
    if enc_out is not None and "cross" in params:
        h = rms_norm(x, params["ln_x"], cfg.norm_eps)
        x = x + attention_train(params["cross"], h, cfg, kv_source=enc_out)
    x, aux = _apply_ffn(params, x, cfg, kind)
    x = constrain(x, ("batch", "seq_res", "d_model"))
    return x, aux


def block_decode(params: dict, x: jax.Array, cache: dict, cfg: ModelConfig,
                 kind: BlockKind, *, cross_memory: dict | None = None
                 ) -> tuple[jax.Array, dict]:
    mixer, _ = kind
    new_cache = dict(cache)
    with jax.named_scope(_mixer_scope(mixer)):
        h = rms_norm(x, params["ln1"], cfg.norm_eps)
        if mixer in ("attn", "swa"):
            y, c = attention_decode(params["attn"], h, cache["attn"], cfg,
                                    window=cfg.window if mixer == "swa" else 0)
            new_cache["attn"] = c
        elif mixer == "mla":
            y, c = mla_decode(params["attn"], h, cache["attn"], cfg)
            new_cache["attn"] = c
        elif mixer == "ssd":
            y, c = ssm.ssd_block_decode(params["ssd"], h, cache["ssd"], cfg)
            new_cache["ssd"] = c
        else:
            y, c = griffin.rglru_block_decode(params["rglru"], h,
                                              cache["rglru"], cfg)
            new_cache["rglru"] = c
        x = x + y
    mem = cross_memory if cross_memory is not None else cache.get("cross")
    if mem is not None and "cross" in params:
        h = rms_norm(x, params["ln_x"], cfg.norm_eps)
        y, _ = attention_decode(params["cross"], h, {}, cfg, cross_memory=mem)
        x = x + y
    x, _ = _apply_ffn(params, x, cfg, kind)
    return x, new_cache


# ---------------------------------------------------------------------------
# cache parameter trees (decode state)
# ---------------------------------------------------------------------------


def _block_cache_defs(cfg: ModelConfig, kind: BlockKind, batch: int,
                      seq_len: int) -> dict:
    mixer, _ = kind
    hd, kv = cfg.resolved_head_dim, cfg.n_kv_heads
    # enc-dec decoder blocks carry a static cross-attention KV memory
    # (precomputed from the encoder output at prefill time)
    cross: dict = {}
    if cfg.encoder_layers:
        cross = {"cross": {
            "k": pdef((batch, "batch"), (seq_len, "seq"), (kv, "kv_heads"),
                      (hd, None), init="zeros"),
            "v": pdef((batch, "batch"), (seq_len, "seq"), (kv, "kv_heads"),
                      (hd, None), init="zeros"),
        }}
    if mixer in ("attn", "bidir"):
        smax = seq_len
        return {"attn": {
            "k": pdef((batch, "batch"), (smax, "seq"), (kv, "kv_heads"), (hd, None),
                      init="zeros"),
            "v": pdef((batch, "batch"), (smax, "seq"), (kv, "kv_heads"), (hd, None),
                      init="zeros"),
            "len": pdef(init="zeros", dtype=jnp.int32),
        }, **cross}
    if mixer == "swa":
        smax = min(cfg.window, seq_len)
        return {"attn": {
            "k": pdef((batch, "batch"), (smax, None), (kv, "kv_heads"), (hd, None),
                      init="zeros"),
            "v": pdef((batch, "batch"), (smax, None), (kv, "kv_heads"), (hd, None),
                      init="zeros"),
            "len": pdef(init="zeros", dtype=jnp.int32),
        }}
    if mixer == "mla":
        m = cfg.mla
        return {"attn": {
            "ckv": pdef((batch, "batch"), (seq_len, "seq"), (m.kv_lora_rank, None),
                        init="zeros"),
            "k_rope": pdef((batch, "batch"), (seq_len, "seq"),
                           (m.qk_rope_head_dim, None), init="zeros"),
            "len": pdef(init="zeros", dtype=jnp.int32),
        }}
    if mixer == "ssd":
        s = cfg.ssm
        dims = ssm.ssm_dims(cfg)
        return {"ssd": {
            "conv": pdef((batch, "batch"), (s.conv_width - 1, None),
                         (dims["conv_dim"], "heads"), init="zeros"),
            "state": pdef((batch, "batch"), (dims["n_heads"], "heads"),
                          (s.head_dim, None), (s.d_state, None), init="zeros"),
        }}
    if mixer == "rglru":
        g = cfg.rglru
        w = griffin.rglru_dims(cfg)["lru_width"]
        return {"rglru": {
            "conv": pdef((batch, "batch"), (g.conv_width - 1, None), (w, "d_ff"),
                         init="zeros"),
            "h": pdef((batch, "batch"), (w, "d_ff"), init="zeros"),
        }}
    raise ValueError(mixer)  # pragma: no cover


# ---------------------------------------------------------------------------
# whole-model parameter trees
# ---------------------------------------------------------------------------


def param_defs(cfg: ModelConfig) -> dict:
    cfg.validate()
    cross = cfg.encoder_layers > 0
    segments = cfg.scan_segments()
    defs: dict[str, Any] = {
        "embed": pdef((cfg.vocab_size, "vocab"), (cfg.d_model, "d_model"),
                      scale=1.0),
        "final_norm": make_norm_def(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        defs["head"] = pdef((cfg.d_model, "d_model"), (cfg.vocab_size, "vocab"))
    defs["segments"] = [
        {str(u): stack_defs(block_defs(cfg, kind, cross=cross), repeats)
         for u, kind in enumerate(unit)}
        for unit, repeats in segments
    ]
    if cross:
        enc_kind: BlockKind = ("bidir", "dense")
        defs["encoder"] = {
            "blocks": stack_defs(block_defs(cfg, enc_kind), cfg.encoder_layers),
            "final_norm": make_norm_def(cfg.d_model),
        }
    if cfg.mtp:
        defs["mtp"] = {
            "proj": pdef((2 * cfg.d_model, "d_model"), (cfg.d_model, "d_model")),
            "block": block_defs(cfg, (cfg.pattern[-1][0], "dense")),
            "norm_h": make_norm_def(cfg.d_model),
            "norm_e": make_norm_def(cfg.d_model),
        }
    return defs


def cache_defs(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """Decode-state tree matching the segment structure."""
    cfg.validate()
    segs = cfg.scan_segments()
    return {
        "segments": [
            {str(u): stack_defs(_block_cache_defs(cfg, kind, batch, seq_len),
                                repeats)
             for u, kind in enumerate(unit)}
            for unit, repeats in segs
        ],
    }


# ---------------------------------------------------------------------------
# forward (train)
# ---------------------------------------------------------------------------


def _run_segments_train(params: dict, x: jax.Array, cfg: ModelConfig, *,
                        enc_out: jax.Array | None, remat: bool) -> tuple[jax.Array, jax.Array]:
    aux_total = jnp.zeros((), jnp.float32)
    for seg_params, (unit, repeats) in zip(params["segments"], cfg.scan_segments()):
        def body(carry, layer_params, _unit=unit):
            h, aux = carry
            for u, kind in enumerate(_unit):
                h, a = block_train(layer_params[str(u)], h, cfg, kind,
                                   enc_out=enc_out)
                aux = aux + a
            return (h, aux), None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("layers"):
            if repeats == 1:
                squeezed = jax.tree.map(lambda p: p[0], seg_params)
                (x, aux_total), _ = body((x, aux_total), squeezed)
            else:
                (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                                 seg_params)
    return x, aux_total


def embed_inputs(params: dict, batch: dict, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("embed"):
        if cfg.input_kind == "embeds":
            x = batch["embeds"]
        else:
            x = params["embed"][batch["inputs"]]
        return constrain(x.astype(cfg.cdtype), ("batch", "seq", "d_model"))


def _encoder_forward(params: dict, batch: dict, cfg: ModelConfig, *,
                     remat: bool) -> jax.Array:
    enc = params["encoder"]
    x = constrain(batch["enc_embeds"].astype(cfg.cdtype),
                  ("batch", "seq", "d_model"))

    def body(carry, layer_params):
        h, = carry
        h, _ = block_train(layer_params, h, cfg, ("bidir", "dense"),
                           bidirectional=True)
        return (h,), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x,), _ = jax.lax.scan(body, (x,), enc["blocks"])
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def forward_train(params: dict, batch: dict, cfg: ModelConfig, *,
                  remat: bool = True) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (hidden (B,S,d), enc_out|None, aux_loss)."""
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _encoder_forward(params, batch, cfg, remat=remat)
    x = embed_inputs(params, batch, cfg)
    x, aux = _run_segments_train(params, x, cfg, enc_out=enc_out, remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, enc_out, aux


def _logits(params: dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("logits"):
        w = params["embed"].T if cfg.tie_embeddings else params["head"]
        return (h @ w.astype(h.dtype)).astype(jnp.float32)


def _ce_chunk(params: dict, h: jax.Array, targets: jax.Array,
              cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Cross entropy + z-loss for one sequence chunk; returns (sum_ce, count)."""
    logits = _logits(params, h, cfg)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = lse - gold
    zloss = 1e-4 * lse ** 2
    valid = (targets >= 0).astype(jnp.float32)
    return jnp.sum((ce + zloss) * valid), jnp.sum(valid)


def loss_fn(params: dict, batch: dict, cfg: ModelConfig, *,
            remat: bool = True, ce_chunk: int = 512) -> tuple[jax.Array, dict]:
    h, enc_out, aux = forward_train(params, batch, cfg, remat=remat)
    targets = batch["targets"]
    b, s = targets.shape
    if ce_chunk and s > ce_chunk and s % ce_chunk == 0:
        nc = s // ce_chunk
        hc = h.reshape(b, nc, ce_chunk, cfg.d_model).swapaxes(0, 1)
        tc = targets.reshape(b, nc, ce_chunk).swapaxes(0, 1)

        def body(carry, xs):
            tot, cnt = carry
            hh, tt = xs
            l, c = _ce_chunk(params, hh, tt, cfg)
            return (tot + l, cnt + c), None

        body = jax.checkpoint(body, prevent_cse=False) if remat else body
        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (hc, tc))
    else:
        tot, cnt = _ce_chunk(params, h, targets, cfg)
    loss = tot / jnp.maximum(cnt, 1.0)

    metrics = {"ce_loss": loss, "aux_loss": aux}
    if cfg.mtp:
        mtp_loss = _mtp_loss(params, h, batch, cfg)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    return loss + aux, metrics


def _mtp_loss(params: dict, h: jax.Array, batch: dict, cfg: ModelConfig) -> jax.Array:
    """DeepSeek-V3 multi-token prediction: one extra depth predicting t+2.

    h'_t = W [RMSNorm(h_t) ; RMSNorm(Emb(target_{t+1}))] -> block -> head.
    """
    mtp = params["mtp"]
    targets = batch["targets"]
    # teacher embedding of the next token (shift targets left by one)
    nxt = jnp.concatenate([targets[:, 1:], targets[:, -1:]], axis=1)
    e = params["embed"][jnp.maximum(nxt, 0)].astype(h.dtype)
    # anchor the gather output sharding (otherwise SPMD replicates the
    # full (B,S,d) lookup while resharding - XLA b/433785288)
    e = constrain(e, ("batch", "seq_res", "d_model"))
    hn = rms_norm(h, mtp["norm_h"], cfg.norm_eps)
    en = rms_norm(e, mtp["norm_e"], cfg.norm_eps)
    hm = jnp.concatenate([hn, en], axis=-1) @ mtp["proj"]
    hm, _ = block_train(mtp["block"], hm, cfg, (cfg.pattern[-1][0], "dense"))
    # predict t+2: shift targets by 2
    t2 = jnp.concatenate([targets[:, 2:], targets[:, -2:]], axis=1)
    tot, cnt = _ce_chunk(params, hm, t2, cfg)
    return tot / jnp.maximum(cnt, 1.0)


def block_prefill(params: dict, x: jax.Array, cfg: ModelConfig,
                  kind: BlockKind, *, seq_len: int,
                  enc_out: jax.Array | None = None
                  ) -> tuple[jax.Array, dict]:
    """Like block_train but also captures the decode cache (prefill path)."""
    mixer, _ = kind
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    entry: dict
    s = x.shape[1]
    if mixer == "attn":
        y, kvs = attention_train(params["attn"], h, cfg, return_kv=True)
        entry = {"attn": {**kvs, "len": jnp.asarray(s, jnp.int32)}}
    elif mixer == "swa":
        y, kvs = attention_train(params["attn"], h, cfg, window=cfg.window,
                                 return_kv=True)
        w = min(cfg.window, seq_len)
        if s > w:
            # ring-buffer layout: token p lives at slot p % w
            kvs = {k: jnp.roll(v[:, -w:], s % w, axis=1) for k, v in kvs.items()}
        entry = {"attn": {**kvs, "len": jnp.asarray(s, jnp.int32)}}
    elif mixer == "mla":
        y, c = mla_train(params["attn"], h, cfg, return_cache=True)
        entry = {"attn": {**c, "len": jnp.asarray(s, jnp.int32)}}
    elif mixer == "ssd":
        y, c = ssm.ssd_block_train(params["ssd"], h, cfg, return_state=True)
        entry = {"ssd": c}
    else:
        y, c = griffin.rglru_block_train(params["rglru"], h, cfg,
                                         return_state=True)
        entry = {"rglru": c}
    x = x + y
    if enc_out is not None and "cross" in params:
        hx = rms_norm(x, params["ln_x"], cfg.norm_eps)
        out, kvs = attention_train(params["cross"], hx, cfg,
                                   kv_source=enc_out, return_kv=True)
        x = x + out
        entry["cross"] = kvs
    x, _ = _apply_ffn(params, x, cfg, kind)
    x = constrain(x, ("batch", "seq_res", "d_model"))
    return x, entry


def prefill_forward(params: dict, batch: dict, cfg: ModelConfig, *,
                    remat: bool = True) -> tuple[jax.Array, dict]:
    """Full-sequence prefill: returns (last-token logits, decode cache)."""
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _encoder_forward(params, batch, cfg, remat=remat)
    x = embed_inputs(params, batch, cfg)
    seq_len = x.shape[1]
    segments_cache = []
    for seg_params, (unit, repeats) in zip(params["segments"],
                                           cfg.scan_segments()):
        def body(h, layer_params, _unit=unit):
            entries = {}
            for u, kind in enumerate(_unit):
                h, e = block_prefill(layer_params[str(u)], h, cfg, kind,
                                     seq_len=seq_len, enc_out=enc_out)
                entries[str(u)] = e
            return h, entries

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        with jax.named_scope("layers"):
            if repeats == 1:
                squeezed = jax.tree.map(lambda p: p[0], seg_params)
                x, entries = body(x, squeezed)
                entries = jax.tree.map(lambda p: p[None], entries)
            else:
                x, entries = jax.lax.scan(body, x, seg_params)
        segments_cache.append(entries)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x[:, -1:], cfg)
    return logits, {"segments": segments_cache}


def prefill_cross_memory(params: dict, cache: dict, enc_out: jax.Array,
                         cfg: ModelConfig) -> dict:
    """Precompute per-decoder-layer cross-attention K/V from the encoder
    output and store them in the decode cache (enc-dec serving prefill)."""
    hd, kv = cfg.resolved_head_dim, cfg.n_kv_heads
    b, s, _ = enc_out.shape
    new_segments = []
    for seg_params, seg_cache, (unit, repeats) in zip(
            params["segments"], cache["segments"], cfg.scan_segments()):
        seg_new = {}
        for u, kind in enumerate(unit):
            entry = dict(seg_cache[str(u)])
            cross_p = seg_params[str(u)].get("cross")
            if cross_p is not None and "cross" in entry:
                k = jnp.einsum("bsd,rdf->rbsf", enc_out,
                               cross_p["wk"].astype(enc_out.dtype))
                v = jnp.einsum("bsd,rdf->rbsf", enc_out,
                               cross_p["wv"].astype(enc_out.dtype))
                entry["cross"] = {
                    "k": k.reshape(repeats, b, s, kv, hd).astype(
                        entry["cross"]["k"].dtype),
                    "v": v.reshape(repeats, b, s, kv, hd).astype(
                        entry["cross"]["v"].dtype),
                }
            seg_new[str(u)] = entry
        new_segments.append(seg_new)
    return {"segments": new_segments}


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------


def decode_step(params: dict, state: dict, batch: dict, cfg: ModelConfig
                ) -> tuple[jax.Array, dict]:
    """One-token decode.  batch: {"inputs": (B,1) ids} or {"embeds": (B,1,d)};
    optional {"cross_memory": [...]} for enc-dec.  Returns (logits, state)."""
    with jax.named_scope("embed"):
        if cfg.input_kind == "embeds" and "embeds" in batch:
            x = batch["embeds"].astype(cfg.cdtype)
        else:
            x = params["embed"][batch["inputs"]].astype(cfg.cdtype)
        x = constrain(x, ("batch", "seq_res", "d_model"))
    cross_mem = batch.get("cross_memory")

    new_segments = []
    for seg_params, seg_cache, (unit, repeats) in zip(
            params["segments"], state["segments"], cfg.scan_segments()):
        def body(h, xs, _unit=unit):
            layer_params, layer_cache = xs
            new_cache = {}
            for u, kind in enumerate(_unit):
                h, c = block_decode(layer_params[str(u)], h, layer_cache[str(u)],
                                    cfg, kind, cross_memory=cross_mem)
                new_cache[str(u)] = c
            return h, new_cache

        with jax.named_scope("layers"):
            if repeats == 1:
                sp = jax.tree.map(lambda p: p[0], seg_params)
                sc = jax.tree.map(lambda p: p[0], seg_cache)
                x, nc = body(x, (sp, sc))
                nc = jax.tree.map(lambda p: p[None], nc)
            else:
                x, nc = jax.lax.scan(body, x, (seg_params, seg_cache))
        new_segments.append(nc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, x, cfg)
    return logits, {"segments": new_segments}
