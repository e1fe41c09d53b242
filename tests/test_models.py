"""Model-zoo correctness: layer oracles, train-vs-decode consistency,
MoE dispatch equivalence, property tests."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import (
    ModelConfig,
    cache_defs,
    decode_step,
    forward_train,
    param_defs,
    param_count,
)
from repro.models.model import _logits
from repro.models.spec import materialize

KEY = jax.random.PRNGKey(42)


def fp32(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, compute_dtype="float32")


def fp32_params(defs, key=KEY):
    params = materialize(defs, key)
    return jax.tree.map(
        lambda x: x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


def make_batch(cfg: ModelConfig, b: int, s: int, key=KEY):
    batch = {}
    if cfg.encoder_layers:
        batch["enc_embeds"] = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
    if cfg.input_kind == "embeds":
        batch["embeds"] = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
    else:
        batch["inputs"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch["targets"] = jax.random.randint(jax.random.fold_in(key, 1), (b, s),
                                          0, cfg.vocab_size)
    return batch


# ---------------------------------------------------------------- configs --
def test_all_assigned_configs_match_spec():
    spec = {
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "deepseek-v3-671b": (61, 7168, 128, 128, 18432, 129280),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "mamba2-780m": (48, 1536, 48, 48, 0, 50280),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab_size)
        assert got == spec[cfg.name], (cfg.name, got)
        cfg.validate()


def test_deepseek_v3_param_count_near_671b():
    cfg = get_config("deepseek_v3_671b")
    n = param_count(param_defs(cfg))
    assert 6.0e11 < n < 7.4e11, f"{n:,}"


def test_scan_segments_cover_all_layers():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        segs = cfg.scan_segments()
        assert sum(len(u) * r for u, r in segs) == cfg.n_layers
        # HLO size guard: few segments even for 95-layer models
        assert len(segs) <= 4, (arch, segs)


def test_gemma3_pattern_is_5_local_1_global():
    cfg = get_config("gemma3_27b")
    kinds = cfg.block_kinds()
    for i, (mixer, _) in enumerate(kinds):
        assert mixer == ("attn" if i % 6 == 5 else "swa")


def test_granite4h_attention_at_5_15_25_35_in_one_scan_segment():
    cfg = get_config("granite_4_0_h_micro")
    kinds = cfg.block_kinds()
    assert [i for i, (m, _) in enumerate(kinds) if m == "attn"] == [5, 15, 25, 35]
    assert all(m == "ssd" for m, _ in kinds if m != "attn")
    assert all(f == "dense" for _, f in kinds)
    (unit, repeats), = cfg.scan_segments()
    assert unit == cfg.pattern and repeats == 4


def test_deepseek_v3_first_3_dense():
    kinds = get_config("deepseek_v3_671b").block_kinds()
    assert all(f == "dense" for _, f in kinds[:3])
    assert all(f == "moe" for _, f in kinds[3:])


# -------------------------------------------------- train/decode parity --
@pytest.mark.parametrize("arch", ["granite_3_2b", "gemma3_27b", "mamba2_780m",
                                  "recurrentgemma_9b", "deepseek_v3_671b",
                                  "seamless_m4t_medium", "granite_4_0_h_micro"])
def test_decode_matches_train_forward(arch):
    """Token-by-token decode must reproduce the full-sequence forward."""
    cfg = fp32(get_smoke_config(arch))
    b, s = 2, 16
    defs = param_defs(cfg)
    params = fp32_params(defs)
    batch = make_batch(cfg, b, s)

    h, enc_out, _ = forward_train(params, batch, cfg, remat=False)
    from repro.models.layers import rms_norm  # noqa: PLC0415
    train_logits = _logits(params, h, cfg)     # (B,S,V) — h already normed

    cache = fp32_params(cache_defs(cfg, b, s))
    if cfg.encoder_layers:
        # prefill the cross memory from the encoder output
        from repro.models.model import prefill_cross_memory
        cache = prefill_cross_memory(params, cache, enc_out, cfg)
    dec = []
    for t in range(s):
        db = {}
        if cfg.input_kind == "embeds" and not cfg.encoder_layers:
            db["embeds"] = batch["embeds"][:, t:t + 1]
        else:
            db["inputs"] = batch["inputs"][:, t:t + 1]
        logits, cache = decode_step(params, cache, db, cfg)
        dec.append(logits[:, 0])
    dec_logits = jnp.stack(dec, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits), np.asarray(train_logits),
                               rtol=2e-3, atol=2e-3)


def test_swa_ring_buffer_decode_matches_train():
    """Window cache smaller than the sequence: ring buffer must still match."""
    cfg = fp32(get_smoke_config("gemma3_27b"))
    assert cfg.window == 32
    b, s = 1, 48                                  # s > window
    params = fp32_params(param_defs(cfg))
    batch = make_batch(cfg, b, s)
    h, _, _ = forward_train(params, batch, cfg, remat=False)
    train_logits = _logits(params, h, cfg)
    cache = fp32_params(cache_defs(cfg, b, s))
    dec = []
    for t in range(s):
        logits, cache = decode_step(params, cache,
                                    {"inputs": batch["inputs"][:, t:t + 1]}, cfg)
        dec.append(logits[:, 0])
    dec_logits = jnp.stack(dec, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits), np.asarray(train_logits),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------- layers --
def test_blockwise_mha_matches_dense():
    from repro.models.layers import blockwise_mha, mha
    key = KEY
    b, s, h, kv, d = 2, 256, 4, 2, 16
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kv, d))
    ref = mha(q, k, v, causal=True)
    out = blockwise_mha(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
    ref_w = mha(q, k, v, causal=True, window=32)
    out_w = blockwise_mha(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(ref_w), rtol=1e-5, atol=1e-5)


def test_ssd_scan_matches_naive_recurrence():
    from repro.models.ssm import ssd_scan
    key = KEY
    b, l, h, p, n = 1, 64, 2, 4, 8
    x = jax.random.normal(key, (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, l, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)) * 0.2)
    bb = jax.random.normal(jax.random.fold_in(key, 3), (b, l, 1, n))
    cc = jax.random.normal(jax.random.fold_in(key, 4), (b, l, 1, n))
    y, final = ssd_scan(x, dt, a, bb, cc, chunk=16)
    # naive per-step recurrence oracle
    state = np.zeros((b, h, p, n))
    ys = []
    for t in range(l):
        decay = np.exp(np.asarray(dt[:, t] * a[None]))          # (b,h)
        upd = np.einsum("bhp,bn,bh->bhpn", np.asarray(x[:, t]),
                        np.asarray(bb[:, t, 0]), np.asarray(dt[:, t]))
        state = state * decay[..., None, None] + upd
        ys.append(np.einsum("bhpn,bn->bhp", state, np.asarray(cc[:, t, 0])))
    ref = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), state, rtol=1e-4, atol=1e-4)


def test_rglru_scan_matches_loop():
    from repro.models.griffin import _rglru_core, make_rglru_defs
    cfg = fp32(get_smoke_config("recurrentgemma_9b"))
    params = fp32_params(make_rglru_defs(cfg))
    b, l, w = 2, 32, 64
    x = jax.random.normal(KEY, (b, l, w))
    y, h_last = _rglru_core(params, x)
    # step-by-step loop oracle
    r = jax.nn.sigmoid(x @ params["w_a"] + params["b_a"])
    i = jax.nn.sigmoid(x @ params["w_x"] + params["b_x"])
    log_a = -8.0 * jax.nn.softplus(params["lam"])[None, None] * r
    a = np.asarray(jnp.exp(log_a))
    gated = np.asarray(jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-6)) * i * x)
    h = np.zeros((b, w))
    ys = []
    for t in range(l):
        h = a[:, t] * h + gated[:, t]
        ys.append(h.copy())
    ref = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)


def test_moe_scatter_matches_gshard():
    """With ample capacity the two dispatch implementations agree exactly."""
    from repro.models.moe import make_moe_defs, moe_gshard, moe_scatter
    cfg = fp32(get_smoke_config("olmoe_1b_7b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
    params = fp32_params(make_moe_defs(cfg))
    x = jax.random.normal(KEY, (2, 16, cfg.d_model))
    y1, _ = moe_gshard(params, x, cfg)
    y2, _ = moe_scatter(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens():
    from repro.models.moe import make_moe_defs, moe_scatter
    cfg = fp32(get_smoke_config("olmoe_1b_7b"))
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.05))
    params = fp32_params(make_moe_defs(cfg))
    x = jax.random.normal(KEY, (2, 64, cfg.d_model))
    y, aux = moe_scatter(params, x, cfg)
    assert jnp.all(jnp.isfinite(y))
    assert float(aux) > 0


def test_mla_absorbed_decode_equivalence_is_covered():
    # covered by test_decode_matches_train_forward[deepseek_v3_671b];
    # here we additionally check the MLA cache is the compressed latent
    cfg = get_smoke_config("deepseek_v3_671b")
    cd = cache_defs(cfg, batch=2, seq_len=16)
    seg0 = cd["segments"][0]["0"]
    assert "ckv" in seg0["attn"]
    assert seg0["attn"]["ckv"].shape[-1] == cfg.mla.kv_lora_rank


# ------------------------------------------------------------ properties --
@settings(max_examples=20, deadline=None)
@given(st.integers(2, 64), st.integers(1, 8))
def test_rms_norm_scale_invariance(d, bmul):
    from repro.models.layers import rms_norm
    x = jax.random.normal(KEY, (bmul, d)) * 3.0
    w = jnp.zeros((d,))
    y = rms_norm(x, w)
    # unit RMS after normalization with identity scale
    rms = jnp.sqrt(jnp.mean(y.astype(jnp.float32) ** 2, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3)


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 16).map(lambda v: v * 2), st.integers(1, 512))
def test_rope_preserves_norm(d, pos):
    from repro.models.layers import apply_rope
    x = jax.random.normal(KEY, (1, 1, 2, d))
    y = apply_rope(x, jnp.array([pos]), 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y)), np.linalg.norm(np.asarray(x)), rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6))
def test_rope_relative_property(shift):
    """<rope(q,p1), rope(k,p2)> depends only on p1-p2."""
    from repro.models.layers import apply_rope
    d = 16
    q = jax.random.normal(KEY, (1, 1, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 1, d))

    def dot_at(p1, p2):
        qr = apply_rope(q, jnp.array([p1]), 10_000.0)
        kr = apply_rope(k, jnp.array([p2]), 10_000.0)
        return float(jnp.sum(qr * kr))

    assert abs(dot_at(5 + shift, 5) - dot_at(11 + shift, 11)) < 1e-3


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 5), st.integers(2, 5))
def test_segsum_matches_definition(h, q):
    from repro.models.ssm import _segsum
    a = jax.random.normal(KEY, (h, q))
    out = np.asarray(_segsum(a))
    for i in range(q):
        for j in range(q):
            if i >= j:
                expect = float(jnp.sum(a[0, j + 1:i + 1]))
                assert abs(out[0, i, j] - expect) < 1e-4
            else:
                assert out[0, i, j] == -np.inf
