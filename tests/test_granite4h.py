"""granite-4.0-h-micro in the program: Mamba-2 and NoPE attention layers
in one scan unit, and granite's four multipliers.

At smoke widths in float32, on seeded random weights drawn as the
benchmark draws them: decode through the donated cache against the
program's own full-sequence forward and against the benchmark's plain
float32 reference (``bench/layers``), each of the five fields shown to
matter, and granite-3-2b's decode program unchanged by its file's
values.  Also the serving plane's gauge of the decode state's bytes.
"""
import copy
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, weights  # noqa: E402
from bench.harness import load_json  # noqa: E402
from bench.layers import kind, layer_parts, rows  # noqa: E402
from bench.program import PASSED, program_config  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.engine.cluster import Node  # noqa: E402
from repro.models import (ModelConfig, abstract, cache_defs,  # noqa: E402
                          decode_state_bytes, decode_step, forward_train,
                          materialize, param_defs)
from repro.models.model import _logits  # noqa: E402
from repro.serve import WrathServeDriver  # noqa: E402
from repro.serve.batcher import JaxDecodeBackend, decode_program  # noqa: E402

FILE = ROOT / "bench/configs/granite-4.0-h-micro.json"
B, S, SEED = 2, 16, 3000001701
#: every product's output as wide as at full size: std * sqrt(width) of
#: the file's 0.1 over 2048 at width 64.  At a smaller std the embedding
#: times 12 outweighs the layers and the tied head echoes its input.
SMOKE_STD = 0.1 * (2048 / 64) ** 0.5
#: float32 against float32: the readings agree to about 1e-5; a field at
#: the wrong value moves the logits by 0.1 or more
TOL = 2e-4
#: each field at the program's default, where the file states otherwise
DEFAULTS = {"embedding_multiplier": 1.0, "attention_multiplier": None,
            "residual_multiplier": 1.0, "logits_scaling": 1.0,
            "position_embedding_type": "rope"}


def _smoke_model() -> dict:
    """The committed file at smoke widths: two periods of its stack (20
    layers, so the layer scan runs two repeats of the 10-layer unit), its
    NoPE attention and its multipliers."""
    m = copy.deepcopy(load_json(FILE)["model"])
    m.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, intermediate_size=128, vocab_size=256,
             mamba_d_head=16, mamba_n_heads=8, mamba_d_state=16,
             mamba_chunk_size=16, initializer_range=SMOKE_STD,
             num_hidden_layers=20, layer_types=m["layer_types"][:20])
    return m


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def reference_logits(w: dict, m: dict, tokens) -> np.ndarray:
    """The benchmark's float32 reference, every logit: its layers in turn,
    the final norm and the tied head over ``logits_scaling``."""
    x = w["embed"][tokens].astype(jnp.float32) * m["embedding_multiplier"]
    at = rows(m)
    for i, parts in enumerate(layer_parts(m)):
        for name in parts:
            lw = {k: w["layers"][k][at[name][i]] for k in kind(name).LAYOUT}
            x = kind(name).forward(x, lw, m, "f32")
    h = reference.rms_norm(x, w["ln_f"], m["rms_norm_eps"])
    return np.asarray(reference.mm(h, w["embed"].T, "f32") / m["logits_scaling"])


@pytest.fixture(scope="module")
def setup():
    m = _smoke_model()
    cfg = dataclasses.replace(program_config({"model": m,
                                              "program_config": "granite-4-0-h-micro"}),
                              compute_dtype="float32")
    w = weights.make_weights(m, SEED)
    params = _f32(weights.to_program(w, m, cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, m["vocab_size"])
    return m, cfg, params, tokens, reference_logits(w, m, tokens)


def _decode(cfg, params, tokens) -> np.ndarray:
    """Token by token through the served program, its state donated."""
    step = decode_program(cfg)
    cache = _f32(materialize(cache_defs(cfg, B, S), jax.random.PRNGKey(0)))
    out = []
    for t in range(S):
        before = cache
        logits, cache = step(params, cache, {"inputs": tokens[:, t:t + 1]})
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
        out.append(np.asarray(logits[:, 0]))
    return np.stack(out, axis=1)


def test_the_program_runs_the_files_stack_in_one_scan(setup):
    _, cfg, _, _, _ = setup
    (unit, repeats), = cfg.scan_segments()
    assert repeats == 2 and len(unit) == 10
    assert [m for m, _ in unit].index("attn") == 5
    assert cfg.position_embedding_type == "nope"
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (12, 0.015625, 0.22, 8)


def test_decode_through_the_donated_state_matches_forward_and_reference(setup):
    m, cfg, params, tokens, ref = setup
    dec = _decode(cfg, params, tokens)
    h, _, _ = forward_train(params, {"inputs": tokens}, cfg, remat=False)
    train = np.asarray(_logits(params, h, cfg))
    # the logits are wide enough that agreement is not an echo
    assert ref.std() > 0.1
    assert np.mean(ref.argmax(-1) == np.asarray(tokens)) < 0.5
    np.testing.assert_allclose(dec, train, atol=TOL, rtol=0)
    np.testing.assert_allclose(dec, ref, atol=TOL, rtol=0)


@pytest.mark.parametrize("field", list(DEFAULTS))
def test_each_field_changes_the_served_logits(setup, field):
    # the program with one field at its default is no longer the model
    # the reference runs
    _, cfg, params, tokens, ref = setup
    other = dataclasses.replace(cfg, **{field: DEFAULTS[field]})
    assert getattr(other, field) != getattr(cfg, field)
    gap = np.abs(_decode(other, params, tokens) - ref).max()
    assert gap > 100 * TOL, (field, gap)


def _hlo(cfg, batch: int, length: int, *, compile_: bool) -> str:
    step = jax.jit(lambda p, c, b: decode_step(p, c, b, cfg), donate_argnums=(1,))
    lowered = step.lower(abstract(param_defs(cfg)),
                         abstract(cache_defs(cfg, batch, length)),
                         {"inputs": jax.ShapeDtypeStruct((batch, 1), jnp.int32)})
    return lowered.compile().as_text() if compile_ else lowered.as_text()


@pytest.mark.parametrize("size", ["smoke", "full"])
def test_granites_decode_program_is_unchanged_by_its_files_values(size):
    # granite-3-2b's file states 1.0, 0.125 (1/sqrt(64)), 1.0, 1.0 and no
    # position embedding, so rope; the same program as with every field at
    # its default: a multiplier of 1 adds no op, and 0.125 is the default
    # scale.  At smoke widths the head dim is 64 so that it still is; the
    # full-size program is lowered, not compiled.
    config = load_json(ROOT / "bench/configs/granite-3-2b.json")
    stated = {k: config["model"][k] for k in PASSED if k in config["model"]}
    assert stated == {"embedding_multiplier": 1.0, "attention_multiplier": 0.125,
                      "residual_multiplier": 1.0, "logits_scaling": 1.0}
    with_values = program_config(config)
    assert with_values.attention_multiplier == 0.125
    defaults = dataclasses.replace(with_values, **{
        f.name: f.default for f in dataclasses.fields(ModelConfig) if f.name in PASSED})
    assert defaults.attention_multiplier is None
    shape = (96, 512)
    if size == "smoke":
        small = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                     vocab_size=256, head_dim=64)
        with_values, defaults = with_values.scaled(**small), defaults.scaled(**small)
        shape = (4, 32)
    # both from one line, so that the text's source locations are alike
    a, b = [_hlo(c, *shape, compile_=size == "smoke") for c in (with_values, defaults)]
    assert a == b


def test_the_decode_state_bytes_gauge_is_set_when_a_replica_starts():
    cfg = get_smoke_config("granite_4_0_h_micro")
    backend = JaxDecodeBackend(cfg, max_batch=4, max_len=32)
    drv = WrathServeDriver(cfg, n_replicas=1, max_batch=4, decode=backend)
    try:
        want = decode_state_bytes(abstract(cache_defs(cfg, 4, 32)))
        assert set(want) == {"kv", "ssd", "conv"}
        # 2 repeats x 4 slots x 32 positions x 2 kv heads x 16, K and V, bf16
        assert want["kv"] == 2 * 4 * 32 * 32 * 2 * 2 + 2 * 4
        for label, n in want.items():
            stats = drv.monitor.gauge_stats("serve.decode_state_bytes",
                                            label=label)
            assert stats is not None and stats.n == 1 and stats.mean == n
        assert drv.monitor.gauge_stats("serve.decode_state_bytes") is None
        # a replica started afresh sets it again
        backend.start_replica(Node("replica9", workers_per_node=1))
        assert drv.monitor.gauge_stats("serve.decode_state_bytes",
                                       label="ssd").n == 2
    finally:
        drv.shutdown()


def test_the_published_config_and_its_smoke_variant():
    cfg = get_config("granite-4-0-h-micro")
    assert get_config("granite_4_0_h_micro") is cfg
    (unit, repeats), = cfg.scan_segments()
    assert (len(unit), repeats) == (10, 4)
    smoke = get_smoke_config("granite-4-0-h-micro")
    assert smoke.pattern == cfg.pattern and smoke.scan_segments()[0][1] == 2
    for f in DEFAULTS:
        assert getattr(smoke, f) == getattr(cfg, f)
    with pytest.raises(ValueError):
        cfg.scaled(position_embedding_type="alibi").validate()
