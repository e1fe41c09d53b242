"""The cell ``granite4h-serve-gen`` at smoke size on the CPU: the committed
``granite-4.0-h-micro`` file at smoke widths (its 40-layer stack of
Mamba-2 and NoPE attention layers, each followed by an MLP, and its
multipliers) served through the harness's own plane function against the
float32 reference; the scopes of its decode step; the reader of the SSD
mixers' share of busy time; and the file's counts, pinned."""
import functools

import jax
import jax.numpy as jnp
import pytest

from bench_smoke import ROOT, run_cell, smoke_cell
from bench import cost, scope_reduce
from bench import serve_phase as sp
from bench.harness import load_json, load_reader
from bench.layers import kind, kind_counts

CELL = ("granite-4.0-h-micro", "serve-gen")
#: the file's std, 0.1 at width 2048, scaled so that every product's
#: output is as wide at smoke width 64 as at full size.  At the smoke
#: cells' 0.11 the embedding times 12 outweighs the layers' outputs times
#: 0.22, and the tied head echoes its input: sound runs and the float8
#: control then both read 0.
SMOKE_STD = 0.1 * (2048 / 64) ** 0.5
#: smoke readings at that std: sound 0.055, float8 control 0.907, the
#: planted faults 2.7 and more
CONTROL_LIMIT = 0.3


def _cell():
    """The smoke cell with the file's own attention scale (``SMOKE_MODEL``
    sets the program's old 1/sqrt(head_dim)) and the std above."""
    cell = smoke_cell(*CELL)
    model = cell.config["model"]
    model["attention_multiplier"] = load_json(
        ROOT / "bench/configs/granite-4.0-h-micro.json")["model"]["attention_multiplier"]
    model["initializer_range"] = SMOKE_STD
    assert len(model["layer_types"]) == 40
    assert model["position_embedding_type"] == "nope"
    return cell


def test_a_sound_run_is_correct():
    out = run_cell(_cell())
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert out["checks"]["served_logit_gap"]["value"] < CONTROL_LIMIT / 2


def _altered(self, replica, inputs):
    return [(t + 1) % self.cfg.vocab_size for t in _orig_step(self, replica, inputs)]


def _state_unchanged(self, replica, inputs):
    before = self._caches[replica.name]
    out = _orig_step(self, replica, inputs)
    self._caches[replica.name] = before
    return out


def _half_batch(self, replica, inputs):
    half = len(inputs) // 2
    return _orig_step(self, replica, inputs[:half] + [0 if t is not None else None
                                                      for t in inputs[half:]])


_orig_step = sp.BenchDecodeBackend.step


@pytest.mark.parametrize("fault", [_altered, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    # at the file's own limit; the state left unchanged is deleted by the
    # next step's donation, so every request of that run fails
    monkeypatch.setattr(sp.BenchDecodeBackend, "step", fault)
    out = run_cell(_cell())
    assert not out["correct"]
    assert not out["checks"]["served_logit_gap"]["ok"]


def test_the_float8_control_is_not_correct(monkeypatch):
    def cell():
        c = _cell()
        c.config["limits"]["served_logit_gap"] = CONTROL_LIMIT
        return c

    assert run_cell(cell())["correct"]
    monkeypatch.setattr(sp, "served_gap", sp.control_gap)
    out = run_cell(cell())
    assert not out["correct"]
    assert not out["checks"]["served_logit_gap"]["ok"], out["checks"]


def test_the_hybrid_decode_step_is_classed_by_its_scopes():
    # the smoke program's 10-layer unit, two repeats: nine SSD positions
    # and one attention position carried in one scan.  In float32: the CPU
    # backend computes bf16 in float32, and converts a bf16 state whole to
    # do so, which the v5e program does not (``test_tpu_compile.py``)
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import cache_defs, materialize, param_defs
    from repro.serve.batcher import decode_program

    cfg = dataclasses.replace(get_smoke_config("granite_4_0_h_micro"),
                              compute_dtype="float32")
    f32 = functools.partial(jax.tree.map, lambda x: x.astype(jnp.float32)
                            if jnp.issubdtype(x.dtype, jnp.floating) else x)
    params = f32(materialize(param_defs(cfg), jax.random.PRNGKey(0)))
    cache = f32(materialize(cache_defs(cfg, 4, 32), jax.random.PRNGKey(0)))
    text, state = scope_reduce.program_text(
        decode_program(cfg), params, cache,
        {"inputs": jnp.zeros((4, 1), jnp.int32)}, state_arg=1)
    scopes = scope_reduce.hlo_scopes(text, state)
    classes = set(scopes.values())
    assert {"ssd", "attn", "kv_write", "ffn", "logits", "embed"} <= classes
    # the scan's own ops move no decode state: each layer updates its part
    # of the carried state inside its mixer's scope (an SSD layer its state
    # and window, attention one block of positions), so no op slices or
    # writes back, let alone copies, a unit position's stacked state
    assert "layer_state" not in classes
    assert sum(1 for c in scopes.values() if c == "kv_write") >= 2


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        todo = list(eqn.params.values())
        while todo:
            v = todo.pop()
            if isinstance(v, (tuple, list)):
                todo += v
            elif hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                yield from _eqns(getattr(v, "jaxpr", v))


def test_the_multipliers_sit_inside_the_model_scopes():
    # each multiplier's op is traced under the scope whose work it scales,
    # so that no device time of theirs is classed ``other``
    from repro.configs import get_smoke_config
    from repro.models import abstract, cache_defs, decode_step, param_defs

    cfg = get_smoke_config("granite_4_0_h_micro")
    jaxpr = jax.make_jaxpr(lambda p, c, b: decode_step(p, c, b, cfg))(
        abstract(param_defs(cfg)), abstract(cache_defs(cfg, 4, 32)),
        {"inputs": jax.ShapeDtypeStruct((4, 1), jnp.int32)}).jaxpr
    found = []
    for eqn in _eqns(jaxpr):
        consts = [float(v.val) for v in eqn.invars
                  if hasattr(v, "val") and getattr(v.val, "shape", None) == ()]
        for value, name in ((12.0, "embedding"), (0.015625, "attention"),
                            (0.22, "residual"), (8.0, "logits")):
            # 0.22 is rounded to the activations' bf16
            if eqn.primitive.name in ("mul", "div") and any(
                    abs(c - value) <= 2e-3 * value for c in consts):
                found.append((name, str(eqn.source_info.name_stack).split("/")[-1]))
    # the unit's 9 SSD layers and 1 attention layer, each with its MLP
    assert sorted(found) == sorted(
        [("embedding", "embed"), ("attention", "attn"), ("logits", "logits"),
         ("residual", "attn")] + [("residual", "ssd")] * 9
        + [("residual", "ffn")] * 10)


def _read(name, run):
    return load_reader(ROOT / "bench" / "metrics", name)(run)


def test_ssd_share_reader_on_a_small_recorded_run():
    run = {"plane": "serve", "trace": {"busy_s": 10.0},
           "scopes": {"ssd": 6.5, "attn": 0.5, "ffn": 2.5, "logits": 0.1,
                      "kv_write": 0.1, "layer_state": 0.0, "other": 0.3}}
    assert _read("ssd_share.serve", run) == pytest.approx(65.0)
    assert _read("ssd_share.serve", {k: v for k, v in run.items()
                                     if k != "scopes"}) is None
    assert _read("ssd_share.serve", {**run, "plane": "train"}) is None
    assert _read("ssd_share.serve", {**run, "trace": {"busy_s": 0.0}}) is None


MODEL = load_json(ROOT / "bench/configs/granite-4.0-h-micro.json")["model"]


@pytest.mark.parametrize("what, got, want", [
    ("layers of each kind", dict(kind_counts(MODEL)),
     {"mamba": 36, "attention": 4, "mlp": 40}),
    # in_proj 2048 x (2 * 4096 + 2 * 128 + 64), out_proj 4096 x 2048
    ("mamba matmul", kind("mamba").matmul_params(MODEL), 25_821_184),
    # q and o 2048 x 2048, k and v 2048 x 512
    ("attention matmul", kind("attention").matmul_params(MODEL), 10_485_760),
    ("mlp matmul", kind("mlp").matmul_params(MODEL), 50_331_648),
    # with the tied embedding once, 2048 x 100352
    ("matmul", cost.matmul_params(MODEL), 3_190_292_480),
    ("served bytes", cost.param_bytes(MODEL), 6_383_432_704),
    # the weights; 36 layers' SSD state and convolution window, read and
    # written; 4 layers' K/V over 512 live positions read, one written
    ("decode step at 96 slots, position 511", cost.decode_step(MODEL, 96, 511),
     (623_206_465_536.0, 14_214_329_344.0)),
])
def test_the_files_counts_are_pinned(what, got, want):
    assert got == want, what


def test_the_files_parameters_are_the_programs():
    # 3.19 B parameters, counted from the layers' files and from the
    # program's own parameter tree
    from repro.models import param_count, param_defs

    from bench.program import program_config

    total = cost.matmul_params(MODEL) + MODEL["hidden_size"] + sum(
        n * sum(kind(k).other_params(MODEL)) for k, n in kind_counts(MODEL).items())
    assert total == 3_191_396_096
    config = load_json(ROOT / "bench/configs/granite-4.0-h-micro.json")
    assert param_count(param_defs(program_config(config))) == total


def test_the_published_keys_and_the_model_the_benchmark_reads_agree():
    # the file states the source's config.json at its top level and again
    # under ``model``, which the benchmark reads; the two must not drift
    config = load_json(ROOT / "bench/configs/granite-4.0-h-micro.json")
    top = {k: v for k, v in config.items() if k in config["model"]}
    assert len(top) == 33 and "layer_types" in top and "head_dim" not in top
    assert top == {k: config["model"][k] for k in top}
