"""A model as a list of layer kinds (``bench/layers``): the weight draw and
the cost counts of the committed files pinned, the program's configuration
built from the stack, and a hybrid's weights placed where the program's
scan runs each layer."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench_smoke import DATA, ROOT, load_json, smoke_cell
from bench import cost, weights
from bench.layers import kind, layer_parts, layer_types, norm_names, rows
from bench.program import program_config

PINNED = json.loads((DATA / "pinned_counts.json").read_text())
DECODE_AT = ((96, 0), (96, 17), (61, 200), (1, 511))
HYBRID = load_json(DATA / "hybrid-tiny.json")


def _digest(named_leaves) -> str:
    h = hashlib.sha256()
    for name, a in named_leaves:
        a = np.asarray(jax.device_get(a))
        for part in (name, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", ["granite-3-2b", "mamba2-780m"])
@pytest.mark.parametrize("seed", [0, 2**31 + 3, 3000001601])
def test_the_weight_draw_is_pinned(config, seed):
    c = smoke_cell(config, "serve-gen").config
    m = c["model"]
    bench = sorted(weights.flat(weights.make_weights(m, seed)).items())
    prog = weights.program_weights(m, program_config(c), seed)
    got = {"bench": _digest(bench),
           "program": _digest((jax.tree_util.keystr(k), v) for k, v in
                              jax.tree_util.tree_leaves_with_path(prog))}
    assert got == PINNED["weights"][f"{config}/{seed}"]


@pytest.mark.parametrize("config", ["granite-3-2b", "mamba2-780m"])
def test_the_cost_counts_are_pinned(config):
    m = load_json(ROOT / f"bench/configs/{config}.json")["model"]
    want = PINNED["cost"][config]
    assert cost.matmul_params(m) == want["matmul_params"]
    assert cost.param_bytes(m) == want["param_bytes"]
    assert [[o, p, *cost.decode_step(m, o, p)] for o, p in DECODE_AT] == \
        want["decode_step"]
    assert cost.train_flops_per_token(m, 512) == want["train_flops_per_token_512"]


def test_granites_program_is_its_own_entry_at_the_files_sizes():
    from repro.configs import get_config

    c = load_json(ROOT / "bench/configs/granite-3-2b.json")
    assert program_config(c) == get_config("granite-3-2b").scaled(
        n_layers=40, d_model=2048, vocab_size=49155, norm_eps=1e-05,
        n_heads=32, n_kv_heads=8, head_dim=64, d_ff=8192, rope_theta=10000.0)


def test_a_files_key_that_names_a_program_field_reaches_the_program(
        monkeypatch):
    import dataclasses

    from bench import program
    from repro.configs import get_config
    from repro.models import ModelConfig

    @dataclasses.dataclass(frozen=True)
    class WithMultiplier(ModelConfig):
        embedding_multiplier: float = 1.0

    def entry(name):
        cfg = get_config(name)
        return WithMultiplier(**{f.name: getattr(cfg, f.name)
                                 for f in dataclasses.fields(cfg)})

    monkeypatch.setattr(program, "get_config", entry)
    c = load_json(ROOT / "bench/configs/granite-3-2b.json")
    c["model"].update(embedding_multiplier=12.0, compute_dtype="float32",
                      window=77)
    cfg = program_config(c)
    assert cfg.embedding_multiplier == 12.0
    # the program's own settings are not a file's to set
    assert (cfg.compute_dtype, cfg.window) == (
        get_config("granite-3-2b").compute_dtype,
        get_config("granite-3-2b").window)


def test_a_file_without_layer_types_reads_its_family():
    granite = load_json(ROOT / "bench/configs/granite-3-2b.json")["model"]
    mamba = load_json(ROOT / "bench/configs/mamba2-780m.json")["model"]
    assert layer_parts(granite) == [("attention", "mlp")] * 40
    assert layer_parts(mamba) == [("mamba",)] * 48


def test_granite_4_0_h_micros_stack_is_one_period_of_ten():
    # the catalog's layer_types: attention at 5, 15, 25 and 35
    types = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
    c = json.loads(json.dumps(HYBRID))
    c["model"].update(layer_types=types, num_hidden_layers=40)
    cfg = program_config(c)
    assert len(cfg.pattern) == 10 and cfg.pattern[5] == ("attn", "dense")
    assert cfg.scan_segments() == [(cfg.pattern, 4)]
    assert [s for seg in weights.slots(c["model"], cfg) for s in seg][5] == \
        [5, 15, 25, 35]


def test_a_hybrids_weights_sit_where_the_scan_runs_each_layer():
    from repro.models import abstract, param_defs

    m = HYBRID["model"]
    cfg = program_config(HYBRID)
    assert cfg.pattern == (("ssd", "dense"), ("ssd", "dense"), ("attn", "dense"))
    w = weights.make_weights(m, 5)
    tree = weights.to_program(w, m, cfg)
    weights.check_layout(tree, abstract(param_defs(cfg)))
    at = rows(m)
    # layer 4 (mamba, the second unit position, second repeat) and layer 5
    # (attention, third position, second repeat)
    seg = tree["segments"][0]
    np.testing.assert_array_equal(seg["1"]["ssd"]["in_proj"][1],
                                  w["layers"]["in_proj"][at["mamba"][4]])
    np.testing.assert_array_equal(seg["2"]["attn"]["wq"][1],
                                  w["layers"]["q"][at["attention"][5]])
    np.testing.assert_array_equal(seg["2"]["ffn"]["w1"][1],
                                  w["layers"]["gate"][5])
    # and back, every leaf in layer order
    back = weights.flat_from_program(tree, m, cfg)
    for name, leaf in weights.flat(w).items():
        want = leaf - 1.0 if name.split(".")[-1] in norm_names(m) else leaf
        np.testing.assert_array_equal(back[name], want)


def test_each_kind_is_found_by_name_and_an_unknown_one_is_refused():
    assert {kind(k).BLOCK for k in ("attention", "mamba", "mlp")} == {
        ("mixer", "attn"), ("mixer", "ssd"), ("ffn", "dense")}
    with pytest.raises(ValueError):
        layer_types({**HYBRID["model"], "layer_types": ["mamba"]})
    with pytest.raises(ValueError):
        kind("sliding_window")


def test_nope_attention_leaves_positions_unrotated():
    from bench import reference

    m = dict(HYBRID["model"], layer_types=["attention"], num_hidden_layers=1,
             intermediate_size=0)
    w = weights.make_weights(m, 3)
    toks = jax.numpy.asarray(np.arange(12, dtype=np.int32).reshape(2, 6))
    rope, _, _ = reference.logits_summary(w, m, toks, toks)
    nope, _, _ = reference.logits_summary(
        w, dict(m, position_embedding_type="nope"), toks, toks)
    # the first position has angle 0 either way; later ones differ
    np.testing.assert_allclose(rope[:, 0], nope[:, 0], rtol=1e-6)
    assert not np.allclose(rope[:, 1:], nope[:, 1:])
    with pytest.raises(ValueError):
        cost.decode_step(dict(m, position_embedding_type="alibi"), 1, 0)


@pytest.mark.parametrize("key,value", [("position_embedding_type", "alibi"),
                                       ("mamba_conv_bias", False)])
def test_a_path_the_layers_do_not_have_is_refused_once(key, value):
    m = dict(HYBRID["model"], **{key: value})
    for use in (layer_parts, cost.param_bytes,
                lambda m: weights.make_weights(m, 0)):
        with pytest.raises(ValueError, match=key):
            use(m)
