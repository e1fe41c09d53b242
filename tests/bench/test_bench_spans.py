"""The readers of the program's spans and the model's scopes, on a small
recorded run, and the scope map of a tiny decode step compiled on the
CPU."""
import json
import re
from pathlib import Path

import pytest

from bench_smoke import ROOT
from bench import scope_reduce
from bench.harness import load_reader

DATA = json.loads((Path(__file__).parent / "data/spans_small.json").read_text())
READERS = ["step_host_ms.serve", "plane_host_ms.serve", "queue_wait_ms.serve",
           "decode_state_share.serve"]


def _read(name, run):
    return load_reader(ROOT / "bench" / "metrics", name)(run)


@pytest.mark.parametrize("name, want", [
    # steps of the traced batch: 80 ms less a 50 ms wait, 180 ms less
    # 120 ms; the step after the trace stopped is not read
    ("step_host_ms.serve", 45.0),
    # outermost plane spans 100 + 200 + 1 + 2 ms, less the benchmark's
    # 87 + 190 ms step records (each holds a serve.step), over two steps
    ("plane_host_ms.serve", 13.0),
    # the traced batch's waits, 400 and 500 ms: nearest rank
    ("queue_wait_ms.serve", 500.0),
    # 1 s of cache writes and 4 s of layer state in 10 busy seconds; the
    # 1 s of the weights' layer slices is not state
    ("decode_state_share.serve", 50.0),
])
def test_reader_on_a_small_recorded_run(name, want):
    assert _read(name, DATA["run"]) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_programs_spans_or_scopes(name):
    # a program without the span log or the named scopes gives the
    # reader nothing to read
    run = {k: v for k, v in DATA["run"].items()
           if k not in ("program_spans", "scopes")}
    assert _read(name, run) is None


@pytest.mark.parametrize("name", READERS[:3])
def test_span_reader_is_silent_without_the_trace_stop(name):
    # without the end of the traced batch a reader would mix the
    # profiler's regime with the program's own
    run = {k: v for k, v in DATA["run"].items() if k != "trace_stop"}
    assert _read(name, run) is None


def test_scope_reduction_on_a_small_recorded_trace():
    out = scope_reduce.reduce(DATA["trace_record"], DATA["scope_map"],
                              "jit__lambda")
    # fusion.1 at 620 ns ran in another program and is not counted; the
    # while loop keeps only its own time; copy.4 is cut at the window
    assert out["kv_write"] == pytest.approx(100e-9)
    assert out["layer_state"] == pytest.approx(200e-9)
    assert out["layer_weights"] == pytest.approx(10e-9)
    assert out["attn"] == pytest.approx(50e-9)
    assert out["other"] == pytest.approx(90e-9)
    assert out["unmapped"] == 0.0
    assert scope_reduce.reduce(DATA["trace_record"],
                               {k: "other" for k in DATA["scope_map"]},
                               "jit__lambda") is None


@pytest.mark.parametrize("name, want", [
    ("jit(f)/layers/while/body/closed_call/attn/kv_write/dynamic_update_slice",
     "kv_write"),
    ("jit(f)/layers/while/body/closed_call/ffn/dot_general", "ffn"),
    ("jit(f)/layers/while/body/dynamic_slice", "layers"),
    ("jit(f)/layers/while/cond/lt", None),
    ("jit(f)/logits/dot_general", "logits"),
    ("jit(f)/rsqrt", None),
])
def test_scope_class_of_an_op_name(name, want):
    assert scope_reduce.scope_class(name) == want


def test_layer_scan_ops_are_split_into_state_and_weights():
    # a scan over stacked weights (element 2, from parameter 0) and a
    # stacked cache (element 3, from parameter 1 through an async copy)
    # that stacks the new cache into a buffer the program returns
    text = (Path(__file__).parent / "data/scan_small.hlo").read_text()
    scopes = scope_reduce.hlo_scopes(text, {1})
    assert scopes["w_slice"] == scopes["w_copy"] == "layer_weights"
    assert scopes["c_slice"] == scopes["ys_new"] == "layer_state"
    assert scopes["new"] == "kv_write"
    # the loop counter is neither
    assert scopes["next"] == scopes["lt"] == "other"
    # named as weights, the cache's slice is weights too
    assert scope_reduce.hlo_scopes(text, set())["c_slice"] == "layer_weights"
    with pytest.raises(ValueError):
        scope_reduce.hlo_scopes(text, {7})


@pytest.fixture(scope="module")
def decode_program():
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import cache_defs, decode_step, materialize, param_defs

    cfg = get_smoke_config("granite_3_2b")
    params = materialize(param_defs(cfg), jax.random.PRNGKey(0))
    cache = materialize(cache_defs(cfg, 4, 32), jax.random.PRNGKey(0))
    step = jax.jit(lambda p, c, b: decode_step(p, c, b, cfg))
    text, state = scope_reduce.program_text(
        step, params, cache, {"inputs": jnp.zeros((4, 1), jnp.int32)},
        state_arg=1)
    return text, state, len(jax.tree.leaves(params)), len(jax.tree.leaves(cache))


def test_program_text_names_the_state_parameters(decode_program):
    text, state, n_params, n_cache = decode_program
    assert state == frozenset(range(n_params, n_params + n_cache))
    # the entry's parameters that hold the cache carry its argument path
    for number in state:
        line = re.search(rf"parameter\({number}\).*", text).group(0)
        assert "op_name=\"c[" in line


def _result_shape(hlo: str, name: str) -> tuple | None:
    """The dims of instruction ``name``'s array result in the HLO text."""
    m = re.search(rf"^\s*(?:ROOT\s+)?%?{re.escape(name)}\s*=\s*\w+\[([\d,]*)\]",
                  hlo, re.M)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


def test_every_op_of_the_decode_layer_scan_has_a_class(decode_program):
    import jax

    from repro.configs import get_smoke_config
    from repro.models import abstract, cache_defs

    decode_hlo, state, _, _ = decode_program
    scopes = scope_reduce.hlo_scopes(decode_hlo, state)
    comps, _, _ = scope_reduce._computations(decode_hlo)
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", decode_hlo))
    body_ops = [ins for b in bodies for ins in comps[b]]
    assert body_ops
    classes = {ins["name"]: scopes[ins["name"]] for ins in body_ops}
    assert set(classes.values()) <= {"kv_write", "layer_state",
                                     "layer_weights", "attn", "ffn", "other"}
    assert {"kv_write", "attn", "ffn"} <= set(classes.values())
    # where the scan's own ops move the decode state (a result shaped as a
    # state leaf or one layer of it, as this CPU program's are and the v5e
    # program's no longer are), they are layer_state, and only then is
    # that class required
    state_shapes = set()
    for leaf in jax.tree.leaves(abstract(cache_defs(
            get_smoke_config("granite_3_2b"), 4, 32))):
        if leaf.ndim >= 2:
            state_shapes |= {leaf.shape, leaf.shape[1:], (1,) + leaf.shape[1:]}
    moves_state = []
    for ins in body_ops:
        if ins["opcode"] in scope_reduce.CONTROL:
            continue
        # an op that carries a model scope of its own is classed by it
        own = scope_reduce.scope_class(ins["op_name"])
        if own not in (None, "layers"):
            assert classes[ins["name"]] == own, ins
        elif own == "layers" and _result_shape(decode_hlo, ins["name"]) in state_shapes:
            moves_state.append(ins["name"])
    assert all(classes[n] == "layer_state" for n in moves_state)
    assert ("layer_state" in classes.values()) == bool(moves_state)
    # the cache writes hold the attention's dynamic-update-slices
    assert any("dynamic-update-slice" in n or "dynamic_update_slice" in n
               for n, c in classes.items() if c == "kv_write")
    # outside the scan: the embedding lookup and the logits
    assert {"embed", "logits"} <= set(scopes.values())
    assert scope_reduce.module_name(decode_hlo).startswith("jit_")


#: a traced window of 1000 ns: one decode step whose program runs from 150
#: to 250 ns and whose argmax runs from 260 to 280 ns
PROBE_RECORD = {
    "host_spans": [["bench.trace_window", 0, 1000], ["serve.batch", 0, 1000],
                   ["serve.step", 100, 300], ["serve.step.launch", 180, 10],
                   ["serve.step.wait", 200, 200]],
    "devices": {"/device:TPU:0": [["fusion.1", 150, 100], ["argmax.2", 260, 20]]},
    "modules": {"/device:TPU:0": [["jit__lambda(1)", 150, 100],
                                  ["jit_argmax(2)", 260, 20]]},
}


@pytest.mark.parametrize("offset, want", [
    # as traced: idle 0-150, 250-260 and 280-1000
    (0.0, {"serve.batch": 700e-9, "serve.step": 50e-9,
           "serve.step.wait": 130e-9}),
    # the device 100 ns later: idle 0-250, 350-360 and 380-1000
    (100.0, {"serve.batch": 700e-9, "serve.step": 90e-9,
             "serve.step.launch": 10e-9, "serve.step.wait": 80e-9}),
])
def test_probe_splits_idle_over_the_innermost_span(offset, want):
    from bench import span_probe

    got = span_probe.idle_by_span(PROBE_RECORD, offset)
    assert got.keys() == want.keys()
    for name, t in want.items():
        assert got[name] == pytest.approx(t), name


def test_probe_bounds_the_device_clock_offset():
    from bench import span_probe

    # the program starts 30 ns before its launch began, and the argmax
    # ends 120 ns before the wait did
    assert span_probe.clock_offset(PROBE_RECORD, "jit__lambda") == (30, 120)
    assert span_probe.clock_offset(PROBE_RECORD, "jit_other") is None


def test_probe_exits_2_off_the_chip(capsys):
    from bench import span_probe

    assert span_probe.main(["--workload", "granite-serve-gen",
                            "--seed", "3000000001"]) == 2
    assert "needs a TPU" in capsys.readouterr().err


def test_probe_runs_at_smoke_size_on_the_cpu():
    import jax

    from bench import serve_phase as sp
    from bench import span_probe
    from bench.harness import CompileClock, Spans
    from bench_smoke import PEAKS, smoke_cell

    cell = smoke_cell("granite-3-2b", "serve-gen")
    cell.per_layer = [{"name": "decode_step_ms"}, {"name": "host_gap_ms.serve"}]
    seed = 2**31 + 5
    with CompileClock() as clock:
        rig = sp.build(cell.config, cell.traffic, seed, Spans(annotate=True),
                       jax.devices()[:1])
        sp.warm_up(rig, seed)
        off, on = span_probe.log_cost(rig, cell, seed, 2)
        traced = span_probe.traced_run(rig, cell, seed, 1.0, PEAKS, clock)
    rig.driver.shutdown()
    assert off["n_spans"] == 0 and off["step_host_ms.serve"] is None
    assert on["n_spans"] > 0
    assert on["step_host_ms.serve"] > 0 and on["queue_wait_ms.serve"] > 0
    metrics = traced["metrics"]
    assert metrics["step_host_ms.serve"] > 0
    assert metrics["plane_host_ms.serve"] is not None
    assert traced["window_compiles"] == 0
    # the program's step lies inside the benchmark's span around it
    cross = traced["cross"]
    assert cross["serve_step_mean_ms"] <= cross["decode_step_ms_traced"]
