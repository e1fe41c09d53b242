"""The harness's pieces on the CPU: finding cells and metrics by name, the
peaks table, the tail and rate arithmetic, the operation and byte counts,
the trace reduction, and the refusal of any backend but the TPU."""
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_smoke import DATA, ROOT, committed
from bench import cost, trace_reduce, weights
from bench.harness import (BenchError, load_peaks, load_reader, percentile,
                           resolve_cell)
from bench.program import program_config
from bench.serve_phase import end_to_end
from repro.models import abstract, param_defs

SPEC = committed("BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_with_a_reader_per_metric(name):
    cell = resolve_cell(name, ROOT)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(load_reader(cell.metrics_dir, m["name"]))


def test_a_cell_and_a_metric_added_as_new_files_only(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    # the new files
    mix = json.loads((tmp_path / "bench/traffic/serve-gen.json").read_text())
    mix["output"] = {"median": 96, "sigma": 0.6, "min": 32, "max": 256}
    (tmp_path / "bench/traffic/serve-mid.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/served_tokens.py").write_text(
        "def read(run):\n    return float(sum(s['occupied'] for s in run['steps']))\n")
    # a configuration of another layer mix: Mamba-2 and attention layers
    shutil.copy(DATA / "hybrid-tiny.json", tmp_path / "bench/configs/hybrid-tiny.json")
    # the new entries
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "hybrid-tiny", "source": "test",
                            "file": "bench/configs/hybrid-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"] += [
        {"name": "granite-serve-mid", "config": "granite-3-2b",
         "traffic": "serve-mid", "chips": 1, "why": "test"},
        {"name": "hybrid-serve-gen", "config": "hybrid-tiny",
         "traffic": "serve-gen", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"]:
        if "granite-serve-gen" in m.get("workloads", ()):
            m["workloads"] += ["granite-serve-mid", "hybrid-serve-gen"]
    spec["per_layer"].append({"name": "served_tokens", "unit": "tokens",
                              "better": "higher", "source": "program_counter",
                              "layer": "batcher", "moves": "serve_tokens_per_s",
                              "workloads": ["granite-serve-mid"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = resolve_cell("granite-serve-mid", tmp_path)
    assert cell.traffic["output"]["median"] == 96
    assert cell.config["name"] == "granite-3-2b"
    names = {m["name"] for m in cell.per_layer}
    assert "served_tokens" in names and "slot_occupancy" in names
    read = load_reader(cell.metrics_dir, "served_tokens")
    assert read({"steps": [{"occupied": 3}, {"occupied": 4}]}) == 7.0
    # the new metric is not asked of the cells that do not list it
    assert "served_tokens" not in {m["name"] for m in
                                   resolve_cell("granite-serve-gen", tmp_path).per_layer}
    # the hybrid resolves and builds: its program, and its weights in the
    # program's tree
    hybrid = resolve_cell("hybrid-serve-gen", tmp_path)
    cfg = program_config(hybrid.config)
    assert {mixer for mixer, _ in cfg.pattern} == {"ssd", "attn"}
    weights.check_layout(weights.program_weights(hybrid.config["model"], cfg, 1),
                         abstract(param_defs(cfg)))
    assert {m["name"] for m in hybrid.per_layer} >= {"slot_occupancy", "mfu.serve"}
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())   # no file edited


def test_peaks_by_device_kind():
    assert load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError):
        load_peaks("TPU v99")


def test_percentile_counts_a_failure_as_a_miss():
    vals = [1.0] * 19 + [math.inf]
    assert percentile(vals, 95) == 1.0
    assert percentile(vals + [math.inf], 95) == math.inf
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0


def _fake_rig(step_times, prompt, out, status="done"):
    """One batch of one request; step i returns at step_times[i]."""
    req = SimpleNamespace(prompt=(1,) * prompt, max_new_tokens=out, rid=0)
    served = SimpleNamespace(status=status, generated=[0] * out)
    records = [SimpleNamespace(t_return=t) for t in step_times]
    batch = SimpleNamespace(send=0.0, first_step=0, requests=[req], served=[served])
    return SimpleNamespace(batches=[batch], backend=SimpleNamespace(records=records))


def test_rate_and_tails_over_the_window():
    times = [0.1 * (i + 1) for i in range(12)]               # steps every 0.1 s
    rig = _fake_rig(times, prompt=3, out=10)                 # tokens at steps 2..11
    m = end_to_end(rig, (0.0, 1.25))                         # the batch ended at 1.25
    assert m["serve_tokens_per_s"] == pytest.approx(10 / 1.25)
    assert m["ttft_p95_s"] == pytest.approx(0.3)
    assert m["tbt_p95_ms"] == pytest.approx(100.0)
    slower = end_to_end(_fake_rig([t * 1.05 for t in times], 3, 10),
                        (0.0, 1.25 * 1.05))
    # a step slower by 5% lowers the rate by as much
    assert slower["serve_tokens_per_s"] == pytest.approx(m["serve_tokens_per_s"] / 1.05)


def test_the_window_holds_whole_batches(monkeypatch):
    from bench import serve_phase as sp

    clock = [100.0]
    sent = []

    def batch(rig, reqs):
        sent.append(reqs)
        clock[0] += 0.3                                      # a batch lasts 0.3 s

    monkeypatch.setattr(sp.time, "perf_counter", lambda: clock[0])
    monkeypatch.setattr(sp, "run_batch", batch)
    rig = SimpleNamespace(model={"vocab_size": 50})
    traffic = committed("bench/traffic/serve-gen.json")
    t0, t1 = sp.serve_window(rig, traffic, seed=3, seconds=1.0)
    # batches start at 0, 0.3, 0.6 and 0.9 s; the fourth runs on past 1.0
    assert len(sent) == 4 and t0 == 100.0 and t1 == pytest.approx(101.2)


def test_a_stall_inside_the_window_moves_the_tail():
    times = [0.1 * (i + 1) for i in range(30)]
    stalled = times[:10] + [t + 2.0 for t in times[10:]]
    base = end_to_end(_fake_rig(times, 2, 29), (0.0, 10.0))
    hit = end_to_end(_fake_rig(stalled, 2, 29), (0.0, 10.0))
    assert hit["tbt_p95_ms"] > base["tbt_p95_ms"]
    assert end_to_end(_fake_rig(times, 2, 29, status="failed"),
                      (0.0, 10.0))["ttft_p95_s"] == math.inf


def test_cost_against_a_hand_count():
    m = {"family": "dense", "num_hidden_layers": 1, "hidden_size": 4,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
         "intermediate_size": 8, "vocab_size": 10}
    # q, o: 4x4 each; k, v: 4x2 each; gate, up, down: 4x8 each; head 4x10
    assert cost.matmul_params(m) == 32 + 16 + 96 + 40
    assert cost.param_bytes(m) == 184 * 2 + (2 * 4 + 4) * 4
    flops, nbytes = cost.decode_step(m, occupied=3, position=5)
    assert flops == 2 * 184 * 3 + 4 * 2 * 2 * 6 * 1 * 3
    assert nbytes == 416 + 3 * (2 * 1 * 2 * 2) * 6
    assert cost.train_flops_per_token(m, 16) == 6 * 184 + 6 * 1 * 2 * 2 * 16
    peaks = {"bf16_flops": 1000.0, "hbm_bytes_per_s": 100.0}
    assert cost.step_seconds(flops, nbytes, peaks) == nbytes / 100.0


def test_trace_reduction_on_a_small_recorded_trace():
    rec = json.loads((Path(__file__).parent / "data/trace_small.json").read_text())
    out = trace_reduce.reduce(rec)
    assert out["window_s"] == pytest.approx(1000e-9)
    # fusion.1 and fusion.2 overlap: busy is their union, 100..350, and 600..700
    assert out["busy_s"] == pytest.approx(350e-9)
    assert dict(out["device_ops"]) == pytest.approx(
        {"fusion.1": 200e-9, "fusion.2": 100e-9, "copy.3": 100e-9})
    # gaps 0..100 and 700..1000 fall under the batch alone; 350..600 under
    # the reset, the innermost span that covers it
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"serve.batch": 400e-9, "serve.reset": 250e-9})


def test_a_loop_op_counts_only_its_own_time():
    ops = [("%while.2 = (s32[]) while(...)", 0, 100), ("%fusion.1 = bf16[8] fusion(...)", 10, 30),
           ("%copy.3 = bf16[8] copy(...)", 50, 20), ("%fusion.1 = bf16[8] fusion(...)", 150, 10)]
    rec = {"devices": {"/device:TPU:0": ops},
           "host_spans": [["bench.trace_window", 0, 200]]}
    out = trace_reduce.reduce(rec)
    assert dict(out["device_ops"]) == pytest.approx(
        {"while.2": 50e-9, "fusion.1": 40e-9, "copy.3": 20e-9})
    assert out["busy_s"] == pytest.approx(110e-9)


def test_trace_extraction_finds_the_window_span(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    rec = trace_reduce.extract(trace_reduce.find_xplane(str(tmp_path)))
    assert trace_reduce.WINDOW_SPAN in {n for n, _, _ in rec["host_spans"]}


def _run_bench(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-serve-gen",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu_backend():
    res = _run_bench(ROOT)
    assert res.returncode == 2
    assert res.stdout == ""
    assert "needs a TPU" in res.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_bench(tmp_path)
    assert res.returncode != 0
    assert res.stdout == ""
