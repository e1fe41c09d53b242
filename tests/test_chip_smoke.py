"""``chip_smoke.py``'s phases at smoke size on the CPU, its refusal of any
backend but the TPU, and where the launchers keep the compile cache."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch.compile_cache import CHECKOUT, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

SMOKE = get_smoke_config("granite-3-2b")


def test_serve_phase_smoke_size():
    out = cs.serve_phase(SMOKE, prompt_len=8, new_tokens=4)
    assert out["completed"] == out["requests"] == 8
    assert set(out["placement"]) == {"replica0", "replica1"}
    assert out["served_greedy"] == out["served_tokens"] == 32
    assert out["rel_diff"] <= cs.LOGIT_REL_BOUND


def test_train_phase_smoke_size(tmp_path, monkeypatch):
    # an explicit cache directory keeps the launcher's cache setting out
    # of this process's jax config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = cs.train_phase(str(tmp_path / "ckpt"), full=False, seq=32)
    assert out["steps"] == len(out["losses"]) == 4
    assert out["hosts_left"] == 3
    assert [(s, h) for s, h, _ in out["recoveries"]] == [(2, "host01")]
    assert out["checkpoint_step"] == 3


def test_failover_puts_each_replica_on_its_own_device():
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(ROOT)!r})
        import jax, chip_smoke as cs
        from repro.configs import get_smoke_config
        out = cs.failover_phase(get_smoke_config("granite-3-2b"),
                                devices=jax.devices(), prompt_len=8,
                                new_tokens=4)
        print(json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(set(out["spread"]["placement"].values())) == 4
    assert len(set(out["one_device"]["placement"].values())) == 1
    for run in ("one_device", "spread"):
        assert out[run]["completed"] == 8
        assert out[run]["denylisted"] == ["replica1"]
        assert out[run]["recovered"] >= 1
    assert out["same_tokens"] == 8


def test_main_refuses_the_cpu(capsys):
    assert cs.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.parametrize("alone", [False, True])
def test_script_fails_without_a_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if alone:
        # the script by itself, without the rest of the repository
        script = Path(shutil.copy(script, tmp_path))
        env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, str(script)], env=env,
                         cwd=script.parent, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_compile_cache_follows_the_environment(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache") == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
