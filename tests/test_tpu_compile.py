"""Compile the main path for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what the CPU backend and Pallas interpret mode
accept: block shapes off the tiling, more fast memory than a kernel may
use, programs larger than the chip's memory.  These tests compile the
serve and train programs and both Pallas kernels at published widths
for one v5e chip and check that each fits its 16 GB.  Nothing runs.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bh
from repro.kernels.ssd_scan import ssd_scan_kernel
from repro.models import abstract, cache_defs, decode_step, param_defs
from repro.optim import OptConfig
from repro.train import WrathTrainSupervisor

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one, so keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    # the TPU library logs to a directory of its own unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= V5E_HBM_BYTES, f"{total / 1e9:.2f} GB does not fit"


def test_granite_decode_step_full_width(one_chip):
    cfg = get_config("granite-3-2b")
    params = _on(one_chip, abstract(param_defs(cfg)))
    cache = _on(one_chip, abstract(cache_defs(cfg, 8, 1024)))
    batch = _on(one_chip, {"inputs": jax.ShapeDtypeStruct((8, 1), jnp.int32)})
    compiled = jax.jit(lambda p, c, b: decode_step(p, c, b, cfg)).lower(
        params, cache, batch).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def granite_decode_text(one_chip):
    """The granite-3-2b decode step compiled for one v5e chip: its HLO
    text, the parameters that hold the cache, and the abstract cache, at
    the benchmark's 96 slots of 512 positions."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import scope_reduce

    cfg = get_config("granite-3-2b")
    params = _on(one_chip, abstract(param_defs(cfg)))
    cache = _on(one_chip, abstract(cache_defs(cfg, 96, 512)))
    batch = _on(one_chip, {"inputs": jax.ShapeDtypeStruct((96, 1), jnp.int32)})
    text, state = scope_reduce.program_text(
        jax.jit(lambda p, c, b: decode_step(p, c, b, cfg)), params, cache,
        batch, state_arg=1)
    return text, state, cache


def test_granite_decode_step_keeps_the_model_scopes(granite_decode_text):
    # the scopes reach the TPU program's op metadata, where a profiler
    # trace's op names can be mapped back to them
    import re

    text, _, _ = granite_decode_text
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/layers/" in n and
               n.endswith("/attn/kv_write/dynamic_update_slice")
               for n in op_names)
    for scope in ("/embed/", "/layers/while/body/dynamic_slice",
                  "/attn/", "/ffn/", "/logits/"):
        assert any(scope in n for n in op_names), scope


def test_granite_decode_layer_slices_split_into_state_and_weights(
        granite_decode_text):
    # the layer scan's own slices and write-backs are told apart by the
    # data they move: a weight's layer slice, or the cache's
    import math
    import re

    from bench import scope_reduce

    text, state, cache = granite_decode_text
    scopes = scope_reduce.hlo_scopes(text, state)
    sizes = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = \w+\[([\d,]*)\]",
                         text, re.M):
        sizes[m.group(1)] = math.prod(int(d) for d in m.group(2).split(",") if d)
    per_layer = {math.prod(leaf.shape[1:]) for leaf in jax.tree.leaves(cache)}
    state_ops = [n for n, c in scopes.items() if c == "layer_state"]
    weight_ops = [n for n, c in scopes.items() if c == "layer_weights"]
    assert state_ops and weight_ops
    # granite's per-layer cache slices are [96, 512, 8, 64]; no weight
    # slice has as many elements
    assert any(sizes[n] in per_layer for n in state_ops)
    assert not any(sizes[n] in per_layer for n in weight_ops)


def test_supervisor_grad_fn_granite_4_layers(one_chip, tmp_path):
    cfg = get_config("granite-3-2b").scaled(n_layers=4)
    sup = WrathTrainSupervisor(cfg, OptConfig(), ckpt_dir=str(tmp_path))
    params = _on(one_chip, abstract(param_defs(cfg)))
    tokens = jax.ShapeDtypeStruct((2, 1024), jnp.int32)
    batch = _on(one_chip, {"inputs": tokens, "targets": tokens})
    _fits(sup._grad_fn.lower(params, batch).compile())


def test_flash_attention_granite_widths(one_chip):
    qkv = _on(one_chip, jax.ShapeDtypeStruct((32, 2048, 64), jnp.bfloat16))
    compiled = jax.jit(flash_attention_bh).lower(qkv, qkv, qkv).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_mamba2_widths(one_chip):
    ssm = get_config("mamba2-780m").ssm
    b, seq, h, p, n = 1, 2048, 48, ssm.head_dim, ssm.d_state
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(s, f32) for s in
            ((b, seq, h, p), (b, seq, h), (h,), (b, seq, n), (b, seq, n))]
    compiled = jax.jit(lambda *a: ssd_scan_kernel(*a, chunk=ssm.chunk)).lower(
        *_on(one_chip, args)).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()
