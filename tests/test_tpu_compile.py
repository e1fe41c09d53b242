"""Compile the main path for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what the CPU backend and Pallas interpret mode
accept: block shapes off the tiling, more fast memory than a kernel may
use, programs larger than the chip's memory.  These tests compile the
serve and train programs and both Pallas kernels at published widths
for one v5e chip and check that each fits its 16 GB.  Nothing runs.
"""
import os

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
