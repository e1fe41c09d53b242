"""Compile the main path for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what the CPU backend and Pallas interpret mode
accept: block shapes off the tiling, more fast memory than a kernel may
use, programs larger than the chip's memory.  These tests compile the
serve and train programs and both Pallas kernels at published widths
for one v5e chip and check that each fits its 16 GB.  Nothing runs.
"""
import os
import re
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
from repro.models.layers import WRITE_BLOCK
from repro.optim import OptConfig
from repro.serve.batcher import decode_program
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


def _bench_on_path():
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)


def test_granite_decode_step_full_width(one_chip):
    cfg = get_config("granite-3-2b")
    params = _on(one_chip, abstract(param_defs(cfg)))
    cache = _on(one_chip, abstract(cache_defs(cfg, 8, 1024)))
    batch = _on(one_chip, {"inputs": jax.ShapeDtypeStruct((8, 1), jnp.int32)})
    compiled = jax.jit(lambda p, c, b: decode_step(p, c, b, cfg)).lower(
        params, cache, batch).compile()
    _fits(compiled)


def _granite_decode_args(one_chip):
    """granite-3-2b's weights, decode state and inputs at the benchmark's
    96 slots of 512 positions, on one described v5e chip."""
    cfg = get_config("granite-3-2b")
    params = _on(one_chip, abstract(param_defs(cfg)))
    cache = _on(one_chip, abstract(cache_defs(cfg, 96, 512)))
    batch = _on(one_chip, {"inputs": jax.ShapeDtypeStruct((96, 1), jnp.int32)})
    return cfg, params, cache, batch


@pytest.fixture(scope="module")
def granite_decode_text(one_chip):
    """The granite-3-2b decode step as the serving backend jits it,
    compiled for one v5e chip: its HLO text, the parameters that hold the
    cache, and the abstract cache."""
    _bench_on_path()
    from bench import scope_reduce

    cfg, params, cache, batch = _granite_decode_args(one_chip)
    text, state = scope_reduce.program_text(
        decode_program(cfg), params, cache, batch, state_arg=1)
    return text, state, cache


def test_granite_decode_step_keeps_the_model_scopes(granite_decode_text):
    # the scopes reach the TPU program's op metadata, where a profiler
    # trace's op names can be mapped back to them
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
    # the layer scan's own slices are told apart by the data they move: a
    # weight's layer slice, or the cache's.  The scan carries the cache
    # and each layer writes its one position in place, so every slice of
    # the scan's own is a weight's
    import math

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
    assert weight_ops and not state_ops
    # granite's per-layer cache slices are [96, 512, 512]; no weight
    # slice has as many elements
    assert not any(sizes[n] in per_layer for n in weight_ops)


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
#: ops that only route a value, or run the loop that carries it
_ROUTING = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def _instructions(text: str):
    """(computation, name, the dims of each value it makes, opcode,
    operand names, computations it calls, each value's layout from minor
    to major) of every instruction of a compiled HLO module's text."""
    comp = None
    for line in text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            words = line.split()
            comp = (words[1] if words[0] == "ENTRY" else words[0]).lstrip("%")
            continue
        head, eq, rest = line.partition(" = ")
        op = _OPCODE.search(" " + rest) if eq and comp is not None else None
        if op is None:
            continue
        shape = (" " + rest)[:op.start()]
        args = (" " + rest)[op.end():].split(")", 1)[0]
        made = re.findall(r"\w+\[([\d,]*)\](?:\{([\d,]*))?", shape)
        yield (comp, head.split()[-1].lstrip("%"),
               [tuple(int(d) for d in m.split(",") if d) for m, _ in made],
               op.group(1), re.findall(r"%([\w.\-]+)", args),
               re.findall(r"\bcalls=%?([\w.\-]+)", rest),
               [tuple(int(d) for d in lay.split(",") if d) for _, lay in made])


def test_granite_decode_step_updates_its_state_in_place(one_chip):
    """The decode step as the serving backend runs it, at the benchmark's
    96 slots of 512 positions, aliases the whole decode state, holds no
    second copy of it, and writes one position per layer.

    Readings of this compile (the installed TPU compiler, a described
    v5e), temporaries / aliased bytes:

    * the layer scan slicing the stacked state (``xs``) and stacking it
      back (``ys``), not donated: 0 / 0, and a fresh 4.05 GB output each
      step;
    * the same, donated: 4.03 GB / 4.03 GB, the scan's ``ys`` a second
      whole state copied into the donated one;
    * the state carried in the scan and written at one position, donated:
      5.37 GB / 4.03 GB, the write's fused operand laying the carried
      state out for itself, four copies of the whole state into that
      layout and back;
    * one buffer per layer, the layer loop unrolled, donated: 0.26 GB /
      4.03 GB, and a cold compile of 24 s against 3.8 s;
    * the state carried, each one-position write's operand kept out of
      its fusion (an optimization barrier): 0.0004 GB / 4.03 GB, but the
      state laid out with the positions minor, so that each write
      touches every tile of a layer's slab (measured on a v5e: as long as
      the copy it replaced);
    * as built, the KV heads folded into the minor axis, positions
      second, and each write an aligned block of ``WRITE_BLOCK``
      positions: 0.0004 GB / 4.03 GB.
    """
    cfg, params, cache, batch = _granite_decode_args(one_chip)
    compiled = decode_program(cfg).lower(params, cache, batch).compile()
    mem = compiled.memory_analysis()
    leaves = jax.tree.leaves(cache)
    assert mem.alias_size_in_bytes >= sum(
        leaf.size * leaf.dtype.itemsize for leaf in leaves)
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    stacked = {leaf.shape for leaf in leaves if leaf.ndim > 1}
    slabs = {s[1:] for s in stacked} | {(1,) + s[1:] for s in stacked}
    instrs = list(_instructions(text))
    dims = {name: made[0] for _, name, made, *_ in instrs if made}
    fused = {c for _, _, _, _, _, calls, _ in instrs for c in calls}
    writes_in = {comp for comp, _, made, op, *_ in instrs
                 if op == "dynamic-update-slice" and stacked & set(made)}
    writes = 0
    for comp, name, made, op, operands, calls, layouts in instrs:
        if stacked & set(made) and op not in _ROUTING:
            # nothing makes a value of the whole state's shape but a
            # write of one block of positions into it, alone or fused
            assert op == "dynamic-update-slice" or (
                op == "fusion" and writes_in & set(calls)), (name, op)
            if op == "dynamic-update-slice":
                block = dims[operands[1]]
                assert block[2] <= WRITE_BLOCK, (name, block)
                # the positions are not the minor (lane) axis, so that a
                # block of them is whole rows of tiles, not a column
                # through every tile of the layer
                assert layouts[0][0] != 2, (name, layouts[0])
                writes += 1
        # a layer's slab is read inside the fusions that use it, never
        # made on its own
        assert comp in fused or not slabs & set(made), (name, op, made)
    assert writes == 2                         # K and V, in the layer loop


@pytest.fixture(scope="module")
def ssd_decode_programs(one_chip):
    """The decode steps of the two configurations with Mamba-2 layers at
    published widths and the benchmark's 96 slots of 512 positions, as
    the serving backend jits them, compiled for one v5e chip: per
    configuration its compiled program, abstract cache and the number of
    weight leaves (the cache's parameters follow them)."""
    out = {}
    for arch in ("mamba2-780m", "granite-4-0-h-micro"):
        cfg = get_config(arch)
        params = _on(one_chip, abstract(param_defs(cfg)))
        cache = _on(one_chip, abstract(cache_defs(cfg, 96, 512)))
        batch = _on(one_chip,
                    {"inputs": jax.ShapeDtypeStruct((96, 1), jnp.int32)})
        compiled = decode_program(cfg).lower(params, cache, batch).compile()
        out[arch] = compiled, cache, len(jax.tree.leaves(params))
    return out


def test_granite4h_decode_step_carries_both_states_in_place(
        ssd_decode_programs):
    """granite-4.0-h-micro at published widths and the benchmark's 96
    slots of 512 positions, as the serving backend jits it: 36 Mamba-2
    layers' SSD state and window and 4 attention layers' K/V carried in
    one scan of the 10-layer unit, all aliased, none copied.  This
    compile: 10.50 GB of arguments, 4.12 GB aliased, 0.028 GB of
    temporaries (with the SSD state updated in XLA over every slot, 0.84
    GB: one layer's SSD intermediates, 0.1 GB each)."""
    _bench_on_path()
    from bench import scope_reduce

    compiled, cache, n_params = ssd_decode_programs["granite-4-0-h-micro"]
    _fits(compiled)
    mem = compiled.memory_analysis()
    state_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 0.1e9
    scopes = scope_reduce.hlo_scopes(
        compiled.as_text(),
        range(n_params, n_params + len(jax.tree.leaves(cache))))
    classes = set(scopes.values())
    assert {"ssd", "attn", "kv_write", "ffn", "logits"} <= classes
    assert "layer_state" not in classes


@pytest.mark.parametrize("arch", ["mamba2-780m", "granite-4-0-h-micro"])
def test_ssd_decode_step_updates_the_state_by_the_kernel_in_place(
        ssd_decode_programs, arch):
    # the SSD state of every Mamba-2 layer is updated by the Pallas kernel
    # (one per unit position of the layer scan), under the ``ssd`` scope,
    # into the donated state: aliased whole, no op copies it or makes a
    # layer's slab of it, and the temporaries stay small (mamba2-780m
    # 0.0004 GB, granite-4.0-h-micro 0.028 GB in this compile)
    _bench_on_path()
    from bench import scope_reduce

    compiled, cache, n_params = ssd_decode_programs[arch]
    _fits(compiled)
    mem = compiled.memory_analysis()
    states = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
              if getattr(path[-1], "key", None) == "state"]
    assert mem.alias_size_in_bytes >= sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < 0.1e9
    text = compiled.as_text()
    kernels = [name for _, name, _, op, *_ in _instructions(text)
               if op == "custom-call" and "ssd_decode_update" in name]
    units = {s.shape for s in states}
    assert len(kernels) == len(states) and units
    scopes = scope_reduce.hlo_scopes(
        text, range(n_params, n_params + len(jax.tree.leaves(cache))))
    assert {scopes[k] for k in kernels} == {"ssd"}
    slabs = units | {s[1:] for s in units} | {(1,) + s[1:] for s in units}
    for _, name, made, op, *_ in _instructions(text):
        if slabs & set(made):
            assert op in _ROUTING | {"custom-call"}, (name, op, made)


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
