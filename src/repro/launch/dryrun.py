import os

# jax fixes the host device count when its backend starts, so the fake
# 512-chip topology must be in XLA_FLAGS before jax is imported; a count
# the caller already set wins
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count=512".strip())

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware: ``jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed
on the 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh, and the
compiled artifact yields ``memory_analysis()`` (fits-in-HBM proof) and
``cost_analysis()`` + HLO collectives (roofline terms, §Roofline).

The ``XLA_FLAGS`` assignment above MUST stay first (before any jax
import): jax locks the device count on first initialization.

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both
    python -m repro.launch.dryrun --arch deepseek-v3-671b --shape train_4k \
        --mesh single --elastic 4     # degraded mesh after losing 4 hosts
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.distributed import (
    ACT_RULES,
    CACHE_RULES,
    PARAM_RULES,
    StepConfig,
    activation_sharding,
    build_prefill_step,
    build_serve_step,
    build_train_step,
    defs_shardings,
    spec_for,
)
from repro.launch.mesh import make_elastic_mesh, make_production_mesh, mesh_chip_count
from repro.launch.shapes import (
    SHAPES,
    batch_axes,
    batch_specs,
    shape_applicable,
)
from repro.models import cache_defs, param_defs
from repro.models.config import ModelConfig
from repro.models.spec import abstract
from repro.optim import OptConfig
from repro.optim.adamw import opt_state_defs
from repro.roofline import analyze

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# per-arch step tuning for the train_4k cell: microbatch count + dtypes.
# Chosen so per-device HBM stays under the 16 GB v5e budget (EXPERIMENTS.md
# §Dry-run records the resulting numbers).
TRAIN_TUNING: dict[str, tuple[int, str, str]] = {
    # name: (microbatches, accum_dtype, moment_dtype)
    "deepseek-v3-671b": (8, "bfloat16", "bfloat16"),   # §Perf: halves grad-AR
    "deepseek-67b": (16, "bfloat16", "bfloat16"),
    "llava-next-34b": (8, "bfloat16", "bfloat16"),
    "gemma3-27b": (8, "float32", "float32"),
    "recurrentgemma-9b": (4, "float32", "float32"),
    "minitron-4b": (4, "float32", "float32"),
    "granite-3-2b": (1, "float32", "float32"),   # §Perf: mb=1 + full-DP
    "seamless-m4t-medium": (2, "float32", "float32"),
    "olmoe-1b-7b": (4, "float32", "float32"),
    "mamba2-780m": (2, "float32", "float32"),
}


def step_tuning(cfg: ModelConfig) -> tuple[StepConfig, OptConfig]:
    mb, acc, mom = TRAIN_TUNING.get(cfg.name, (1, "float32", "float32"))
    return (StepConfig(microbatches=mb, remat=True, accum_dtype=acc),
            OptConfig(moment_dtype=mom))


# per-arch activation-rule overrides (EXPERIMENTS.md §Perf).  For small
# dense models, TP all-reduces of activations dominate; sharding the batch
# over (data × model) turns the layout into pure DP/ZeRO-3 (weights
# all-gathered per layer — far fewer bytes than per-layer activation
# all-reduces when params << activations).
ARCH_ACT_OVERRIDES: dict[str, dict] = {
    "granite-3-2b": {"batch": (("pod", "data", "model"), ("pod", "data"),
                               ("data",))},
}


def act_rules_for(cfg: ModelConfig, shape_kind: str):
    if shape_kind == "train" and cfg.name in ARCH_ACT_OVERRIDES:
        return ACT_RULES.replace(**ARCH_ACT_OVERRIDES[cfg.name])
    return ACT_RULES


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    status: str
    seconds: float = 0.0
    error: str = ""
    memory: dict | None = None
    roofline: dict | None = None
    skip_reason: str = ""


def _memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    d = {k: int(getattr(ma, k)) for k in
         ("argument_size_in_bytes", "output_size_in_bytes",
          "temp_size_in_bytes", "alias_size_in_bytes")}
    d["per_device_total"] = (d["argument_size_in_bytes"]
                             + d["output_size_in_bytes"]
                             + d["temp_size_in_bytes"]
                             - d["alias_size_in_bytes"])
    return d


def run_cell(arch: str, shape: str, mesh_kind: str, *,
             elastic_lost_hosts: int = 0, save: bool = True) -> CellResult:
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape)
    cell = CellResult(arch=cfg.name, shape=shape, mesh=mesh_kind, status="skip",
                      skip_reason=reason)
    if not ok:
        return cell

    multi = mesh_kind == "multi"
    if elastic_lost_hosts:
        mesh = make_elastic_mesh(elastic_lost_hosts, multi_pod=multi)
        cell.mesh = f"{mesh_kind}-elastic{elastic_lost_hosts}"
    else:
        mesh = make_production_mesh(multi_pod=multi)
    chips = mesh_chip_count(mesh)
    sp = SHAPES[shape]
    step_cfg, opt_cfg = step_tuning(cfg)

    t0 = time.time()
    try:
        pdefs = param_defs(cfg)
        p_sh = defs_shardings(pdefs, PARAM_RULES, mesh)
        p_abs = abstract(pdefs)
        b_specs = batch_specs(cfg, shape)
        b_axes = batch_axes(cfg, shape)
        act_rules = act_rules_for(cfg, sp.kind)
        b_sh = {k: jax.sharding.NamedSharding(
            mesh, spec_for(b_specs[k].shape, b_axes[k], act_rules, mesh))
            for k in b_specs}

        with mesh, activation_sharding(mesh, act_rules):
            if sp.kind == "train":
                odefs = opt_state_defs(pdefs, opt_cfg)
                o_sh = defs_shardings(odefs, PARAM_RULES, mesh)
                o_abs = abstract(odefs)
                step = build_train_step(cfg, opt_cfg, step_cfg)
                jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                                 out_shardings=(p_sh, o_sh, None),
                                 donate_argnums=(0, 1))
                lowered = jitted.lower(p_abs, o_abs, b_specs)
                tokens = sp.global_batch * sp.seq_len
            elif sp.kind == "prefill":
                step = build_prefill_step(cfg, step_cfg)
                cdefs = cache_defs(cfg, sp.global_batch, sp.seq_len)
                c_sh = defs_shardings(cdefs, CACHE_RULES, mesh)
                jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                                 out_shardings=(None, c_sh))
                lowered = jitted.lower(p_abs, b_specs)
                tokens = sp.global_batch * sp.seq_len
            else:  # decode
                step = build_serve_step(cfg)
                cdefs = cache_defs(cfg, sp.global_batch, sp.seq_len)
                c_sh = defs_shardings(cdefs, CACHE_RULES, mesh)
                c_abs = abstract(cdefs)
                jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                                 out_shardings=(None, c_sh),
                                 donate_argnums=(1,))
                lowered = jitted.lower(p_abs, c_abs, b_specs)
                tokens = sp.global_batch  # one token per sequence

            compiled = lowered.compile()

        mem = _memory_dict(compiled)
        hlo = compiled.as_text()
        report = analyze(
            arch=cfg.name, shape=shape, mesh_name=cell.mesh, chips=chips,
            compiled=compiled, hlo_text=hlo, cfg=cfg, defs=pdefs,
            kind=sp.kind, tokens=tokens,
            per_device_hbm_bytes=mem["per_device_total"])

        cell.status = "ok"
        cell.memory = mem
        cell.roofline = report.row()
        cell.roofline["coll_breakdown"] = dict(report.coll_breakdown)
        cell.roofline["xla_reported_flops"] = f"{report.xla_reported_flops:.3e}"
        cell.seconds = time.time() - t0
        if save:
            RESULTS_DIR.mkdir(parents=True, exist_ok=True)
            out = RESULTS_DIR / f"{cfg.name}__{shape}__{cell.mesh}.json"
            out.write_text(json.dumps(dataclasses.asdict(cell), indent=1))
    except Exception as e:  # noqa: BLE001 - report compile failures as data
        cell.status = "fail"
        cell.error = f"{type(e).__name__}: {e}\n{traceback.format_exc()[-2000:]}"
        cell.seconds = time.time() - t0
        if save:
            RESULTS_DIR.mkdir(parents=True, exist_ok=True)
            out = RESULTS_DIR / f"{cfg.name}__{shape}__{cell.mesh}.json"
            out.write_text(json.dumps(dataclasses.asdict(cell), indent=1))
    return cell


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (dashed ok)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="all archs × shapes")
    ap.add_argument("--elastic", type=int, default=0,
                    help="lost hosts for the degraded-mesh dry-run")
    ap.add_argument("--no-save", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    rows = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                cell = run_cell(arch, shape, mk,
                                elastic_lost_hosts=args.elastic,
                                save=not args.no_save)
                r = cell.roofline or {}
                print(f"{cell.arch:22s} {shape:12s} {cell.mesh:8s} "
                      f"{cell.status:5s} {cell.seconds:7.1f}s "
                      f"hbm/dev={r.get('per_device_hbm_gb', '-'):>8} "
                      f"dom={r.get('dominant', cell.skip_reason or cell.error[:60])}",
                      flush=True)
                rows.append(cell)
    n_ok = sum(1 for c in rows if c.status == "ok")
    n_skip = sum(1 for c in rows if c.status == "skip")
    n_fail = sum(1 for c in rows if c.status == "fail")
    print(f"\n{n_ok} ok, {n_skip} skipped (noted), {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
