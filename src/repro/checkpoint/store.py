"""Sharded checkpointing: atomic commit, retention, async save, elastic
restore-with-resharding.

Layout (one directory per step)::

    <dir>/step_0000100/
        manifest.json         # tree structure, shapes, dtypes, metadata
        shard_00000.npz       # flattened leaves, chunked by byte budget
        ...
        COMMITTED             # written last — crash-safe commit marker

Restore rebuilds the pytree and (optionally) ``device_put``s each leaf to a
new sharding — the elastic re-mesh path: a checkpoint written on a 16×16
mesh restores cleanly onto a degraded 8×16 mesh because shardings are
reapplied at load time, not baked into the files.

The paper's framework-layer recovery (restart component → retry) maps to
``CheckpointManager.restore_latest()`` after a training-plane failure.
"""
from __future__ import annotations

import json
import shutil
import threading
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np

_COMMIT = "COMMITTED"


def _flatten(tree: Any) -> tuple[list[tuple[str, Any]], Any]:
    leaves, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((key, leaf))
    return out, treedef


def save_checkpoint(directory: str | Path, step: int, tree: Any, *,
                    metadata: dict | None = None,
                    shard_mb: int = 256) -> Path:
    """Atomic checkpoint save; returns the committed directory."""
    directory = Path(directory)
    final = directory / f"step_{step:08d}"
    tmp = directory / f".tmp_step_{step:08d}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)

    leaves, _ = _flatten(tree)
    manifest: dict[str, Any] = {
        "step": step,
        "time": time.time(),
        "metadata": metadata or {},
        "leaves": [],
    }
    budget = shard_mb * 2**20
    shard_idx, shard_bytes, shard_data = 0, 0, {}

    def flush():
        nonlocal shard_idx, shard_bytes, shard_data
        if shard_data:
            np.savez(tmp / f"shard_{shard_idx:05d}.npz", **shard_data)
            shard_idx += 1
            shard_bytes, shard_data = 0, {}

    for key, leaf in leaves:
        arr = np.asarray(leaf)
        dtype_str = str(arr.dtype)
        if arr.dtype.kind == "V" or dtype_str not in np.sctypeDict:
            # ml_dtypes (bfloat16, fp8, ...): store a raw uint view and
            # record the logical dtype for the loader to view back
            dtype_str = str(leaf.dtype) if hasattr(leaf, "dtype") else dtype_str
            arr = arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
        # npz keys cannot contain '/'
        nkey = key.replace("/", "|")
        manifest["leaves"].append({
            "key": key, "npz_key": nkey, "shard": None,
            "shape": list(arr.shape), "dtype": dtype_str})
        if shard_bytes + arr.nbytes > budget:
            flush()
        manifest["leaves"][-1]["shard"] = shard_idx
        shard_data[nkey] = arr
        shard_bytes += arr.nbytes
    flush()
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    (tmp / _COMMIT).write_text(str(time.time()))
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)
    return final


def load_checkpoint(path: str | Path, tree_like: Any, *,
                    shardings: Any | None = None) -> tuple[Any, dict]:
    """Restore into the structure of ``tree_like``.

    ``shardings``: optional matching pytree of NamedShardings — the elastic
    restore path places each leaf on the (possibly different) target mesh.
    """
    path = Path(path)
    if not (path / _COMMIT).exists():
        raise FileNotFoundError(f"checkpoint {path} is not committed")
    manifest = json.loads((path / "manifest.json").read_text())
    by_key = {l["key"]: l for l in manifest["leaves"]}
    shards: dict[int, Any] = {}

    def get(key: str) -> np.ndarray:
        info = by_key[key]
        si = info["shard"]
        if si not in shards:
            shards[si] = np.load(path / f"shard_{si:05d}.npz")
        arr = shards[si][info["npz_key"]]
        if str(arr.dtype) != info["dtype"]:
            import ml_dtypes  # noqa: F401 - registers bf16/fp8 dtypes

            arr = arr.view(np.dtype(info["dtype"]))
        return arr

    leaves, treedef = _flatten(tree_like)
    if shardings is not None:
        sh_leaves = jax.tree.leaves(shardings)
        if len(sh_leaves) != len(leaves):
            # a partial/mismatched shardings pytree would zip-truncate
            # silently (list-shaped) or die deep in jax.tree.unflatten
            raise ValueError(
                f"shardings pytree has {len(sh_leaves)} leaves but "
                f"checkpoint {path} expects {len(leaves)}; pass one "
                f"sharding per restored leaf (or shardings=None)")
    else:
        sh_leaves = [None] * len(leaves)
    out = []
    for (key, like), sh in zip(leaves, sh_leaves):
        arr = get(key)
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jax.numpy.asarray(arr))
    tree = jax.tree.unflatten(jax.tree.structure(tree_like), out)
    return tree, manifest["metadata"] | {"step": manifest["step"]}


class CheckpointManager:
    """Retention + async save + latest-restore."""

    def __init__(self, directory: str | Path, *, keep: int = 3,
                 async_save: bool = False):
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self._sweep_tmp()  # a crash mid-save leaves orphaned .tmp_step_* dirs
        self.keep = keep
        self.async_save = async_save
        self._pending: threading.Thread | None = None
        # exception raised by the async writer thread, surfaced to the
        # caller on the next wait()/save()/restore_latest() instead of
        # dying silently in a daemon thread
        self._async_error: BaseException | None = None

    # ------------------------------------------------------------------ #
    def steps(self) -> list[int]:
        out = []
        for p in self.directory.glob("step_*"):
            if (p / _COMMIT).exists():
                out.append(int(p.name.split("_")[1]))
        return sorted(out)

    def save(self, step: int, tree: Any, metadata: dict | None = None) -> None:
        tree = jax.tree.map(np.asarray, tree)  # snapshot before async write

        def do():
            save_checkpoint(self.directory, step, tree, metadata=metadata)
            self._retain()

        if self.async_save:
            self.wait()  # re-raises a previous async failure before queuing more

            def do_async():
                try:
                    do()
                except BaseException as e:  # noqa: BLE001 - surfaced on wait()
                    self._async_error = e

            self._pending = threading.Thread(target=do_async, daemon=True)
            self._pending.start()
        else:
            do()

    def wait(self) -> None:
        """Block until the pending async save finishes.

        Re-raises any exception the writer thread hit — a failed
        checkpoint must not be discovered only at restore time.
        """
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        if self._async_error is not None:
            err = self._async_error
            self._async_error = None
            raise err

    def _sweep_tmp(self) -> None:
        """Remove uncommitted ``.tmp_step_*`` dirs from interrupted saves.

        Safe while a save is in flight: :func:`save_checkpoint` recreates
        its tmp dir from scratch, and the manager serializes saves (every
        ``save()`` waits for the previous async writer), so any tmp dir
        seen here belongs to a crashed writer, not a live one.
        """
        for p in self.directory.glob(".tmp_step_*"):
            shutil.rmtree(p, ignore_errors=True)

    def _retain(self) -> None:
        steps = self.steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(self.directory / f"step_{s:08d}", ignore_errors=True)
        self._sweep_tmp()

    def restore_latest(self, tree_like: Any, *, shardings: Any | None = None
                       ) -> tuple[Any, dict] | None:
        self.wait()
        steps = self.steps()
        if not steps:
            return None
        return load_checkpoint(self.directory / f"step_{steps[-1]:08d}",
                               tree_like, shardings=shardings)
