"""A train cell's run at smoke size on the CPU, through the harness's own
plane function: the host loss in the window's 5th step, the 3-row shard
warmed in set-up, the first steps against the reference, and ``correct``
turning false when the timed path is broken underneath."""
import pytest

import repro.train.supervisor as supervisor_mod
from bench_smoke import run_cell, smoke_cell

CELL = ("granite-3-2b-l4", "train-hostloss")


def test_a_sound_run_recovers_the_host_and_is_correct():
    out = run_cell(smoke_cell(*CELL), seconds=3.0)
    assert out["correct"], out["checks"]
    steps = out["run"]["steps"]
    # the 5th step of the window loses host01: its shard fails and is
    # retried elsewhere; later steps split the batch over the 3 hosts left
    assert "host01" in steps[4]["hosts"] and len(steps[4]["shards"]) >= 4
    for s in steps[5:]:
        assert "host01" not in s["hosts"] and len(s["hosts"]) == 3
        assert set(s["shards"]) <= {2, 3}
    # the 3-row shard was compiled in set-up: nothing compiles in the window
    assert out["checks"]["window_compiles"]["value"] == 0
    assert out["checks"]["host_loss_recovered"]["ok"]
    assert out["end_to_end"]["train_tokens_per_s"] > 0


def _state_unchanged(params, grads, state, cfg):
    return params, state, {}


_orig_shard = supervisor_mod.WrathTrainSupervisor._shard_task


def _half_batch(self, step, host, params, batch, injected_nan):
    n = batch["inputs"].shape[0]
    keep = (n + 1) // 2
    half = {k: v[[i % keep for i in range(n)]] for k, v in batch.items()}
    return _orig_shard(self, step, host, params, half, injected_nan)


def _loss_altered(self, step, host, params, batch, injected_nan):
    loss, grads = _orig_shard(self, step, host, params, batch, injected_nan)
    return loss * 1.05, grads


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "loss_altered"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(supervisor_mod, "adamw_apply", _state_unchanged)
    else:
        monkeypatch.setattr(supervisor_mod.WrathTrainSupervisor, "_shard_task",
                            _half_batch if fault == "half_batch" else _loss_altered)
    out = run_cell(smoke_cell(*CELL), seconds=1.5)
    assert not out["correct"]
    failed = {k for k, c in out["checks"].items() if not c["ok"]}
    assert failed & {"loss_rel_gap", "first_grad_leaf_gap", "change_leaf_gap"}
