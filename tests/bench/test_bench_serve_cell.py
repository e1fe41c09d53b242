"""A serve cell's run at smoke size on the CPU, through the harness's own
plane function: the batch client's fresh-state reset, the served tokens
against the reference, and ``correct`` turning false when the timed path
is broken underneath."""
import pytest

from bench_smoke import run_cell, smoke_cell
from bench import serve_phase as sp
from bench.harness import Spans
from bench.traffic_gen import closed_batch, steps_of

CELL = ("granite-3-2b", "serve-gen")


def test_each_batch_starts_whole_on_a_fresh_state():
    cell = smoke_cell(*CELL)
    rig = sp.build(cell.config, cell.traffic, 2**31 + 11, Spans())
    sp.warm_up(rig, 11)
    seen = []
    for index in range(3):
        reqs = closed_batch(cell.traffic, seed=11, index=index, vocab=256)
        b = sp.run_batch(rig, reqs)
        (name,) = rig.backend._caches
        seen.append(name)
        recs = rig.backend.records[b.first_step:b.end_step]
        assert recs[0].occupied == len(reqs)               # seated at step 0
        assert len(recs) == max(steps_of(r) for r in reqs)
        assert sp.batch_problems(rig, b) == []
        assert all(s.status == "done" for s in b.served)
    assert len(set(seen)) == 3                              # a new replica each batch
    sp.release_program_state(rig)


@pytest.mark.parametrize("cell", [CELL, ("granite-3-2b", "serve-prompt"),
                                  ("mamba2-780m", "serve-gen")],
                         ids=lambda c: "-".join(c))
def test_a_sound_run_is_correct(cell):
    out = run_cell(smoke_cell(*cell))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    e2e = out["end_to_end"]
    assert e2e["serve_tokens_per_s"] > 0 and e2e["ttft_p95_s"] > 0
    assert out["checks"]["served_tokens_compared"]["value"] >= 8


def _altered(self, replica, inputs):
    return [(t + 1) % self.cfg.vocab_size for t in _orig_step(self, replica, inputs)]


def _state_unchanged(self, replica, inputs):
    before = self._caches[replica.name]
    out = _orig_step(self, replica, inputs)
    self._caches[replica.name] = before
    return out


def _half_batch(self, replica, inputs):
    half = len(inputs) // 2
    return _orig_step(self, replica, inputs[:half] + [0 if t is not None else None
                                                      for t in inputs[half:]])


_orig_step = sp.BenchDecodeBackend.step


@pytest.mark.parametrize("fault", [_altered, _state_unchanged, _half_batch],
                         ids=["token_altered", "state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(sp.BenchDecodeBackend, "step", fault)
    out = run_cell(smoke_cell(*CELL))
    assert not out["correct"]
    assert not out["checks"]["served_logit_gap"]["ok"]


def test_the_float8_control_is_not_correct(monkeypatch):
    # The control in the program's place: at each served position the
    # token the float8 reference puts first, through the run's own check.
    # At smoke size sound runs read under 0.01 and the control about 0.3,
    # under the committed limit set from full-size readings (PERF.md), so
    # the check is held to a limit that sound smoke runs pass.
    def cell():
        c = smoke_cell(*CELL)
        c.config["limits"]["served_logit_gap"] = 0.1
        return c

    assert run_cell(cell())["correct"]
    monkeypatch.setattr(sp, "served_gap", sp.control_gap)
    out = run_cell(cell())
    assert not out["correct"]
    assert not out["checks"]["served_logit_gap"]["ok"], out["checks"]
