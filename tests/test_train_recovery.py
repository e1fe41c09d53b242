"""Training-plane WRATH: recovery from host loss, NaN, stragglers, OOM;
checkpoint-resume continuity; elastic re-meshing.

Every test here drives real multi-second jax training sweeps, so the
whole module runs in the ``slow`` CI job (``pytest -m slow``)."""
import pytest

from repro.configs import get_smoke_config
from repro.optim import OptConfig
from repro.train import TrainEvent, WrathTrainSupervisor

pytestmark = pytest.mark.slow


def mk(tmp_path, tag, **kw):
    cfg = get_smoke_config("granite_3_2b")
    defaults = dict(n_hosts=3, global_batch=6, seq_len=32,
                    ckpt_dir=str(tmp_path / tag), ckpt_every=5)
    defaults.update(kw)
    return WrathTrainSupervisor(
        cfg, OptConfig(lr=5e-3, warmup_steps=5, total_steps=40), **defaults)


def test_clean_run_converges(tmp_path):
    sup = mk(tmp_path, "clean")
    rep = sup.run(25)
    assert rep.steps_completed == 25
    assert rep.losses[-1] < rep.losses[0]
    assert not rep.recoveries


def test_host_loss_elastic_remesh(tmp_path):
    sup = mk(tmp_path, "hostloss")
    rep = sup.run(20, events=[TrainEvent(step=5, kind="host_down",
                                         host="host01")])
    assert rep.final_hosts == 2          # re-meshed to surviving hosts
    # the host is lost during step 5: its shard fails and is recovered
    assert [(r["step"], r["host"], r["error"]) for r in rep.recoveries] == [
        (5, "host01", "HardwareShutdownError")]
    assert rep.recovered_all
    assert rep.steps_completed == 20
    assert rep.losses[-1] < rep.losses[0]


def test_nan_restores_checkpoint(tmp_path):
    sup = mk(tmp_path, "nan")
    rep = sup.run(25, events=[TrainEvent(step=12, kind="nan")])
    assert rep.restores >= 1
    assert any(r["error"] == "NumericalDivergenceError" for r in rep.recoveries)
    assert rep.losses[-1] < rep.losses[0]


def test_straggler_speculation_and_denylist(tmp_path):
    sup = mk(tmp_path, "strag")
    rep = sup.run(30, events=[TrainEvent(step=5, kind="straggler",
                                         host="host02", factor=50)])
    assert rep.speculations >= 1
    assert "host02" in rep.denylisted     # chronic straggler denylisted


def test_oom_shard_routed_to_big_host(tmp_path):
    """A shard too big for regular hosts lands on the big-memory host via
    the feasibility-aware retry ladder."""
    sup = mk(tmp_path, "oom", host_memory_gb=0.5, shard_memory_gb=1.0)
    rep = sup.run(6)
    assert rep.steps_completed == 6
    assert any(r["error"] == "MemoryError" and r["action"] != "fail"
               for r in rep.recoveries)


def test_checkpoint_resume_continuity(tmp_path):
    sup = mk(tmp_path, "resume")
    rep1 = sup.run(12)
    # a new supervisor over the same ckpt dir resumes past step 10
    sup2 = mk(tmp_path, "resume")
    rep2 = sup2.run(20)
    assert rep2.steps_completed <= 10     # only the remaining steps ran
    assert rep2.losses[-1] <= rep1.losses[0]


def test_elastic_host_join_reshards_live(tmp_path):
    """A host joining mid-run becomes part of the data-parallel mesh on
    the very next step — batch shards spread over one more host."""
    sup = mk(tmp_path, "join")
    rep = sup.run(20, events=[TrainEvent(step=5, kind="host_join",
                                         host="host99")])
    assert rep.final_hosts == 4           # 3 seed hosts + the joiner
    assert rep.steps_completed == 20
    assert rep.losses[-1] < rep.losses[0]
    joins = [e for e in sup.monitor.system_events
             if e["event"] == "host_join"]
    assert joins and joins[0]["node"] == "host99"


def test_elastic_host_leave_reshards_live(tmp_path):
    """A decommissioned host drops out of the mesh without a recovery
    event — leave is planned, not a failure."""
    sup = mk(tmp_path, "leave")
    rep = sup.run(20, events=[TrainEvent(step=5, kind="host_leave",
                                         host="host02")])
    assert rep.final_hosts == 2
    assert rep.steps_completed == 20
    assert rep.losses[-1] < rep.losses[0]


def test_join_then_leave_round_trip(tmp_path):
    sup = mk(tmp_path, "roundtrip")
    rep = sup.run(20, events=[
        TrainEvent(step=4, kind="host_join", host="hostX"),
        TrainEvent(step=10, kind="host_leave", host="hostX")])
    assert rep.final_hosts == 3           # back to the seed mesh
    assert rep.steps_completed == 20
