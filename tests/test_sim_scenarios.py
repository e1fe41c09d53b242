"""Deterministic simulation plane: virtual clock, scenario DSL, seeded
chaos campaigns, determinism regression, and WRATH-specific properties.

The chaos property holds under *any* seed; ``hypothesis`` explores the
seed space adaptively, and the failing seed is printed and reproduces
the run exactly (``run_scenario(Scenario.random(seed))``).
"""
import json

import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.engine.events import EventLoop
from repro.engine.policies import ProactivePolicy, WrathPolicy
from repro.sim import (
    Fault,
    NodeSpec,
    Scenario,
    SimTaskSpec,
    VirtualClock,
    campaign,
    run_scenario,
)

# --------------------------------------------------------------------- #
# virtual clock + event loop basics
# --------------------------------------------------------------------- #
def test_virtual_clock_advances_only_by_decree():
    clock = VirtualClock()
    assert clock.now() == 0.0
    clock.advance(5.0)
    assert clock.now() == 5.0
    clock.advance_to(3.0)                 # never backwards
    assert clock.now() == 5.0
    assert clock.time() == VirtualClock.EPOCH + 5.0


def test_event_loop_run_until_executes_in_timestamp_order():
    clock = VirtualClock()
    loop = EventLoop(clock=clock)
    seen = []
    loop.call_later(2.0, lambda: seen.append(("b", clock.now())))
    loop.call_later(1.0, lambda: seen.append(("a", clock.now())))
    loop.call_later(3.0, lambda: seen.append(("c", clock.now())))
    n = loop.run_until()
    assert n == 3
    assert seen == [("a", 1.0), ("b", 2.0), ("c", 3.0)]
    assert clock.now() == 3.0


def test_event_loop_run_until_deadline_stops_and_lands_clock():
    clock = VirtualClock()
    loop = EventLoop(clock=clock)
    seen = []
    loop.schedule_periodic(1.0, lambda: seen.append(clock.now()))
    loop.run_until(deadline=4.5)
    assert seen == [1.0, 2.0, 3.0, 4.0]
    assert clock.now() == 4.5             # landed exactly on the deadline


def test_event_loop_run_until_predicate_stops_between_events():
    clock = VirtualClock()
    loop = EventLoop(clock=clock)
    seen = []
    for i in range(10):
        loop.call_later(float(i + 1), lambda i=i: seen.append(i))
    loop.run_until(lambda: len(seen) >= 3)
    assert seen == [0, 1, 2]
    assert clock.now() == 3.0


def test_event_loop_refuses_run_until_on_real_clock():
    loop = EventLoop()
    with pytest.raises(RuntimeError, match="virtual clock"):
        loop.run_until()


# --------------------------------------------------------------------- #
# a "60-second" scenario in microseconds
# --------------------------------------------------------------------- #
def test_minute_long_heartbeat_loss_scenario_runs_instantly():
    """The tentpole claim: a long heartbeat-silence scenario needs no
    wall-clock time — virtual time jumps straight between events."""
    import time as wall

    scenario = Scenario(
        seed=0,
        nodes=[NodeSpec("n0", workers=1), NodeSpec("n1", workers=1)],
        tasks=[SimTaskSpec(at=0.0, name="long", duration=60.0)],
        faults=[Fault(at=30.0, kind="node_down", node="n1")],
        horizon=200.0)
    t0 = wall.perf_counter()
    result = run_scenario(scenario, heartbeat_period=1.0)
    elapsed = wall.perf_counter() - t0
    assert result.ok, result.violations
    assert result.outcomes["long"][0] == "ok"
    assert elapsed < 2.0                  # ~200 virtual seconds of events


# --------------------------------------------------------------------- #
# determinism regression (satellite)
# --------------------------------------------------------------------- #
def test_same_seed_produces_byte_identical_event_trace():
    first = run_scenario(Scenario.random(1234))
    second = run_scenario(Scenario.random(1234))
    assert first.trace == second.trace
    assert first.trace                      # non-trivial scenario
    # every counter matches; wrath_overhead_s is *real* measured seconds
    # (policy-hook cost) and is the one legitimately wall-clock stat
    drop = "wrath_overhead_s"
    assert ({k: v for k, v in first.stats.items() if k != drop}
            == {k: v for k, v in second.stats.items() if k != drop})


def test_different_seeds_produce_different_traces():
    a = run_scenario(Scenario.random(1234))
    b = run_scenario(Scenario.random(4321))
    assert a.trace != b.trace


def test_scenario_generation_is_seed_deterministic():
    assert Scenario.random(77) == Scenario.random(77)
    assert Scenario.random(77) != Scenario.random(78)


# --------------------------------------------------------------------- #
# campaign invariants (the CI chaos gate, small here; 500 runs nightly)
# --------------------------------------------------------------------- #
def test_chaos_campaign_invariants_hold_across_seeds():
    report = campaign(30, base_seed=0, determinism_checks=2)
    assert report.ok, report.summary()
    assert len(report.results) == 30
    # the sweep must actually exercise chaos, not trivially-green runs
    assert any(r.stats["failed"] or r.stats["dep_failed"]
               for r in report.results)
    assert any(r.stats["retries"] for r in report.results)


def test_chaos_campaign_with_proactive_stack():
    report = campaign(15, base_seed=100,
                      policy_factory=lambda: [ProactivePolicy(),
                                              WrathPolicy()],
                      determinism_checks=1)
    assert report.ok, report.summary()


def test_chaos_campaign_baseline_policy_still_conserves_tasks():
    report = campaign(15, base_seed=200, policy_factory=lambda: None,
                      determinism_checks=1)
    assert report.ok, report.summary()


# --------------------------------------------------------------------- #
# WRATH-specific properties
# --------------------------------------------------------------------- #
def test_resolvable_spec_modification_failures_succeed_by_replacement():
    """§VII-C: a 200 GB spec-injected task fails on the 192 GB node but a
    big-memory node exists — WRATH's hierarchical retry must save it."""
    scenario = Scenario(
        seed=0,
        nodes=[NodeSpec("small", memory_gb=192.0),
               NodeSpec("big", memory_gb=6144.0)],
        tasks=[SimTaskSpec(at=0.0, name="hungry", fail="memory"),
               SimTaskSpec(at=0.0, name="needs-pkg", fail="import")],
        horizon=60.0)
    # wrathpkg exists nowhere -> only the memory task is resolvable
    result = run_scenario(scenario)
    assert result.ok, result.violations
    assert result.outcomes["hungry"] == ("ok", 0)
    assert result.outcomes["needs-pkg"][0] == "error"


def test_destined_to_fail_tasks_fast_fail_under_proactive_policy():
    """Fig 4: with no feasible node anywhere, the proactive plane must
    terminate the task before it burns a single attempt."""
    scenario = Scenario(
        seed=0,
        nodes=[NodeSpec("a", memory_gb=8.0), NodeSpec("b", memory_gb=8.0)],
        tasks=[SimTaskSpec(at=0.0, name="monster", fail="memory")],
        horizon=60.0)
    reactive = run_scenario(scenario)
    proactive = run_scenario(
        scenario, policy_factory=lambda: [ProactivePolicy(), WrathPolicy()])
    assert reactive.outcomes["monster"][0] == "error"
    assert proactive.outcomes["monster"][0] == "error"
    assert proactive.stats["fast_fails"] >= 1
    assert proactive.stats["retries"] == 0       # terminated pre-attempt
    assert proactive.stats["retries"] < reactive.stats["retries"] or (
        reactive.stats["retries"] == 0)


def test_cancelled_scope_stays_cancelled_under_chaos():
    scenario = Scenario(
        seed=0,
        nodes=[NodeSpec("n0", workers=1)],
        tasks=[SimTaskSpec(at=0.0, name="member0", duration=5.0,
                           workflow="wf"),
               SimTaskSpec(at=0.1, name="member1", duration=5.0,
                           workflow="wf"),
               SimTaskSpec(at=6.0, name="late", duration=5.0,
                           workflow="wf")],
        faults=[Fault(at=1.0, kind="cancel_workflow", workflow="wf")],
        horizon=60.0,
        workflows={"wf": "none"})
    result = run_scenario(scenario)
    assert result.ok, result.violations
    # every member resolved with the cancellation, including the one
    # submitted after the scope died
    assert all(kind == "error" for kind, _ in result.outcomes.values()), \
        result.outcomes


# --------------------------------------------------------------------- #
# engine crash/restart: the lineage-aware checkpoint plane under chaos
# --------------------------------------------------------------------- #
def _crash_dag(crash_at=None):
    """A linear 8-task DAG, one arrival per 0.5s; optionally crash mid-run."""
    tasks = [SimTaskSpec(at=i * 0.5, name=f"t{i:03d}", duration=0.3,
                         depends_on=(i - 1,) if i else ())
             for i in range(8)]
    faults = ([Fault(at=crash_at, kind="engine_crash")]
              if crash_at is not None else [])
    return Scenario(seed=7, tasks=tasks, faults=faults, horizon=60.0)


def _outcome_bytes(result):
    return json.dumps(result.outcomes, sort_keys=True, default=repr).encode()


def test_engine_crash_reexecutes_only_the_incomplete_frontier():
    """Acceptance property: after a mid-campaign crash the rebuilt engine
    re-executes exactly the tasks without a committed result, and the
    final results match the crash-free run byte for byte."""
    crashed = run_scenario(_crash_dag(crash_at=2.2))
    clean = run_scenario(_crash_dag())
    assert crashed.ok, crashed.violations
    assert crashed.crashes == 1
    committed = crashed.committed_at_crash[0]
    assert 0 < committed < 8              # the crash landed mid-DAG
    assert crashed.stats["memo_hits"] == committed
    assert crashed.reexecuted == 8 - committed
    assert _outcome_bytes(crashed) == _outcome_bytes(clean)


def test_engine_crash_trace_is_seed_deterministic():
    first = run_scenario(_crash_dag(crash_at=2.2))
    second = run_scenario(_crash_dag(crash_at=2.2))
    assert first.trace == second.trace
    assert "engine_restart" in first.trace
    assert "memoized" in first.trace


def test_engine_crash_with_injected_failures_keeps_failures_uncommitted():
    """Destined-to-fail tasks are never memoized: they re-execute after
    the restart and fail identically, while healthy committed siblings
    resolve from the store."""
    tasks = [SimTaskSpec(at=0.0, name="ok0", duration=0.2),
             SimTaskSpec(at=0.1, name="doomed", duration=0.2,
                         fail="zero_division", max_retries=0),
             SimTaskSpec(at=0.2, name="ok1", duration=0.2),
             SimTaskSpec(at=5.0, name="late", duration=0.2)]
    scenario = Scenario(seed=3, tasks=tasks,
                        faults=[Fault(at=1.0, kind="engine_crash")],
                        horizon=60.0)
    result = run_scenario(scenario)
    assert result.ok, result.violations
    assert result.outcomes["doomed"][0] == "error"
    assert result.outcomes["ok0"] == ("ok", 0)
    assert result.outcomes["late"] == ("ok", 3)
    # ok0/ok1 committed pre-crash -> memo hits; doomed + late re-executed
    assert result.committed_at_crash == [2]
    assert result.stats["memo_hits"] == 2


def test_engine_crash_preserves_heartbeat_silence():
    """Heartbeat silence is *environment* state: a paused monitoring
    agent must stay paused across the engine restart, so the rebuilt
    engine still detects the loss instead of the fault healing itself."""
    scenario = Scenario(
        seed=5,
        nodes=[NodeSpec("n0", workers=1), NodeSpec("n1", workers=1)],
        # the second arrival keeps the run alive past the staleness
        # window (last beat t=1 + 0.5*5 threshold -> loss check at t=4)
        tasks=[SimTaskSpec(at=3.0, name="late", duration=0.2),
               SimTaskSpec(at=6.0, name="later", duration=0.2)],
        faults=[Fault(at=1.0, kind="hb_pause", node="n1"),
                Fault(at=2.0, kind="engine_crash")],
        horizon=60.0)
    result = run_scenario(scenario, heartbeat_period=0.5)
    assert result.ok, result.violations
    assert result.crashes == 1
    assert "heartbeat_lost" in result.trace   # detected *after* the restart


def test_random_campaign_samples_engine_crashes_and_invariants_hold():
    report = campaign(40, base_seed=300, determinism_checks=2)
    assert report.ok, report.summary()
    crashed = [r for r in report.results if r.crashes]
    assert crashed                        # the sampler exercises the path
    assert any(r.stats["memo_hits"] for r in crashed)


# --------------------------------------------------------------------- #
# the chaos property, hypothesis-driven when available
# --------------------------------------------------------------------- #
def _assert_campaign_property(seed: int) -> None:
    scenario = Scenario.random(seed, max_tasks=12)
    result = run_scenario(scenario)
    assert result.ok, (
        f"invariants violated for seed={seed}: {result.violations}\n"
        f"reproduce: run_scenario(Scenario.random({seed}, max_tasks=12))")
    replay = run_scenario(Scenario.random(seed, max_tasks=12))
    assert replay.trace == result.trace, (
        f"nondeterminism for seed={seed}")


@settings(max_examples=25, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_chaos_property_any_seed(seed):
    _assert_campaign_property(seed)
