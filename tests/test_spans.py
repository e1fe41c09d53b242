"""Host spans: the monitor's span log, and the serve plane and decode step
that record into it."""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import MonitoringDatabase
from repro.core.monitoring import SPAN_RING, SpanLog
from repro.serve import ServeRequest, WrathServeDriver
from repro.sim import VirtualClock

STEP_CHILDREN = ["serve.step.pack", "serve.step.put", "serve.step.launch",
                 "serve.step.sample", "serve.step.wait", "serve.step.unpack"]


class CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` and counts uses."""

    names: list[str] = []

    def __init__(self, name):
        CountingAnnotation.names.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture
def annotations(monkeypatch):
    CountingAnnotation.names = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    return CountingAnnotation.names


def test_nesting_and_parents_on_two_threads():
    log = SpanLog()
    log.enable()
    inner_started, main_done = threading.Event(), threading.Event()

    def worker():
        with log.span("w.outer"):
            with log.span("w.inner", rid=7):
                inner_started.set()
                main_done.wait(5.0)

    t = threading.Thread(target=worker)
    t.start()
    assert inner_started.wait(5.0)
    # the worker's open spans are not this thread's parents
    with log.span("m.outer"):
        with log.span("m.inner", step=3):
            pass
    main_done.set()
    t.join(5.0)
    assert not t.is_alive()
    spans = {s["name"]: s for s in log.export()}
    assert spans["m.inner"]["parent"] == spans["m.outer"]["id"]
    assert spans["w.inner"]["parent"] == spans["w.outer"]["id"]
    assert spans["m.outer"]["parent"] is None
    assert spans["w.outer"]["parent"] is None
    assert spans["w.inner"]["rid"] == 7 and spans["m.inner"]["step"] == 3
    for s in spans.values():
        assert s["start"] <= s["end"]
    assert spans["m.outer"]["start"] <= spans["m.inner"]["start"]
    assert spans["m.inner"]["end"] <= spans["m.outer"]["end"]


def test_ring_keeps_the_newest_and_record_adds_an_ended_span():
    log = SpanLog()
    log.enable()
    for i in range(SPAN_RING + 2):
        with log.span("s", step=i):
            pass
    kept = log.export()
    assert len(kept) == SPAN_RING
    assert kept[0]["step"] == 2 and kept[-1]["step"] == SPAN_RING + 1
    with log.span("open"):
        log.record("serve.queue_wait", 1.0, 2.5, rid=9)
    wait = [s for s in log.export() if s["name"] == "serve.queue_wait"]
    assert wait == [{"id": wait[0]["id"], "name": "serve.queue_wait",
                     "start": 1.0, "end": 2.5, "parent": None, "rid": 9}]


def test_disabled_log_records_nothing_and_never_annotates(annotations):
    log = SpanLog()
    with log.span("a") as s:
        assert s is None
    log.record("b", 0.0, 1.0)
    assert log.export() == [] and annotations == []
    annotated = SpanLog()
    annotated.enable(annotate=True)
    with annotated.span("c"):
        pass
    with log.span("d"):
        pass
    assert annotations == ["c"]
    assert [s["name"] for s in annotated.export()] == ["c"]
    assert log.export() == []


def test_log_stays_off_under_a_virtual_clock():
    monitor = MonitoringDatabase(clock=VirtualClock(), keep_event_log=True)
    with pytest.raises(ValueError):
        monitor.spans.enable()
    assert not monitor.spans.enabled


def _jax_driver():
    cfg = get_smoke_config("granite_3_2b")
    return WrathServeDriver(cfg, n_replicas=1, max_batch=4, max_len=32)


def _requests(n=6):
    rng = np.random.default_rng(3)
    return [ServeRequest(rid=i, prompt=rng.integers(0, 256, 3).tolist(),
                         max_new_tokens=2 + i % 3) for i in range(n)]


def test_serve_plane_spans_with_the_log_on(annotations):
    driver = _jax_driver()
    driver.monitor.spans.enable(annotate=True)
    reqs = _requests()
    rep = driver.serve_continuous(reqs, horizon=120.0)
    driver.shutdown()
    assert rep.completed == len(reqs)
    spans = driver.monitor.spans.export()
    by_id = {s["id"]: s for s in spans}
    children: dict = {}
    for s in spans:
        children.setdefault(s["parent"], []).append(s)
    plane_steps = [s for s in spans if s["name"] == "serve.plane.step"]
    assert len(plane_steps) == rep.decode_steps
    for ps in plane_steps:
        kids = sorted(children[ps["id"]], key=lambda s: s["start"])
        names = [k["name"] for k in kids]
        assert names[:2] == ["serve.step", "serve.plane.advance"]
        assert set(names[2:]) <= {"serve.plane.pump"}
        step = kids[0]
        assert step["replica"] == ps["replica"]
        inner = sorted(children[step["id"]], key=lambda s: s["start"])
        assert [k["name"] for k in inner] == STEP_CHILDREN
        for a, b in zip(inner, inner[1:]):
            assert a["end"] <= b["start"]
        assert ps["start"] <= step["start"] and step["end"] <= ps["end"]
    assert all(by_id[s["parent"]]["name"] == "serve.step"
               for s in spans if s["name"] in STEP_CHILDREN)
    waits = [s for s in spans if s["name"] == "serve.queue_wait"]
    assert sorted(s["rid"] for s in waits) == [r.rid for r in reqs]
    assert all(s["start"] <= s["end"] for s in waits)
    assert {s["name"] for s in spans} >= {"serve.plane.submit",
                                          "serve.plane.pump"}
    # with annotate, every span but the queue wait is also a profiler span
    assert len(annotations) == len(spans) - len(waits)


def test_serve_plane_records_nothing_with_the_log_off(annotations):
    driver = _jax_driver()
    rep = driver.serve_continuous(_requests(), horizon=120.0)
    driver.shutdown()
    assert rep.completed == 6
    assert driver.monitor.spans.export() == []
    assert annotations == []
    assert all(r.queued_pc is None for r in driver._requests)


def test_a_replayed_request_records_a_second_queue_wait():
    driver = WrathServeDriver(None, n_replicas=2, max_batch=4, decode="sim")
    driver.monitor.spans.enable()
    reqs = [ServeRequest(rid=i, prompt=[1, 2, 3], max_new_tokens=12)
            for i in range(4)]
    rep = driver.serve_continuous(reqs, faults=[(0.08, "kill", "replica0")],
                                  horizon=30.0)
    driver.shutdown()
    assert rep.completed == 4 and rep.recoveries
    replayed = {r["rid"] for r in rep.recoveries}
    waits = [s["rid"] for s in driver.monitor.spans.export()
             if s["name"] == "serve.queue_wait"]
    assert sorted(waits) == sorted([0, 1, 2, 3] + list(replayed))
