"""The program's own spans in a serve run's record, for the readers in
``bench/metrics``.

``run["program_spans"]`` is the export of the serve plane's span log
(``MonitoringDatabase.spans``, on ``time.perf_counter()``), and
``run["trace_stop"]`` the time on that clock when the profiler trace
began to stop: the end of the batch it traced.  Every reader of these
spans reads that batch alone, from the window's start to
``trace_stop``, so each reads one regime: the host's times with the
profiler on.  It is the batch a ``--trace 1`` run always holds (at 40 s
its window holds no other, since stopping the trace takes longer than
the rest of the window), and the batch the device metrics read.
"""
from __future__ import annotations


def traced_batch(run: dict) -> tuple[list[dict], float, float] | None:
    """The spans that lie inside the traced batch, and its start and end;
    None where the run has no program spans or no ``trace_stop``."""
    spans, stop = run.get("program_spans"), run.get("trace_stop")
    if not spans or stop is None:
        return None
    t0 = run["window"][0]
    return [s for s in spans if t0 <= s["start"] and s["end"] <= stop], t0, stop
