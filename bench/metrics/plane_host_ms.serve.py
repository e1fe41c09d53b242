"""Per decode step of the traced batch, the event loop's time in the serve
plane's own callbacks (the outermost ``serve.plane.*`` spans: step, pump,
tick, submit) less the decode steps inside them, in ms, with the profiler
on (``bench/program_spans.py``).  A decode step is taken as the benchmark
times it (its step records, each of which holds one ``serve.step`` and
the benchmark's own wrapper around it), so that the wrapper is not
counted as the plane's work.  Moves ``tbt_p95_ms``."""
from bench.program_spans import traced_batch


def read(run: dict):
    batch = traced_batch(run)
    if batch is None:
        return None
    spans, t0, stop = batch
    steps = [s for s in run.get("steps", ())
             if t0 <= s["t_call"] and s["t_return"] <= stop]
    if not steps:
        return None
    plane = [s for s in spans if s["name"].startswith("serve.plane.")]
    ids = {s["id"] for s in plane}
    outer = sum(s["end"] - s["start"] for s in plane if s["parent"] not in ids)
    decode = sum(s["t_return"] - s["t_call"] for s in steps)
    return 1e3 * (outer - decode) / len(steps)
