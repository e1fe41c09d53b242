"""Mean over the traced batch's ``serve.step`` spans (the program's decode
step, ``JaxDecodeBackend.step``) of the span less its ``serve.step.wait``
child, in ms: the host's work inside the step (packing, ``device_put``,
dispatch, argmax, unpacking) while the device waits on it, with the
profiler on (``bench/program_spans.py``).  Moves ``tbt_p95_ms``."""
from bench.program_spans import traced_batch


def read(run: dict):
    batch = traced_batch(run)
    if batch is None:
        return None
    spans, _, _ = batch
    wait = {s["parent"]: s["end"] - s["start"] for s in spans
            if s["name"] == "serve.step.wait"}
    host = [s["end"] - s["start"] - wait.get(s["id"], 0.0) for s in spans
            if s["name"] == "serve.step"]
    return 1e3 * sum(host) / len(host) if host else None
