"""95th percentile over the traced batch's requests of the time from
joining the queue to being seated in a slot (the program's
``serve.queue_wait`` spans), in ms, with the profiler on
(``bench/program_spans.py``).  Moves ``ttft_p95_s``."""
from bench.harness import percentile
from bench.program_spans import traced_batch


def read(run: dict):
    batch = traced_batch(run)
    if batch is None:
        return None
    waits = [1e3 * (s["end"] - s["start"]) for s in batch[0]
             if s["name"] == "serve.queue_wait"]
    return percentile(waits, 95) if waits else None
