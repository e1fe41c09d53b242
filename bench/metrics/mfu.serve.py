"""The whole decode step's share of the chip's peak: the least time the
window's steps need (per step the larger of operations over peak FLOP/s
and needed bytes over peak bytes/s, from shapes and slot positions; see
``bench/cost.py``) over the window's seconds, less the time a traced run
spends stopping its trace.  Moves ``serve_tokens_per_s``."""
from bench import cost


def read(run: dict):
    steps = run.get("steps", ())
    if not steps:
        return None
    need = sum(cost.step_seconds(*cost.decode_step(run["model"], s["occupied"],
                                                   s["position"]), run["peaks"])
               for s in steps)
    t0, t1 = run["window"]
    return 100.0 * need / (t1 - t0 - run.get("window_excluded_s", 0.0))
