"""Occupied slot-steps over all slot-steps of the window's decode steps,
counted per step from the step's inputs.  Moves ``serve_tokens_per_s``."""


def read(run: dict):
    steps = run.get("steps", ())
    if not steps:
        return None
    return 100.0 * sum(s["occupied"] for s in steps) / (len(steps) * run["max_batch"])
