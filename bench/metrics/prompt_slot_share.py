"""Occupied slot-steps that consumed a prompt token over all occupied
slot-steps of the window.  Moves ``ttft_p95_s``."""


def read(run: dict):
    steps = run.get("steps", ())
    occupied = sum(s["occupied"] for s in steps)
    if not occupied:
        return None
    return 100.0 * sum(s["prompt_slots"] for s in steps) / occupied
