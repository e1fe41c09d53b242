"""Mean time from the return of one decode step to the call of the next on
the same replica, over the window's steps: the serve plane's host work
between steps.  Moves ``tbt_p95_ms``."""


def read(run: dict):
    gaps = [s["gap_before"] for s in run.get("steps", ())
            if s["gap_before"] is not None]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
