"""Median over the window's steps of the step's wall time less its shard
gradient calls (each ends in ``float(loss)``, a sync): the supervisor's
host work per step, in ms.  Moves ``train_tokens_per_s``."""
import statistics


def read(run: dict):
    steps = [s for s in run.get("steps", ()) if s["end"] <= run["window"][1]]
    if run.get("plane") != "train" or not steps:
        return None
    return 1e3 * statistics.median(s["wall"] - s["grad_calls_s"] for s in steps)
