"""Wall time of the step in which a host is lost, less the median step
wall time of the window.  Moves ``train_tokens_per_s``."""
import statistics


def read(run: dict):
    steps = [s for s in run.get("steps", ()) if s["end"] <= run["window"][1]]
    if run.get("plane") != "train" or not steps or not run.get("loss_steps"):
        return None
    first = steps[0]["k"]
    lost = [s["wall"] for s in steps if s["k"] - first in run["loss_steps"]]
    if not lost:
        return None
    return lost[0] - statistics.median(s["wall"] for s in steps)
