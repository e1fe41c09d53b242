"""Share of the traced window in which no operation ran on the device,
in a train cell.  Moves ``train_tokens_per_s``."""


def read(run: dict):
    trace = run.get("trace")
    if run.get("plane") != "train" or not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
