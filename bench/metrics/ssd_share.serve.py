"""Share of the traced window's device busy time in the decode program's
ops under the ``ssd`` scope (the Mamba-2 mixers: projections,
convolution, state update and read-out), by ``bench/scope_reduce.py``.
Moves ``serve_tokens_per_s``."""


def read(run: dict):
    scopes, trace = run.get("scopes"), run.get("trace")
    if run.get("plane") != "serve" or not scopes or not trace \
            or trace["busy_s"] <= 0:
        return None
    return 100.0 * scopes["ssd"] / trace["busy_s"]
