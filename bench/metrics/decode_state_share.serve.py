"""Share of the traced window's device busy time in the decode program's
ops that move its state: those under the ``kv_write`` scope, and the layer
scan's own per-layer slices and write-backs of the stacked decode state
(``layer_state``; the scan's slices of the stacked weights are
``layer_weights`` and do not count), by ``bench/scope_reduce.py``.
Moves ``serve_tokens_per_s``."""
from bench.scope_reduce import STATE


def read(run: dict):
    scopes, trace = run.get("scopes"), run.get("trace")
    if run.get("plane") != "serve" or not scopes or not trace \
            or trace["busy_s"] <= 0:
        return None
    return 100.0 * sum(scopes[c] for c in STATE) / trace["busy_s"]
