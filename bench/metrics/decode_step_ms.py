"""Mean host-clock time of ``JaxDecodeBackend.step`` over the window's
steps; the call ends in ``np.asarray`` of the next tokens, a sync.  Moves
``tbt_p95_ms``."""


def read(run: dict):
    steps = run.get("steps", ())
    if not steps:
        return None
    return 1e3 * sum(s["t_return"] - s["t_call"] for s in steps) / len(steps)
