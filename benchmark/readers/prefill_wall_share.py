"""Percent of the window's wall time inside `engine.step()` but outside the
compiled decode step: admission, prefill and the scheduler's bookkeeping.
From the benchmark's span around each `engine.step()` call and the
engine's own sum of decode-step times."""


def read(evidence):
    if "engine_step_s" not in evidence:
        return None
    t_open, t_close = evidence["window"]
    return 100.0 * (evidence["engine_step_s"] - evidence["decode_s"]) \
        / (t_close - t_open)
