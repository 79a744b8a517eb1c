"""Device-busy milliseconds per step of the traced slice."""


def read(evidence):
    trace, steps = evidence.get("trace"), evidence.get("traced_steps")
    if not trace or not steps:
        return None
    return 1e3 * trace["busy_s"] / steps
