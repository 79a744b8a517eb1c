"""Percent of the window that its steps, at the median reading's pace, do
not account for."""
from .. import stats


def read(evidence):
    readings = evidence.get("readings")
    if not readings:
        return None
    return stats.stall_share(readings, evidence["window_tokens_per_s"])
