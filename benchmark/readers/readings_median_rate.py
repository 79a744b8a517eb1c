"""Tokens per second of the window's median reading: the pace of a step
when nothing stalls, steadier than the end-to-end rate it stands beside."""
from .. import stats


def read(evidence):
    readings = evidence.get("readings")
    return stats.median_rate(readings) if readings else None
