"""Metric arithmetic, on plain numbers: what a train window's step times
become."""
from __future__ import annotations

import statistics

# steps at the head of a train window that only fill the device queue
QUEUE_FILL_STEPS = 2


def window_rate(steps, tokens_per_step, window_s):
    """All the window's tokens over all its time: what a stall moves."""
    return steps * tokens_per_step / window_s


def train_readings(ready_s, tokens_per_step, count):
    """Cut a window's steps into `count` readings of an equal number of
    steps, for the per-layer view of the window: the pace of its median
    reading, and what the window spent beyond that pace.

    `ready_s[i]` is the host-clock time at which step i's result was seen
    ready while later steps were being dispatched (the device queue never
    drained in between). A reading is its steps' tokens over the time
    between the ready moments that bound it. Returns {"steps_per_reading",
    "tokens_per_s": [...]}."""
    usable = len(ready_s) - 1 - QUEUE_FILL_STEPS
    per = usable // count
    if per < 1:
        raise ValueError(f"{len(ready_s)} steps do not make {count} "
                         "readings")
    edges = [QUEUE_FILL_STEPS + j * per for j in range(count + 1)]
    rates = [per * tokens_per_step / (ready_s[b] - ready_s[a])
             for a, b in zip(edges, edges[1:])]
    return {"steps_per_reading": per, "tokens_per_s": rates}


def median_rate(readings):
    return statistics.median(readings["tokens_per_s"])


def stall_share(readings, window_tokens_per_s):
    """Percent of the window that its steps, at the median reading's
    pace, do not account for: what stalls added to it."""
    return 100.0 * (1.0 - window_tokens_per_s / median_rate(readings))
