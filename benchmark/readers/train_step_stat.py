"""One key of the live `TrainStep`'s `stats()`, scaled: the host time of a
phase of its `__call__` (fed by the program's span `train_step.<phase>`), or
the count of programs it has traced. Covers every call since the step was
built: the checked steps, the window and the traced slice."""
from ..programs import paddle_train_stats


def read(evidence, key, scale=1.0):
    stats = paddle_train_stats.newest_train_step_stats()
    if not stats or key not in stats:
        return None
    return scale * stats[key]
