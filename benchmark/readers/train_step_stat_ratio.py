"""A ratio of sums of the live `TrainStep`'s `stats()` counters (the
newest step's counters of a model that counts), scaled (`scale`, and
`scale_by`, keys of the configuration file whose product turns a largest
load over a total into largest over mean). `less` keys are taken off the
top: a difference of counters over nothing (`under` empty) is the
difference itself."""
from ..programs import paddle_train_stats


def read(evidence, over, under=(), less=(), scale=1.0, scale_by=()):
    stats = paddle_train_stats.newest_train_step_stats()
    if not stats or any(k not in stats for k in (*over, *under, *less)):
        return None
    top = sum(stats[k] for k in over) - sum(stats[k] for k in less)
    for key in scale_by:
        scale = scale * evidence["config"][key]
    if not under:
        return scale * top
    bottom = sum(stats[k] for k in under)
    return scale * top / bottom if bottom else None
