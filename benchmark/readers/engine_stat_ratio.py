"""A ratio of sums of `engine.stats()` counters over the window, scaled
(`scale`, and `scale_by`, a key of the configuration file: a count of
experts held turns a largest load over a total into largest over mean)."""


def read(evidence, over, under, scale=1.0, scale_by=None):
    stats = evidence.get("engine_stats")
    if not stats or any(k not in stats for k in over + under):
        return None
    bottom = sum(stats[k] for k in under)
    if not bottom:
        return None
    if scale_by:
        scale = scale * evidence["config"][scale_by]
    return scale * sum(stats[k] for k in over) / bottom
