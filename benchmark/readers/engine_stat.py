"""One counter of `engine.stats()` over the window, scaled."""


def read(evidence, key, scale=1.0):
    stats = evidence.get("engine_stats")
    if not stats or key not in stats:
        return None
    return scale * stats[key]
