"""Percent of the KV pool's blocks that requests held, averaged over the
engine steps of the window."""


def read(evidence):
    held = evidence.get("pool_blocks_held")
    if not held:
        return None
    return 100.0 * sum(held) / (len(held) * evidence["engine_facts"]["pool_blocks"])
