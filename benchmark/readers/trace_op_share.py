"""Percent of one device's busy time in the operations whose HLO
instruction matches `pattern`. `{pool_shape}` in the pattern stands for the
engine's KV pool shape, comma-separated, as HLO prints it."""
import re

from .. import trace as tr


def read(evidence, pattern):
    trace = evidence.get("trace")
    if not trace:
        return None
    facts = evidence.get("engine_facts") or {}
    if "{pool_shape}" in pattern:
        if not facts.get("pool_shape"):
            return None
        pattern = pattern.replace("{pool_shape}", re.escape(
            ",".join(map(str, facts["pool_shape"]))))
    seconds, _ = tr.seconds_matching(trace, pattern)
    return 100.0 * seconds / trace["busy_s"]
