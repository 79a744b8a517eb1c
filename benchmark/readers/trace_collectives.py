"""Percent of the traced window one device spends in collective
operations (`part` "all"), or in the part of them during which no other
operation runs on that device (`part` "exposed")."""


def read(evidence, part):
    trace = evidence.get("trace")
    if not trace or evidence.get("chips", 1) < 2:
        return None
    key = {"all": "collective_s", "exposed": "collective_exposed_s"}[part]
    return 100.0 * trace[key] / trace["window_s"]
