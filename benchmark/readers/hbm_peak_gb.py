"""Peak device memory on the fullest chip, in GB: for a train cell the
live arrays plus the compiled step's temporaries (`memory_analysis()`;
the backend's own peak misses them), otherwise the backend's peak."""


def read(evidence):
    peak = evidence.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
