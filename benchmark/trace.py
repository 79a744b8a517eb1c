"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, device operations by name, collectives and their exposed part, and
the idle gaps by what the host was doing in them.

Read with `jax.profiler.ProfileData` alone. A device is a plane named
`/device:TPU:<n>`; its operations are the events of the line `XLA Ops`
(name: the HLO instruction). Host spans are `TraceAnnotation`s named
`bench.*`, on the host plane's thread lines, on the same clock."""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# an event's name is the whole HLO instruction: `%name = shape opcode(operands
# ...), attributes`. The opcode follows the result shape's closing bracket;
# operands may NAME a collective (`%all-reduce.3`) without being one.
OPCODE = r"[\])}] "
COLLECTIVE = re.compile(
    OPCODE + r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?\(")
INSTRUCTION = re.compile(r"^%(\S+) = (.*?)[\])}] ([a-z][\w-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(data):
    """{"devices": {id: [(name, start_s, end_s)]}, "spans": [(name,
    start_s, end_s)]} of a `ProfileData`."""
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
            elif not m:
                spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


# -- interval arithmetic ------------------------------------------------

def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of disjoint sorted `intervals` outside disjoint sorted
    `holes`."""
    out = []
    for a, b in intervals:
        at = a
        for ha, hb in holes:
            if hb <= at or ha >= b:
                continue
            if ha > at:
                out.append((at, ha))
            at = max(at, hb)
        if at < b:
            out.append((at, b))
    return out


def op_label(name):
    """An HLO instruction as a key of a metric line: its name, opcode and
    result shape without layouts, in the characters a name may have, at
    most 64."""
    m = INSTRUCTION.match(name)
    if m:
        shape = LAYOUT.sub("", name[len(m.group(1)) + 4:m.end(2) + 1])
        name = f"{m.group(1)}_{m.group(3)}_{shape}"
    return re.sub(r"[^A-Za-z0-9.-]+", "_", name).strip("_")[:64]


# -- the reduction ------------------------------------------------------

def reduce(raw):
    """What the readers read. Times in seconds; per-device quantities are
    averaged over the devices that ran an operation."""
    window = [s for s in raw["spans"] if s[0] == WINDOW_SPAN]
    ops_all = [e for evs in raw["devices"].values() for e in evs]
    if not ops_all:
        raise ValueError("the trace holds no device operation")
    if window:
        lo, hi = window[-1][1], window[-1][2]
    else:
        lo, hi = min(e[1] for e in ops_all), max(e[2] for e in ops_all)
    per_device, by_name, counts = [], {}, {}
    for dev, events in sorted(raw["devices"].items()):
        events = [(n, max(a, lo), min(b, hi)) for n, a, b in events
                  if min(b, hi) > max(a, lo)]
        if not events:
            continue
        busy = union([(a, b) for _, a, b in events])
        coll = union([(a, b) for n, a, b in events if COLLECTIVE.search(n)])
        compute = union([(a, b) for n, a, b in events
                         if not COLLECTIVE.search(n)])
        per_device.append({
            "device": dev, "busy": busy, "busy_s": length(busy),
            "collective_s": length(coll),
            "collective_exposed_s": length(subtract(coll, compute))})
        for n, a, b in events:
            by_name[n] = by_name.get(n, 0.0) + (b - a)
            counts[n] = counts.get(n, 0) + 1
    if not per_device:
        raise ValueError("no device operation inside the traced window")
    n_dev = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device) / n_dev
    first = per_device[0]
    gaps = subtract([(lo, hi)], first["busy"])
    spans = [s for s in raw["spans"] if s[0] != WINDOW_SPAN]
    return {
        "window_s": hi - lo, "devices": n_dev,
        "busy_s": mean("busy_s"),
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        # summed over devices; divide by `devices` for one device's share
        "op_seconds": by_name, "op_counts": counts,
        "gaps": [(_span_at((a + b) / 2, spans), b - a) for a, b in gaps],
        "spans": spans,
    }


def _span_at(t, spans):
    """The innermost benchmark span open at time t."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no_span"


def breakdown(reduced, top=10):
    """The `breakdown` of a traced run's last line: the device operations
    that took most time (seconds on one device, averaged), and the idle
    gaps by span: each span's total first, then the longest single gaps."""
    n = reduced["devices"]
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    totals = {}
    for name, dur in reduced["gaps"]:
        totals[name] = totals.get(name, 0.0) + dur
    gap_rows = [[f"total:{k}", v] for k, v in
                sorted(totals.items(), key=lambda kv: -kv[1])]
    gap_rows += [[k, v] for k, v in
                 sorted(reduced["gaps"], key=lambda kv: -kv[1])]
    return {"device_ops": [[op_label(k), v / n] for k, v in ops[:top]],
            "idle_gaps": gap_rows[:top]}


def seconds_matching(reduced, pattern):
    """(seconds on one device, events on one device) of the operations
    whose HLO instruction matches `pattern`."""
    rx = re.compile(pattern)
    n = reduced["devices"]
    hit = [k for k in reduced["op_seconds"] if rx.search(k)]
    return (sum(reduced["op_seconds"][k] for k in hit) / n,
            sum(reduced["op_counts"][k] for k in hit) / n)
