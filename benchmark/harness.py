"""What every loop shares: the run's facts, the device check, host spans,
the traced slice, the per-layer readers and the result line."""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import tempfile
import time

from . import peaks, trace

clock = time.perf_counter


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


class Run:
    """One run of one cell. `root` holds BENCHMARK.json and the data
    directories; `require_chip=False` is the tests' way to drive a tiny
    cell on the CPU, and no command line reaches it."""

    def __init__(self, root, workload, seed, seconds, traced,
                 require_chip=True, t_start=None):
        self.root = root
        self.t_start = clock() if t_start is None else t_start
        self.spec = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                             f"known: {sorted(cells)}")
        self.cell = cells[workload]
        self.data = os.path.join(root, self.spec["paths"][0])
        files = {c["name"]: c["file"] for c in self.spec["configs"]}
        self.config = load_json(root, files[self.cell["config"]])
        self.traffic = load_json(self.data, "traffic",
                                 self.cell["traffic"] + ".json")
        limits = os.path.join(self.data, "limits", workload + ".json")
        self.limits = load_json(limits) if os.path.exists(limits) else {}
        self.seed, self.seconds, self.traced = int(seed), float(seconds), \
            bool(traced)
        self.require_chip = require_chip
        self.spans = Spans()
        self.checks = []          # (name, value, limit, ok)
        self.evidence = {"config": self.config, "traffic": self.traffic,
                         "cell": self.cell}
        self.reference_s = 0.0    # the reference's time is not set-up
        self.devices = None

    # -- the device ------------------------------------------------------
    def claim_devices(self):
        import jax
        devices = jax.devices()
        want = self.cell["chips"]
        if self.require_chip and (devices[0].platform != "tpu"
                                  or len(devices) < want):
            raise NoChip(f"{self.cell['name']} needs {want} TPU chip(s); "
                         f"JAX found {len(devices)} x "
                         f"{devices[0].platform!r}")
        if len(devices) < want:
            raise NoChip(f"{want} devices wanted, {len(devices)} found")
        self.devices = devices[:want]
        kind = devices[0].device_kind
        # the CPU of a test's tiny cell has no peaks; its readers see None
        self.evidence["peaks"] = peaks.peaks_for(kind) \
            if devices[0].platform == "tpu" else None
        return self.devices

    def device_record(self, program_peak=0):
        d = self.devices[0]
        stats = [dev.memory_stats() or {} for dev in self.devices]
        peak = max([s.get("peak_bytes_in_use", 0) for s in stats]
                   + [int(program_peak)])
        rec = {"platform": d.platform, "kind": d.device_kind,
               "count": len(self.devices), "memory_peak_bytes": peak}
        reduced = self.evidence.get("trace")
        if reduced:
            rec["busy_s"] = reduced["busy_s"]
            rec["window_s"] = reduced["window_s"]
        return rec

    # -- the comparison with the reference -------------------------------
    def check(self, name, value, limit_key=None):
        """One number compared beside its limit (from the cell's limits
        file; a cell without one compares nothing and is never correct)."""
        limit = self.limits.get(limit_key or name)
        ok = limit is not None and value == value and value <= limit
        self.checks.append((name, value, limit, ok))
        print(json.dumps({"check": name, "value": value, "limit": limit,
                          "ok": ok}), flush=True)
        return ok

    @contextlib.contextmanager
    def reference_time(self):
        t0 = clock()
        yield
        self.reference_s += clock() - t0

    def setup_seconds(self, t_open):
        return t_open - self.t_start - self.reference_s

    # -- the traced slice ------------------------------------------------
    @contextlib.contextmanager
    def traced_slice(self):
        """Profile the block; leaves the reduced trace in the evidence."""
        import jax
        out = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(out)
            try:
                with self.spans.trace_annotations(), \
                        self.spans(trace.WINDOW_SPAN):
                    yield
            finally:
                jax.profiler.stop_trace()
            self.evidence["trace"] = trace.reduce(
                trace.load(trace.newest_xplane(out)))
        finally:
            shutil.rmtree(out, ignore_errors=True)

    # -- the result line -------------------------------------------------
    def per_layer_metrics(self):
        out = {}
        name = self.cell["name"]
        for m in self.spec["per_layer"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            spec = load_json(self.data, "metrics", m["name"] + ".json")
            reader = importlib.import_module(spec["reader"])
            value = reader.read(self.evidence, **spec.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def end_to_end_metrics(self, values):
        out = {}
        name = self.cell["name"]
        for m in self.spec["end_to_end"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            if m["name"] not in values:
                raise KeyError(f"the {self.traffic['loop']} loop reports no "
                               f"{m['name']}, which BENCHMARK.json asks of "
                               f"{name}")
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out

    def result(self, end_to_end, attempted, failed, program_peak=0):
        correct = bool(self.checks) and all(c[3] for c in self.checks)
        device = self.device_record(program_peak)
        self.evidence["memory_peak_bytes"] = device["memory_peak_bytes"]
        line = {"correct": correct, "attempted": int(attempted),
                "failed": int(failed),
                "metrics": self.per_layer_metrics() if self.traced
                else self.end_to_end_metrics(end_to_end),
                "device": device}
        if self.traced and self.evidence.get("trace"):
            line["breakdown"] = trace.breakdown(self.evidence["trace"])
        return line


class Spans:
    """The benchmark's own spans around its calls into the program: kept
    in memory always, and written into the profiler's trace while one is
    being taken."""

    def __init__(self):
        self.records = []         # (name, start_s, end_s)
        self._annotate = False

    @contextlib.contextmanager
    def trace_annotations(self):
        self._annotate = True
        try:
            yield
        finally:
            self._annotate = False

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = clock()
        if self._annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, clock()))
