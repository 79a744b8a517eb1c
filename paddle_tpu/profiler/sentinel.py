"""Performance regression sentinel (PR 19, ROADMAP 7(b)).

The runtime twin of the static fusion linter: PR 15 proves "it fuses",
this module proves "it STAYS fast". Three pieces share one vocabulary:

  * a **record** — the JSON-able per-leg perf shape (goodput bucket
    distribution, split/bypass reason histogram, compile/retrace counts,
    step-time / serve p50/p99, tokens/sec) captured either over a whole
    run (`capture_record`) or over one live evaluation window (the
    watcher below);
  * a **baseline** — per-leg tolerance bands derived from a record
    (`bands_from_record`), kept in a JSON file the operator names
    (`PerfBaseline`: add/match/expire like the lint baseline). The
    repository ships no such file;
  * a **verdict** — `classify(record, bands)` names every band the
    record violates with a REASON_CODES entry: `perf_drift` (goodput /
    throughput floor), `split_regression` (a reason outside the baseline
    histogram, or hang/skip storms), `compile_storm` (retrace or
    decode/prefill-rebuild allowance), `latency_drift` (p50/p99 band).

The live watcher (`SENTINEL`, armed via FLAGS_sentinel or
`fusion_doctor --watch`) snapshots the accountant/registry once per
FLAGS_sentinel_window_s, classifies the window's delta-record against
the named leg of the operator's baseline file — or against its own
first clean window when no leg is configured — emits
`sentinel.check` / `sentinel.drift` /
`sentinel.recover` events, and holds a degraded latch that
telemetry_server's /readyz folds in (503 with the finding attached).

Cost discipline (the telemetry-plane rule): disarmed, every tick site
is one module-bool check; armed, a tick is one perf_counter read until
the window edge, and the per-window evaluation drains only the events
since the previous window.
"""
import json
import os
import threading
import time
from collections import deque

from ..framework.flags import _FLAGS, set_flags
from . import metrics as _metrics

__all__ = [
    "SENTINEL", "Sentinel", "PerfBaseline", "capture_record",
    "bands_from_record", "classify", "arm", "disarm",
    "tick", "sentinel_report", "sentinel_ready", "publish_metrics",
    "maybe_arm_from_flags",
]

RECORD_VERSION = 1

# The demotion/interruption surface a steady window is judged on. The
# benign lifecycle categories (serve.admit, serve.sample, aot.hit,
# serve.prefix_hit, ...) never enter the histogram — a baseline must not
# have to enumerate healthy traffic.
WATCHED_CATS = frozenset({
    "dispatch.bypass", "dispatch.retrace",
    "chain.split", "step.split", "step.deactivate",
    "serve.hang", "serve.refuse", "serve.evict", "serve.expire",
    "serve.cancel", "serve.degrade",
    "kernel.fallback", "aot.corrupt", "aot.version_skew",
})

# Verdict severity when one window violates several bands at once: the
# latch's headline finding is the worst one.
_SEVERITY = ("compile_storm", "split_regression", "perf_drift",
             "latency_drift")


# ---------------------------------------------------------------------------
# probes: one cheap counter snapshot, diffed per window
# ---------------------------------------------------------------------------

def _engine_tallies():
    """(serve_steps, decode_compiles, prefill_compiles, hangs) summed
    over the registered engines — raw field reads, no stats() percentile
    work on the hot path."""
    from . import telemetry_server as _telemetry
    steps = decode = prefill = hangs = 0
    for eng in list(_telemetry._ENGINES):
        try:
            st = eng._stats
            steps += st.steps
            decode += st.decode_compiles
            prefill += st.prefill_compiles
            hangs += st.hangs
        except Exception:
            continue
    return steps, decode, prefill, hangs


def _probe():
    """Absolute counters NOW. Two probes bracket a window; their diff is
    the window's record."""
    from .dispatch import STATS as D
    from .chain_fusion import CHAIN_STATS as C
    from .step_fusion import STEP_STATS as S
    from ..ops.guardian import GUARD_STATS as G
    from .events import EVENTS
    from .goodput import ACCOUNTANT
    serve_steps, decode, prefill, hangs = _engine_tallies()
    return {
        "t": time.perf_counter(),
        "steps": ACCOUNTANT.steps,
        "buckets": dict(ACCOUNTANT.buckets),
        "dispatch": D.misses + D.retraces,
        "chain": C.retraces,
        "step": S.retraces,
        "skips": G.steps_skipped,
        "serve_steps": serve_steps,
        "decode": decode,
        "prefill": prefill,
        "hangs": hangs,
        "serve_tokens": _metrics.SERVE.tokens.value,
        "events_seq": EVENTS.total,
    }


def _drain_reasons(since_seq):
    """Watched (category, reason) histogram of the events emitted after
    `since_seq`. The sentinel's own events are excluded — a drift verdict
    must not feed the next window's histogram."""
    from .events import fusion_events
    reasons = {}
    for e in fusion_events(since_seq=since_seq):
        cat, r = e["cat"], e.get("reason")
        if r is None or cat not in WATCHED_CATS:
            continue
        k = f"{cat}:{r}"
        reasons[k] = reasons.get(k, 0) + 1
    return reasons


def _quantiles_ms():
    T, S = _metrics.TRAIN, _metrics.SERVE
    return (round(T.step_s.quantile(0.5) * 1e3, 4),
            round(T.step_s.quantile(0.99) * 1e3, 4),
            round(S.step_s.quantile(0.5) * 1e3, 4),
            round(S.step_s.quantile(0.99) * 1e3, 4))


def _record_between(p0, p1, leg, reasons):
    """One comparable record from two probes (live window) — the same
    shape `capture_record` builds for a whole leg."""
    d = {k: p1[k] - p0[k] for k in
         ("steps", "serve_steps", "dispatch", "chain", "step",
          "skips", "decode", "prefill", "hangs")}
    buckets = {b: round(max(0.0, p1["buckets"].get(b, 0.0)
                            - p0["buckets"].get(b, 0.0)), 4)
               for b in p1["buckets"]}
    total = sum(buckets.values())
    window_s = max(1e-9, p1["t"] - p0["t"])
    if d["steps"] > 0 and d["serve_steps"] > 0:
        kind = "mixed"
    elif d["serve_steps"] > 0:
        kind = "serve"
    elif d["steps"] > 0:
        kind = "train"
    else:
        kind = "idle"
    t_p50, t_p99, s_p50, s_p99 = _quantiles_ms()
    tok = p1["serve_tokens"] - p0["serve_tokens"]
    tps = _metrics.TRAIN.tokens_per_s.value if kind == "train" \
        else round(tok / window_s, 2)
    return {
        "version": RECORD_VERSION,
        "leg": leg, "kind": kind,
        "window_s": round(window_s, 4),
        "steps": d["steps"], "serve_steps": d["serve_steps"],
        "goodput": round(buckets.get("productive", 0.0) / total, 4)
        if total > 0 else 0.0,
        "buckets_s": buckets,
        "step_ms_p50": t_p50, "step_ms_p99": t_p99,
        "serve_ms_p50": s_p50, "serve_ms_p99": s_p99,
        "tokens_per_sec": round(tps, 2),
        "reasons": dict(sorted(reasons.items())),
        "compiles": {k: d[k] for k in
                     ("dispatch", "chain", "step", "decode", "prefill")},
        "hangs": d["hangs"], "skips": d["skips"],
    }


_ZERO_PROBE = {"t": 0.0, "steps": 0, "buckets": {}, "dispatch": 0,
               "chain": 0, "step": 0, "skips": 0, "serve_steps": 0,
               "decode": 0, "prefill": 0, "hangs": 0, "serve_tokens": 0,
               "events_seq": 0}


def capture_record(leg, kind=None):
    """Whole-run record: absolute counters since the (freshly reset)
    process start, plus the watched reason histogram of the full
    flight-recorder ring. The caller owns slate hygiene (a fresh
    process, or a reset recorder)."""
    p = _probe()
    p0 = dict(_ZERO_PROBE)
    from .goodput import ACCOUNTANT
    p0["t"] = p["t"] - max(1e-9, sum(ACCOUNTANT.buckets.values()))
    rec = _record_between(p0, p, leg, _drain_reasons(0))
    rec["window_s"] = round(sum(v for v in p["buckets"].values()), 4)
    if kind:
        rec["kind"] = kind
    return rec


# ---------------------------------------------------------------------------
# bands: tolerance windows derived from a record
# ---------------------------------------------------------------------------

def bands_from_record(record, slack=25.0):
    """Tolerance bands a future record of the same leg must sit inside.
    `slack` scales the latency/throughput windows (wide across
    machines, 4x for the watcher's own same-process calibration). The
    structural bands are slack-independent: the reason
    histogram is closed over what the clean leg emitted, decode/prefill
    rebuilds get NO headroom (a steady engine never re-traces), and the
    goodput floor is half the observed fraction."""
    slack = max(1.0, float(slack))
    bands = {}
    if record.get("goodput", 0) > 0:
        bands["goodput_min"] = round(record["goodput"] / 2, 4)
    for k in ("step_ms_p50", "step_ms_p99", "serve_ms_p50",
              "serve_ms_p99"):
        if record.get(k, 0) > 0:
            bands[k + "_max"] = round(record[k] * slack, 4)
    if record.get("tokens_per_sec", 0) > 0:
        bands["tokens_per_sec_min"] = round(
            record["tokens_per_sec"] / slack, 4)
    reasons = record.get("reasons") or {}
    bands["allowed_reasons"] = sorted(reasons)
    bands["max_reason_counts"] = {k: max(4 * n, 8)
                                  for k, n in reasons.items()}
    comp = record.get("compiles") or {}
    bands["max_compiles"] = {
        k: (int(comp.get(k, 0)) if k in ("decode", "prefill")
            else int(comp.get(k, 0)) + max(2, int(comp.get(k, 0))))
        for k in ("dispatch", "chain", "step", "decode", "prefill")}
    bands["max_hangs"] = 2 * int(record.get("hangs", 0))
    bands["max_skips"] = max(2 * int(record.get("skips", 0)), 0)
    return bands


def classify(record, bands):
    """Every band the record violates, worst first. Each finding is
    machine-readable: {reason, metric, observed, bound, message} with
    `reason` on the REASON_CODES contract."""
    fs = []

    def hit(reason, metric, observed, bound, msg):
        fs.append({"reason": reason, "metric": metric,
                   "observed": observed, "bound": bound, "message": msg})

    active = record.get("steps", 0) > 0 or record.get("serve_steps", 0) > 0
    gp_min = bands.get("goodput_min")
    if gp_min is not None and active \
            and sum((record.get("buckets_s") or {}).values()) > 0.01 \
            and record.get("goodput", 0.0) < gp_min:
        hit("perf_drift", "goodput", record.get("goodput", 0.0), gp_min,
            f"goodput {record.get('goodput', 0.0):.4f} fell below the "
            f"baseline floor {gp_min:.4f}")
    tps_min = bands.get("tokens_per_sec_min")
    if tps_min is not None and active \
            and record.get("tokens_per_sec", 0) > 0 \
            and record["tokens_per_sec"] < tps_min:
        hit("perf_drift", "tokens_per_sec", record["tokens_per_sec"],
            tps_min, f"throughput {record['tokens_per_sec']} tok/s under "
            f"the baseline floor {tps_min}")
    for k, steps_key in (("step_ms_p50", "steps"),
                         ("step_ms_p99", "steps"),
                         ("serve_ms_p50", "serve_steps"),
                         ("serve_ms_p99", "serve_steps")):
        mx = bands.get(k + "_max")
        if mx is not None and record.get(steps_key, 0) > 0 \
                and record.get(k, 0) > mx:
            hit("latency_drift", k, record[k], mx,
                f"{k} {record[k]}ms left its band (max {mx}ms)")
    allowed = set(bands.get("allowed_reasons") or ())
    caps = bands.get("max_reason_counts") or {}
    for rk, n in sorted((record.get("reasons") or {}).items()):
        if rk not in allowed:
            hit("split_regression", rk, n, 0,
                f"reason {rk} ({n}x) is outside the baseline histogram")
        elif n > caps.get(rk, n):
            hit("split_regression", rk, n, caps[rk],
                f"reason {rk} fired {n}x (cap {caps[rk]})")
    maxc = bands.get("max_compiles") or {}
    for k, v in sorted((record.get("compiles") or {}).items()):
        if k in maxc and v > maxc[k]:
            hit("compile_storm", f"compiles.{k}", v, maxc[k],
                f"{k} compiles/retraces {v} exceeded the baseline "
                f"allowance {maxc[k]}")
    if "max_hangs" in bands and record.get("hangs", 0) > bands["max_hangs"]:
        hit("split_regression", "hangs", record["hangs"],
            bands["max_hangs"],
            f"{record['hangs']} watchdog hang(s) vs baseline allowance "
            f"{bands['max_hangs']}")
    if "max_skips" in bands and record.get("skips", 0) > bands["max_skips"]:
        hit("split_regression", "skips", record["skips"],
            bands["max_skips"],
            f"{record['skips']} guardian skip(s) vs baseline allowance "
            f"{bands['max_skips']}")
    fs.sort(key=lambda f: _SEVERITY.index(f["reason"]))
    return fs


# ---------------------------------------------------------------------------
# the per-leg baseline file (the operator's; none is shipped)
# ---------------------------------------------------------------------------

class PerfBaseline:
    """Per-leg perf bands with the fusion-lint baseline's hygiene: every
    entry carries a human note, `add` re-derives bands from a fresh
    record, `stale`/`expire` keep the file honest when legs are retired,
    saves are atomic (tmp + os.replace)."""

    def __init__(self, legs=None, policy=""):
        self.legs = dict(legs or {})
        self.policy = policy

    @classmethod
    def load(cls, path):
        if not os.path.exists(path):
            return cls()
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        if doc.get("version") != 1:
            raise ValueError(
                f"unsupported perf baseline version {doc.get('version')!r} "
                f"in {path}")
        return cls(doc.get("legs") or {}, doc.get("policy") or "")

    def save(self, path):
        doc = {"version": 1, "policy": self.policy,
               "legs": {k: self.legs[k] for k in sorted(self.legs)}}
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=2, sort_keys=False)
            f.write("\n")
        os.replace(tmp, path)

    def add(self, record, note, slack=25.0):
        """(Re)seed the entry for the record's leg. Idempotent per leg:
        a re-capture replaces the bands; the existing note survives an
        empty one."""
        leg = record["leg"]
        prev = self.legs.get(leg) or {}
        entry = {
            "kind": record.get("kind", ""),
            "note": note or prev.get("note") or "",
            "slack": float(slack),
            "captured": {k: record.get(k) for k in
                         ("window_s", "steps", "serve_steps", "goodput",
                          "step_ms_p50", "step_ms_p99", "serve_ms_p50",
                          "serve_ms_p99", "tokens_per_sec", "hangs",
                          "skips", "compiles", "reasons")},
            "bands": bands_from_record(record, slack=slack),
        }
        if not entry["note"]:
            raise ValueError(
                f"perf baseline entry for leg {leg!r} needs a note "
                "(why these bands, when to tighten)")
        self.legs[leg] = entry
        return entry

    def match(self, leg):
        return self.legs.get(leg)

    def split(self, records):
        """(violations, passed, unbaselined) over comparable records:
        violations are (record, findings) pairs."""
        violations, passed, unbaselined = [], [], []
        for rec in records:
            entry = self.match(rec.get("leg"))
            if entry is None:
                unbaselined.append(rec)
                continue
            fs = classify(rec, entry["bands"])
            if fs:
                violations.append((rec, fs))
            else:
                passed.append(rec)
        return violations, passed, unbaselined

    def stale(self, records):
        """Entries no provided record exercises — retired legs that
        should expire (mirrors Baseline.stale for suppressions)."""
        seen = {r.get("leg") for r in records}
        return [leg for leg in sorted(self.legs) if leg not in seen]

    def expire(self, records):
        dead = self.stale(records)
        for leg in dead:
            del self.legs[leg]
        return dead


# ---------------------------------------------------------------------------
# the live watcher
# ---------------------------------------------------------------------------

_TICKING = False


class Sentinel:
    """Bounded-overhead drift watcher. One instance per process
    (`SENTINEL`); `tick()` rides the optimizer-step boundary and the
    engine decode step."""

    def __init__(self):
        self._lock = threading.Lock()
        self._eval_lock = threading.Lock()
        self.reset()

    def reset(self):
        self.armed = False
        self.leg = ""
        self.baseline_path = ""
        self.bands = None
        self.band_source = None    # "baseline" | "self" | None
        self.window_s = 10.0
        self.windows = 0
        self.checks = {}
        self.degraded = False
        self.finding = None
        self.findings = []
        self.last_record = None
        self.history = deque(maxlen=32)
        self._probe0 = None
        self._next_eval = 0.0
        self._restore_flags = {}

    # -- arming -------------------------------------------------------------

    def arm(self, leg=None, baseline=None, window_s=None):
        """Arm the watcher. Needs the accountant and the flight recorder:
        both flags are raised if off and restored on disarm (the Profiler
        window discipline). A named leg takes its bands from the
        baseline file the operator gives (`baseline=` or
        FLAGS_sentinel_baseline) and is refused without one; with no
        leg the first non-idle window self-calibrates a reference band
        (slack 4x: same host, same process)."""
        global _TICKING
        from .events import EVENTS
        leg = leg if leg is not None \
            else str(_FLAGS.get("FLAGS_sentinel_leg") or "")
        path = baseline if baseline is not None \
            else str(_FLAGS.get("FLAGS_sentinel_baseline") or "")
        bands = None
        if leg:
            # refused BEFORE any flag is borrowed or state is reset
            if not path:
                raise ValueError(
                    f"sentinel leg {leg!r} needs a baseline file: pass "
                    "baseline= or set FLAGS_sentinel_baseline (no file "
                    "is shipped; with no leg the sentinel calibrates on "
                    "its own first clean window)")
            entry = PerfBaseline.load(path).match(leg)
            if entry is None:
                raise ValueError(
                    f"no baseline entry for leg {leg!r} in {path}")
            bands = entry["bands"]
        with self._lock:
            restore = {}
            for fl in ("FLAGS_metrics", "FLAGS_profiler_events"):
                if not _FLAGS.get(fl):
                    restore[fl] = False
            if restore:
                set_flags({k: True for k in restore})
            self.reset()
            self._restore_flags = restore
            self.leg = leg
            self.baseline_path = path
            try:
                self.window_s = float(
                    window_s if window_s is not None
                    else _FLAGS.get("FLAGS_sentinel_window_s", 10.0))
            except (TypeError, ValueError):
                self.window_s = 10.0
            self.window_s = max(0.05, self.window_s)
            if bands is not None:
                self.bands = bands
                self.band_source = "baseline"
            self.armed = True
            self._probe0 = _probe()
            self._next_eval = self._probe0["t"] + self.window_s
            _TICKING = True
        EVENTS.emit("sentinel.arm", op=self.leg or "self",
                    detail={"window_s": self.window_s,
                            "bands": self.band_source or "self"})

    def disarm(self):
        """Stop ticking, restore borrowed flags. The last verdict stays
        readable (postmortem), but a disarmed sentinel never holds
        /readyz degraded."""
        global _TICKING
        with self._lock:
            _TICKING = False
            self.armed = False
            self.degraded = False
            restore, self._restore_flags = self._restore_flags, {}
        if restore:
            set_flags(restore)

    # -- the hot path -------------------------------------------------------

    def tick(self):
        """One perf_counter read per step until the window edge."""
        if time.perf_counter() < self._next_eval:
            return
        if not self._eval_lock.acquire(blocking=False):
            return                 # another thread owns this window
        try:
            self._evaluate()
        finally:
            self._eval_lock.release()

    def _evaluate(self):
        from .events import EVENTS
        p0, p1 = self._probe0, _probe()
        if p0 is None:
            return
        reasons = _drain_reasons(p0["events_seq"])
        rec = _record_between(p0, p1, self.leg or "live", reasons)
        self._probe0 = p1
        self._next_eval = p1["t"] + self.window_s
        self.windows += 1
        self.last_record = rec
        if rec["kind"] == "idle":
            # nothing stepped: no judgment, no recovery — a wedged
            # process must not "recover" by going silent
            self.checks["idle"] = self.checks.get("idle", 0) + 1
            self.history.append({"window": self.windows,
                                 "verdict": "idle"})
            return
        if self.bands is None:
            # self-calibration: the first active window IS the reference
            self.bands = bands_from_record(rec, slack=4.0)
            self.band_source = "self"
            self.checks["calibrate"] = self.checks.get("calibrate", 0) + 1
            self.history.append({"window": self.windows,
                                 "verdict": "calibrate"})
            EVENTS.emit("sentinel.check", op=rec["kind"],
                        detail={"window": self.windows,
                                "calibrated": True})
            return
        findings = classify(rec, self.bands)
        if findings:
            worst = findings[0]
            verdict = worst["reason"]
            self.checks[verdict] = self.checks.get(verdict, 0) + 1
            self.findings = findings
            self.finding = dict(worst, window=self.windows,
                                leg=self.leg or "self")
            flipped = not self.degraded
            self.degraded = True
            self.history.append({"window": self.windows,
                                 "verdict": verdict,
                                 "metric": worst["metric"]})
            EVENTS.emit("sentinel.drift", op=worst["metric"],
                        reason=verdict,
                        detail={"window": self.windows,
                                "observed": worst["observed"],
                                "bound": worst["bound"],
                                "findings": len(findings),
                                "flipped": flipped})
        else:
            self.checks["clean"] = self.checks.get("clean", 0) + 1
            self.history.append({"window": self.windows,
                                 "verdict": "clean"})
            if self.degraded:
                self.degraded = False
                EVENTS.emit("sentinel.recover",
                            op=(self.finding or {}).get("metric", ""),
                            detail={"window": self.windows})
            else:
                EVENTS.emit("sentinel.check", op=rec["kind"],
                            detail={"window": self.windows})
            self.findings = []

    # -- reading ------------------------------------------------------------

    def snapshot(self):
        """The /sentinel endpoint body — everything a supervisor needs
        to route a page without parsing prose."""
        return {
            "armed": self.armed,
            "leg": self.leg or None,
            "band_source": self.band_source,
            "window_s": self.window_s,
            "windows": self.windows,
            "checks": dict(self.checks),
            "degraded": bool(self.armed and self.degraded),
            "finding": self.finding if self.degraded else None,
            "findings": self.findings if self.degraded else [],
            "last_record": self.last_record,
            "bands": self.bands,
            "history": list(self.history),
        }


SENTINEL = Sentinel()


# ---------------------------------------------------------------------------
# module entry points (the disarmed cost: one bool check)
# ---------------------------------------------------------------------------

def tick():
    if not _TICKING:
        return
    SENTINEL.tick()


def arm(leg=None, baseline=None, window_s=None):
    SENTINEL.arm(leg=leg, baseline=baseline, window_s=window_s)
    return SENTINEL


def disarm():
    SENTINEL.disarm()


def maybe_arm_from_flags():
    """FLAGS_sentinel=1 in the environment arms the watcher at import /
    engine build, like FLAGS_telemetry_port starts the HTTP plane."""
    if _FLAGS.get("FLAGS_sentinel") and not SENTINEL.armed:
        arm()
    return SENTINEL.armed


def sentinel_report():
    return SENTINEL.snapshot()


def sentinel_ready():
    """The /readyz contribution: {armed, degraded, finding}."""
    degraded = bool(SENTINEL.armed and SENTINEL.degraded)
    return {"armed": SENTINEL.armed, "degraded": degraded,
            "finding": SENTINEL.finding if degraded else None}


def publish_metrics(reg):
    """Scrape-time collector bridge (metrics._install_collectors): the
    watcher itself never touches the registry on its hot path."""
    s = SENTINEL
    if s.windows:
        fam = reg.get("sentinel_checks_total")
        for verdict, n in s.checks.items():
            fam.labels(verdict=verdict).set_raw(n)
    reg.get("sentinel_degraded")._default.set_raw(
        1 if (s.armed and s.degraded) else 0)
