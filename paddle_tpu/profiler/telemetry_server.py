"""Live HTTP observability plane: scrape the telemetry fabric over a port.

PR 12 made every number live (metrics registry, goodput accountant,
per-request traces) but left them trapped in-process: an operator whose
trainer wedged inside a device call that never returns had NOTHING to
ask the process. This module is the missing
always-on monitor surface — a zero-dependency stdlib
`ThreadingHTTPServer`, gated by ``FLAGS_telemetry_port`` (default 0 =
off: no thread, no socket, and every heartbeat site is one module-bool
check), serving on 127.0.0.1:

  ``/metrics``       Prometheus text exposition of the live registry
                     (profiler/metrics.py — the same snapshot the JSONL
                     sinks persist);
  ``/metrics.json``  the registry snapshot as JSON (what
                     tools/fleet_metrics.py scrapes and merges);
  ``/goodput``       the goodput accountant snapshot — rolling MFU /
                     tokens-per-second, wall-time buckets, AND the
                     per-step attribution rings ("steps 1032, 2048
                     skipped; 4096-4103 stalled");
  ``/doctor``        the fusion doctor report (profiler/explain.explain
                     over the flight-recorder ring) as JSON — the same
                     schema as ``fusion_doctor --json``, so
                     ``fusion_doctor --url http://host:port`` diagnoses
                     a RUNNING process without attaching;
  ``/events``        bounded tail of the flight-recorder ring
                     (``?n=256``, capped);
  ``/healthz``       liveness: the optimizer/decode step heartbeat is
                     fresher than the watchdog window (200 healthy /
                     503 unhealthy) — the endpoint that diagnoses a
                     blind hang in seconds;
  ``/readyz``        readiness: every registered engine has its decode
                     program compiled (or has not been asked to serve
                     yet) and is NOT in the degraded latch — plus the
                     AOT warm-start state (200 ready / 503 not).

Liveness semantics (``/healthz``): a source is stale when its heartbeat
age exceeds its window. Serving engines use the armed watchdog budget
(``FLAGS_serve_step_timeout_ms``) as the window — a hang flips the
endpoint unhealthy within ONE watchdog window — falling back to
``FLAGS_telemetry_stale_s`` when disarmed; an IDLE engine (nothing
queued or running) is never stale. The training heartbeat
(goodput.on_step — beaten at every optimizer boundary, metrics armed or
not) is stale after ``FLAGS_telemetry_stale_s`` only while the
accountant's window is open (``finalize()`` closes it, so a finished
bench child reads healthy-idle, not dead).

Readiness semantics (``/readyz``): supervisors gate traffic on it — a
degraded engine (watchdog ladder / decode fault) reports 503 until its
first clean decode step clears the latch; a fresh engine that has not
served yet is ready (its first request pays the compile or the AOT warm
start, both by design).

Cost contract: everything rides existing snapshots; the server thread
only works while a scraper is connected. ``beat()`` is a module-bool
check + dict store, called once per optimizer boundary / decode step.
Kill-9 mid-scrape can never wedge a restart:
`allow_reuse_address` is set, so the replacement process rebinds the
port immediately (tests/test_telemetry_server.py proves it).
"""
from __future__ import annotations

import json
import threading
import time
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

from ..framework.flags import _FLAGS

__all__ = ["TelemetryServer", "start", "stop", "maybe_start_from_flags",
           "beat", "register_engine", "server", "server_port",
           "server_url", "health_report", "ready_report", "doctor_report",
           "events_tail", "probe_endpoint"]


def probe_endpoint(url, timeout=10):
    """GET one telemetry endpoint: (status, parsed body). The client
    counterpart every prober shares (chaos, the doctor, tests) so the
    endpoint contract has ONE reader: 4xx/5xx JSON bodies (healthz 503)
    are parsed and returned as data, JSON is decoded, /metrics text
    comes back as a string. Network errors propagate to the caller."""
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            status, body = r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        status, body = e.code, e.read().decode()
    try:
        return status, json.loads(body)
    except ValueError:
        return status, body            # /metrics Prometheus text

# module-bool gate: the ONLY cost a heartbeat site pays when no server
# runs (the flight recorder's one-flag-check discipline, but cheaper —
# no dict lookup)
_ARMED = False
_SERVER = None                      # the running TelemetryServer
_HEART: dict = {}                   # kind -> (perf_counter ts, step)
_ENGINES: "weakref.WeakSet" = weakref.WeakSet()
_EVENTS_TAIL_DEFAULT = 256
_EVENTS_TAIL_CAP = 4096


def beat(kind, step=None):
    """Record a liveness heartbeat (one bool check when no server runs).
    Wired at every optimizer-step boundary (profiler/goodput.on_step —
    NOT gated on FLAGS_metrics: liveness must not require the metrics
    plane) and every clean serving decode step (serving/engine.py).
    `step=None` auto-increments the source's own counter, so the step
    number in /healthz keeps moving even when the goodput accountant is
    disarmed; counters reset with the server's heartbeat window."""
    if not _ARMED:
        return
    if step is None:
        prev = _HEART.get(kind)
        step = ((prev[1] or 0) + 1) if prev else 1
    _HEART[kind] = (time.perf_counter(), step)


def register_engine(engine):
    """Track an LLMEngine (weakly) for /healthz busy-staleness and
    /readyz degraded/decode-compiled state. Always-on: registration must
    predate a server started later in the process's life."""
    _ENGINES.add(engine)


# ---------------------------------------------------------------------------
# report builders (also importable directly — the endpoints just render)
# ---------------------------------------------------------------------------

def _stale_window_s():
    try:
        return float(_FLAGS.get("FLAGS_telemetry_stale_s", 120.0) or 120.0)
    except (TypeError, ValueError):
        return 120.0


def _engine_window_s():
    """Liveness window for a serving engine: the armed watchdog budget
    (a hang must flip /healthz within ONE window), else the generic
    staleness default."""
    from ..serving.resilience import watchdog_budget_s
    budget = watchdog_budget_s()
    return budget if budget is not None else _stale_window_s()


def health_report():
    """Liveness view: heartbeat ages vs their windows. `healthy` is the
    conjunction; `last_heartbeat_age_s` is the freshest signal."""
    now = time.perf_counter()
    stale_s = _stale_window_s()
    healthy = True
    ages = []
    sources = {}
    for kind, (ts, step) in sorted(_HEART.items()):
        age = now - ts
        ages.append(age)
        sources[kind] = {"age_s": round(age, 4), "step": step}
    train = sources.get("train")
    if train is not None:
        from . import goodput as _goodput
        finalized = _goodput.ACCOUNTANT._t_final is not None
        # FLAGS_telemetry_stale_s <= 0 disables optimizer-heartbeat
        # staleness entirely (ages stay reported): the opt-out for
        # scripts with legitimate >window non-stepping phases (long
        # eval/checkpoint/export) that cannot call
        # goodput.ACCOUNTANT.finalize() around them
        stale = stale_s > 0 and (not finalized) \
            and train["age_s"] > stale_s
        train.update({"stale": stale, "finalized": finalized,
                      "window_s": stale_s})
        if stale:
            healthy = False
    engines = []
    eng_window = _engine_window_s()
    for eng in list(_ENGINES):
        try:
            sched = eng.scheduler
            busy = bool(sched.running or sched.waiting)
            hb_ns = getattr(eng, "_hb_ns", None)
            age = (time.perf_counter_ns() - hb_ns) / 1e9 \
                if hb_ns else None
            # an idle engine is never "dead"; a busy one whose last
            # step activity is older than the watchdog window is — that
            # is exactly the blind hang this endpoint exists for.
            # While an XLA compile is legitimately in flight (first
            # decode build, a NEW prefill length bucket, a watchdog
            # rebuild — the engine stamps _compile_grace_ns at each),
            # widen the window to the generic staleness bound so a
            # supervisor does not kill a replica mid-compile; a wedge
            # inside compile still flips after FLAGS_telemetry_stale_s
            grace_ns = getattr(eng, "_compile_grace_ns", None)
            in_grace = eng._decode_fn is None or (
                grace_ns is not None
                and (time.perf_counter_ns() - grace_ns) / 1e9 < stale_s)
            window = max(eng_window, stale_s) if in_grace else eng_window
            stale = bool(window > 0 and busy and age is not None
                         and age > window)
            if age is not None:
                ages.append(age)
            if stale:
                healthy = False
            st = eng._stats
            engines.append({"busy": busy,
                            "age_s": round(age, 4) if age is not None
                            else None,
                            "window_s": round(window, 4),
                            "stale": stale,
                            "degraded": bool(eng.degraded),
                            "steps": st.steps, "hangs": st.hangs,
                            "running": len(sched.running),
                            "waiting": len(sched.waiting)})
        except Exception:
            continue            # a dying engine must never sink a probe
    return {"healthy": healthy,
            "last_heartbeat_age_s": round(min(ages), 4) if ages else None,
            "window_s": stale_s,
            "sources": sources,
            "engines": engines}


def ready_report():
    """Readiness view: every engine out of the degraded latch with its
    decode program compiled (or never asked to serve yet), plus the AOT
    warm-start state a restarted replica cold-starts from."""
    ready = True
    engines = []
    for eng in list(_ENGINES):
        try:
            st = eng._stats
            decode_compiled = eng._decode_fn is not None
            e_ready = (not eng.degraded) \
                and (decode_compiled or st.steps == 0)
            if not e_ready:
                ready = False
            engines.append({"ready": e_ready,
                            "degraded": bool(eng.degraded),
                            "decode_compiled": decode_compiled,
                            "decode_compiles": st.decode_compiles,
                            "steps": st.steps,
                            "attention_kernel": eng._attn_kernel})
        except Exception:
            continue
    from .aot import aot_cache_stats
    from . import sentinel as _sentinel
    aot = aot_cache_stats()
    # the regression sentinel's drift latch is a readiness input like an
    # engine's degraded latch: a confirmed perf regression takes the
    # replica out of rotation WITH the machine-readable finding attached
    snt = _sentinel.sentinel_ready()
    if snt["degraded"]:
        ready = False
    return {"ready": ready, "engines": engines,
            "sentinel": snt,
            "aot": {"enabled": bool(_FLAGS.get("FLAGS_aot_cache")),
                    "hits": aot.get("hits", 0),
                    "misses": aot.get("misses", 0),
                    "stores": aot.get("stores", 0)}}


def doctor_report():
    """The fusion doctor's report over the live flight-recorder ring —
    the same JSON schema `fusion_doctor --json [--metrics]` prints, so
    `fusion_doctor --url` renders it unchanged."""
    from .events import EVENTS
    from .explain import explain
    report = explain(EVENTS.snapshot())
    if _FLAGS.get("FLAGS_metrics"):
        from . import goodput as _goodput
        from . import metrics as _metrics
        report["metrics"] = _metrics.metrics_snapshot()
        report["goodput"] = _goodput.ACCOUNTANT.snapshot()
    return report


def events_tail(n=_EVENTS_TAIL_DEFAULT):
    """Bounded tail of the flight-recorder ring (newest last)."""
    from .events import EVENTS
    try:
        n = int(n)
    except (TypeError, ValueError):
        n = _EVENTS_TAIL_DEFAULT
    n = max(1, min(n, _EVENTS_TAIL_CAP))
    ev = EVENTS.snapshot()
    return {"total_emitted": EVENTS.total, "in_ring": len(ev),
            "returned": min(n, len(ev)), "events": ev[-n:]}


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

def _json_body(obj, status=200):
    body = json.dumps(obj, sort_keys=True, default=str).encode()
    return body, "application/json", status


def _route(path, qs):
    """(body bytes, content-type, status) for one GET."""
    if path in ("/metrics", "/metrics/"):
        from . import metrics as _metrics
        return (_metrics.REGISTRY.exposition().encode(),
                "text/plain; version=0.0.4; charset=utf-8", 200)
    if path == "/metrics.json":
        from . import metrics as _metrics
        return _json_body(_metrics.metrics_snapshot())
    if path == "/goodput":
        from . import goodput as _goodput
        return _json_body(_goodput.ACCOUNTANT.snapshot())
    if path == "/doctor":
        return _json_body(doctor_report())
    if path == "/events":
        n = (qs.get("n") or [_EVENTS_TAIL_DEFAULT])[0]
        return _json_body(events_tail(n))
    if path == "/healthz":
        rep = health_report()
        return _json_body(rep, 200 if rep["healthy"] else 503)
    if path == "/readyz":
        rep = ready_report()
        return _json_body(rep, 200 if rep["ready"] else 503)
    if path == "/sentinel":
        from . import sentinel as _sentinel
        return _json_body(_sentinel.sentinel_report())
    if path == "/fleet":
        # this host's elastic-fabric view: membership generation, lease
        # ages, and (on the coordinator host) the whole fleet including
        # stale_hosts — what tools/fleet_metrics.py scrapes to classify
        # stale_member hosts
        from ..distributed import fabric as _fabric
        return _json_body(_fabric.fleet_report())
    if path == "/":
        return _json_body({"endpoints": [
            "/metrics", "/metrics.json", "/goodput", "/doctor",
            "/events", "/healthz", "/readyz", "/sentinel", "/fleet"]})
    return _json_body({"error": f"unknown endpoint {path!r}"}, 404)


class _Handler(BaseHTTPRequestHandler):
    server_version = "paddle-tpu-telemetry/1"
    # keep-alive for the 100 Hz scraper; Content-Length is always set
    protocol_version = "HTTP/1.1"

    def do_GET(self):                                   # noqa: N802
        try:
            url = urlparse(self.path)
            body, ctype, status = _route(url.path, parse_qs(url.query))
        except Exception as e:   # a probe must answer, never hang/500-loop
            body, ctype, status = _json_body({"error": repr(e)[:400]}, 500)
        try:
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass                 # scraper went away mid-write; fine

    def log_message(self, *args):
        pass                     # scrapes must not spam the trainer's log


class _Server(ThreadingHTTPServer):
    # class attributes, consulted during __init__'s server_bind(): a
    # kill-9 mid-scrape leaves accepted sockets in TIME_WAIT, and the
    # restarted process must rebind the advertised port immediately.
    # (HTTPServer already defaults allow_reuse_address on; pinned here
    # because the restart contract depends on it, not on a default.)
    allow_reuse_address = True
    daemon_threads = True


class TelemetryServer:
    """One stdlib HTTP server on a daemon thread. `port=0` binds an
    ephemeral port (tests); the bound port is `self.port`."""

    def __init__(self, port=0, host="127.0.0.1"):
        self._httpd = _Server((host, int(port)), _Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.2},
            daemon=True, name=f"telemetry-server:{self.port}")

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=5)


def start(port=None, host=None):
    """Start the process's telemetry server (idempotent: an already
    running server is returned unchanged). `port=None` reads
    FLAGS_telemetry_port; `port=0` binds an ephemeral port; `host=None`
    reads FLAGS_telemetry_host (default loopback — bind 0.0.0.0 for a
    cross-host Prometheus scrape). Bind failures raise — use
    `maybe_start_from_flags` for the never-crash implicit path."""
    global _SERVER, _ARMED
    if _SERVER is not None:
        return _SERVER
    if port is None:
        try:
            port = int(_FLAGS.get("FLAGS_telemetry_port", 0) or 0)
        except (TypeError, ValueError):
            port = 0
    if host is None:
        host = str(_FLAGS.get("FLAGS_telemetry_host") or "127.0.0.1")
    _HEART.clear()               # fresh liveness window per server life
    srv = TelemetryServer(port, host).start()
    _SERVER = srv
    _ARMED = True
    return srv


def stop():
    """Stop the server and disarm the heartbeat sites (engines stay
    registered — a later start() sees them again)."""
    global _SERVER, _ARMED
    _ARMED = False
    srv, _SERVER = _SERVER, None
    if srv is not None:
        srv.stop()


def maybe_start_from_flags():
    """Start the server iff FLAGS_telemetry_port is nonzero (the
    import-time / engine-build hook). One dict lookup when off. A bind
    failure WARNS and returns None instead of raising: the diagnostics
    plane must never kill the process it monitors — concretely, a
    restart racing the old process's socket, or a DataLoader worker
    that inherited the env flag and re-imports the framework while the
    parent holds the port, degrades to no-server, not a crash."""
    if _SERVER is not None:
        return _SERVER
    try:
        port = int(_FLAGS.get("FLAGS_telemetry_port", 0) or 0)
    except (TypeError, ValueError):
        port = 0
    if port <= 0:
        return None
    try:
        return start(port)
    except OSError as e:
        import warnings
        warnings.warn(
            f"telemetry server could not bind port {port} ({e}); "
            "continuing WITHOUT the observability endpoint")
        return None


def server():
    return _SERVER


def server_port():
    return _SERVER.port if _SERVER is not None else None


def server_url():
    return _SERVER.url if _SERVER is not None else None
