"""Serving resilience primitives: structured refusal, the hung-step
watchdog, and crash-resume snapshots.

PR 6 built the serving happy path (continuous batching, paged KV, ONE
compiled decode step); this module is the failure-handling layer that
makes it a "millions of users" component:

  * `ServeRefusal` — the structured admission refusal. Subclasses
    ValueError (the PR 6 refusal type) so existing callers keep working,
    but carries a machine-readable `reason` from the flight-recorder
    contract (`queue_full` / `deadline_infeasible` / `kv_exhausted`)
    plus a `detail` dict mirroring the emitted `serve.refuse` event.
    Refusing early is the whole point of backpressure: work that would
    expire unserved is bounced at the door, not queued to rot.

  * `MonitoredWait` — bounded completion for a decode/prefill fire. The
    engine dispatches the step (async), then waits for the result
    arrays through `wait()`: an `is_ready()` poll that YIELDS
    (`time.sleep(0)`) between checks against the
    `FLAGS_serve_step_timeout_ms` deadline, escalating to millisecond
    sleeps once a step is clearly slow. The yield is the load-bearing
    part: a hard spin competes with XLA's own compute threads and taxes
    the very step it watches (measured ~30%/step on a 2-core box),
    while yield-polling costs about what the plain blocking read it
    replaces does. No waiter threads: a cross-thread handoff costs 2+
    context
    switches per step (~10x the guard budget) and a wedged waiter could
    not be cancelled anyway. Chaos hang faults
    (`guardian.inject_fault("hang", op="serve.decode")`) short-circuit
    the wait so the recovery ladder is testable without wedging a real
    device.

  * snapshot helpers — `request_payload` / `payload_request` serialize a
    Request's RESUMABLE identity (prompt, emitted tokens, arrival order,
    remaining TTL — not the KV pool: resume re-prefills through the
    PR 6 token-identical machinery). The engine composes these into one
    JSON-able engine snapshot saved on the StepCheckpointer's
    atomic/CRC machinery (incubate.checkpoint.ServeCheckpointer), so a
    kill-9'd server restarts and finishes every in-flight stream
    byte-identically (tools/chaos.py `serve_kill` proves it).
"""
from __future__ import annotations

import time

from ..framework.flags import _FLAGS
from .scheduler import Request

__all__ = ["ServeRefusal", "MonitoredWait", "StepHang", "watchdog_budget_s",
           "request_payload", "payload_request"]


class ServeRefusal(ValueError):
    """Admission refused with a machine-readable reason.

    `reason` is a flight-recorder reason code (`queue_full` /
    `deadline_infeasible` / `kv_exhausted`); `detail` mirrors the
    `serve.refuse` event payload. ValueError subclass: PR 6 callers that
    caught ValueError on admission keep working unchanged.
    """

    def __init__(self, reason, message, detail=None):
        super().__init__(message)
        self.reason = reason
        self.detail = dict(detail or {})


class StepHang(RuntimeError):
    """A monitored decode/prefill step blew the watchdog budget."""

    def __init__(self, phase, budget_ms, attempt):
        super().__init__(
            f"serving {phase} step exceeded the "
            f"FLAGS_serve_step_timeout_ms budget ({budget_ms} ms, "
            f"attempt {attempt})")
        self.phase = phase
        self.budget_ms = budget_ms
        self.attempt = attempt


def watchdog_budget_s():
    """The armed watchdog budget in seconds, or None when disarmed."""
    try:
        ms = float(_FLAGS.get("FLAGS_serve_step_timeout_ms", 0) or 0)
    except (TypeError, ValueError):
        ms = 0.0
    return ms / 1e3 if ms > 0 else None


# a step still pending after this long is no longer latency-critical:
# switch from yield-polling to millisecond sleeps so a slow-but-alive
# device (or a genuine hang burning its budget) costs ~no host CPU
_ESCALATE_S = 0.005
_COARSE_SLEEP_S = 0.001


class MonitoredWait:
    """Bounded wait on a step's result arrays.

    `wait(arrays, phase, attempt, programs)` returns normally once the
    arrays are ready (or immediately when the watchdog is disarmed — the
    caller then blocks on the host transfer exactly as before PR 7); raises
    `StepHang` when the budget elapses first (`programs` budgets where
    the wait covers that many programs on the device's queue: a prefill
    dispatched behind a pipelined decode launch). An armed chaos "hang"
    injector for `op=f"serve.{phase}"` trips the hang path
    deterministically without consuming the budget in real time — each
    ladder rung re-polls, so `times=N` hangs exactly N attempts. A
    "stall" injector is the wall-clock variant: it sleeps the REAL
    budget before the StepHang, so the telemetry server's /healthz can
    observe the wedge (tools/chaos.py `telemetry` scenario).
    """

    def __init__(self, budget_s=None):
        self._budget_s = budget_s

    @property
    def armed(self):
        return (self._budget_s if self._budget_s is not None
                else watchdog_budget_s()) is not None

    def wait(self, arrays, phase, attempt=1, programs=1):
        from ..ops import guardian
        budget = (self._budget_s if self._budget_s is not None
                  else watchdog_budget_s())
        if budget is not None:
            # the budget is one step's: a wait that also covers programs
            # queued ahead of its own is allowed one budget for each
            budget *= programs
        if guardian.faults_armed():
            kind = guardian.poll_fault(f"serve.{phase}",
                                       ("hang", "stall"))
            if kind == "stall":
                # the wall-clock hang variant: burn the REAL budget
                # before the StepHang so the liveness plane (/healthz,
                # profiler/telemetry_server.py) observes a genuinely
                # wedged step — with the watchdog disarmed, model a
                # slow-but-alive step and return normally
                time.sleep(budget if budget is not None
                           else _ESCALATE_S * 10)
                if budget is None:
                    return
                raise StepHang(phase, budget * 1e3, attempt)
            if kind is not None:
                raise StepHang(phase, (budget or 0) * 1e3, attempt)
        if budget is None:
            return
        start = time.perf_counter()
        deadline = start + budget
        escalate = start + min(_ESCALATE_S, budget / 2)
        for a in arrays:
            ready = getattr(a, "is_ready", None)
            if ready is None:
                continue
            while not ready():
                now = time.perf_counter()
                if now >= deadline:
                    raise StepHang(phase, budget * 1e3, attempt)
                # yield, don't spin: XLA's compute threads need the core
                time.sleep(0 if now < escalate else _COARSE_SLEEP_S)


# ---------------------------------------------------------------------------
# crash-resume snapshots
# ---------------------------------------------------------------------------

def request_payload(req, now_ns=None):
    """A Request's resumable identity as a JSON-able dict. Captures WHAT
    was asked and what has been emitted — never device state: the KV
    pool re-prefills on resume via the engine's normal (re-)admission
    path, token-identically. Deadlines serialize as REMAINING seconds
    (the monotonic clock does not survive the process)."""
    return {
        "rid": req.rid,
        "prompt": list(req.prompt),
        "max_new_tokens": req.max_new_tokens,
        "eos_token_id": req.eos_token_id,
        "generated": list(req.generated),
        "arrival_seq": req.arrival_seq,
        "preemptions": req.preemptions,
        "ttl_remaining_s": req.ttl_remaining_s(now_ns),
        # multi-tenant identity (PR 17): which adapter the stream
        # decodes under — restore refuses (adapter_mismatch) when the
        # restoring engine does not have it registered
        "adapter": req.adapter,
        # sampler identity (PR 18): the resolved sampler config,
        # including the resolved seed — (seed, prompt, sampler) is the
        # reproducibility contract, so the restored stream continues
        # byte-identically from the same fold_in positions
        "temperature": req.temperature,
        "top_k": req.top_k,
        "top_p": req.top_p,
        "repetition_penalty": req.repetition_penalty,
        "seed": req.seed,
    }


def payload_request(payload, on_token=None):
    """Rebuild a QUEUED Request from `request_payload` output. The
    emitted-so-far tokens ride in `generated`, so the first admission
    re-prefills prompt + generated and continues the stream exactly
    where the dead process stopped. `on_token` callbacks do not
    serialize — the restoring caller re-attaches its own."""
    ttl = payload.get("ttl_remaining_s")
    req = Request(payload["rid"], payload["prompt"],
                  payload["max_new_tokens"],
                  eos_token_id=payload.get("eos_token_id"),
                  on_token=on_token,
                  ttl_s=max(0.0, ttl) if ttl is not None else None,
                  adapter=payload.get("adapter"),
                  temperature=payload.get("temperature", 0.0),
                  top_k=payload.get("top_k", 0),
                  top_p=payload.get("top_p", 1.0),
                  repetition_penalty=payload.get(
                      "repetition_penalty", 1.0),
                  seed=payload.get("seed"))
    req.generated = list(payload.get("generated") or [])
    # logprobs for pre-crash tokens died with the process — pad with
    # None so the panels stay index-aligned with `generated`
    req.token_logprobs = [None] * len(req.generated)
    req.alt_ids = [None] * len(req.generated)
    req.alt_logprobs = [None] * len(req.generated)
    req.preemptions = int(payload.get("preemptions") or 0)
    return req
