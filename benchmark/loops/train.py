"""The `train` loop: one compiled training step, driven from the seed
through its first steps (which the reference follows), then timed.

The end-to-end rate (named by the traffic file's `rate_metric`) is all the
window's tokens over all its time, from the first dispatch to the moment
the last step's result is ready: a stall anywhere in the window moves it.
The device queue is never drained inside the window: the loop waits on the
result of the step two behind the one it dispatches. For the per-layer
view the window's steps are also cut into the traffic file's `readings`
readings of an equal number of steps."""
from __future__ import annotations

import collections
import importlib
import json
import math

from .. import correct, seeded, stats
from ..harness import clock

QUEUE_DEPTH = 2
CHECKED_STEPS = 3


def drive(trainer, ring, seconds, spans):
    """Dispatch steps for `seconds`, then wait for the last. Returns
    {"steps", "window_s" (first dispatch to last result ready), "ready_s"
    (when each step's result was seen ready while later steps were being
    dispatched; the drain's are left out, they are read differently)} and
    the last loss."""
    pending, ready = collections.deque(), []
    t0, i, loss = clock(), 0, None
    while clock() - t0 < seconds:
        with spans("bench.step"):
            loss = trainer.step(*ring[i % len(ring)])
        pending.append(loss)
        i += 1
        if len(pending) > QUEUE_DEPTH:
            pending.popleft().block_until_ready()
            ready.append(clock())
    while pending:
        pending.popleft().block_until_ready()
    return {"steps": i, "window_s": clock() - t0, "ready_s": ready}, loss


def placement(arrays, devices):
    held = {d.id: 0 for d in devices}
    for a in arrays:
        for shard in a.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[d.id] for d in devices]


def checked_steps(run, trainer, ring):
    """The first steps through the window's own call and feed; returns the
    program's readings in the reference's terms."""
    beta1 = run.config["optimizer"]["beta1"]
    losses, grad_norms = [], None
    for ids, labels in ring[:CHECKED_STEPS]:
        losses.append(float(trainer.step(ids, labels)))
        if grad_norms is None:
            # Adam's first moment after one step is (1 - beta1) * gradient
            grad_norms = {k: v / (1 - beta1) for k, v in
                          correct.leaf_norms(trainer.first_moment()).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": correct.delta_norms(trainer.master_params(),
                                               trainer.initial_params())}


def run(run):
    cfg, traffic = run.config, run.traffic
    program = importlib.import_module(cfg["program"])
    program.enable_compile_cache()
    devices = run.claim_devices()
    phases = {"imports_and_device": clock() - run.t_start}
    rows, seq = traffic["batch_rows"], traffic["seq"]
    tokens_per_step = rows * seq

    # the reference first, before the program's state exists: its memory
    # is gone before the program's peak is read, and its time is not set-up
    with run.reference_time():
        want = correct.reference_train(cfg, traffic, run.seed, devices,
                                       CHECKED_STEPS)

    mark = clock()
    trainer = program.build_trainer(cfg, traffic,
                                    correct.weight_maker(cfg, run.seed),
                                    devices)
    phases["model_and_weights"] = clock() - mark
    try:
        ring = seeded.make_batches(
            max(CHECKED_STEPS, traffic["distinct_batches"]), rows, seq,
            cfg["vocab_size"], run.seed, trainer.batch_sharding())
        mark = clock()
        program_bytes = trainer.program_bytes(*ring[0])
        phases["step_compiled_or_read"] = clock() - mark
        state = trainer.state_arrays()
        held = placement(state, devices)
        mark = clock()
        got = checked_steps(run, trainer, ring)
        phases["three_checked_steps"] = clock() - mark
        numbers = correct.train_numbers(got, want)
        print(json.dumps({"setup_phases_s": phases,
                          "reference_s": run.reference_s}), flush=True)
        print(json.dumps({"reference": want["losses"],
                          "program": got["losses"],
                          "worst_leaves": numbers.pop("leaves"),
                          "state_bytes_per_device": held}), flush=True)
        for name, value in numbers.items():
            run.check(name, value)
        if len(devices) > 1:
            # the state is spread over the mesh before the first step:
            # no device holds (nearly) all of it
            run.check("state_max_share",
                      max(held) / sum(a.nbytes for a in state))

        live = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in devices)
        t_open = clock()
        window, loss = drive(trainer, ring, run.seconds, run.spans)
        rate = stats.window_rate(window["steps"], tokens_per_step,
                                 window["window_s"])
        readings = stats.train_readings(window["ready_s"], tokens_per_step,
                                        traffic["readings"])
        print(json.dumps({"window_steps": window["steps"],
                          "window_s": window["window_s"],
                          "window_tokens_per_s": rate,
                          "readings_tokens_per_s": readings["tokens_per_s"],
                          "steps_per_reading": readings["steps_per_reading"]}),
              flush=True)
        if not math.isfinite(float(loss)):
            raise FloatingPointError(f"loss {float(loss)} after the window")
        run.evidence.update({
            "readings": readings, "window_tokens_per_s": rate,
            "program_bytes": program_bytes, "live_bytes": live,
            "mesh": traffic.get("mesh"), "chips": len(devices),
            "params": correct.ref.num_params(cfg)})
        if run.traced:
            with run.traced_slice():
                traced, _ = drive(trainer, ring, traffic["trace_seconds"],
                                  run.spans)
            run.evidence["traced_steps"] = traced["steps"]
        end_to_end = {traffic["rate_metric"]: rate,
                      "setup_s": run.setup_seconds(t_open)}
        # the compiler's temporaries ride on top of the live arrays while
        # the step runs; the backend's own peak does not count them
        return run.result(end_to_end, attempted=window["steps"], failed=0,
                          program_peak=live + program_bytes["temp"])
    finally:
        trainer.close()
