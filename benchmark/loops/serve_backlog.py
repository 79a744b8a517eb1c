"""The `serve_backlog` loop: a queue that outlasts the window, so every
decode step runs a full batch. `serve_tokens_per_s` is the output tokens
committed inside the window over the window; every slot has been filled
once, and the first requests have finished, before it opens."""
from __future__ import annotations

import json

from ..harness import clock
from . import serving


def top_up(engine, client, filler, depth):
    while len(engine.scheduler.waiting) < depth:
        prompt, want = next(filler)
        client.send(prompt, want)


def serve_for(engine, client, filler, depth, seconds, after_step=None):
    t0 = clock()
    while clock() - t0 < seconds:
        top_up(engine, client, filler, depth)
        client.step()
        if after_step:
            after_step()
    return t0, clock()


def serve(run):
    """Everything that needs the engine: set-up, warm traffic, the window
    and the traced slice. What it returns is plain values, so that when it
    has returned nothing holds the engine, its pools or its programs."""
    mix = run.traffic
    program, engine, client, filler = serving.set_up(run)
    depth = mix["queue_depth"]
    # warm traffic: until the batch is full and the first wave has left
    while True:
        top_up(engine, client, filler, depth)
        client.step()
        s = engine.stats()
        if s["admitted"] >= engine.max_batch_size \
                and s["completed"] >= mix["warm_completions"]:
            break
    engine.reset_stats()
    first = len(client.log)
    held = []      # pool blocks that requests hold, after every step
    t_open, t_close = serve_for(
        engine, client, filler, depth, run.seconds,
        lambda: held.append(program.pool_blocks_held(engine)))
    run.evidence["pool_blocks_held"] = held
    serving.nothing_compiled(engine)
    serving.window_evidence(run, program, engine, t_open, t_close)
    if run.traced:
        with run.traced_slice():
            serve_for(engine, client, filler, depth, mix["trace_seconds"])
    tokens = sum(t_open <= t <= t_close for r in client.log
                 for t in r["token_s"])
    ended = [r for r in client.log
             if serving.Client.done(r) and r["token_s"]
             and t_open <= r["token_s"][-1] <= t_close]
    ended += [r for r in client.log[first:] if r["refused"]]
    failed = [r for r in ended if not serving.Client.served(r)]
    print(json.dumps({"window_s": t_close - t_open, "window_tokens": tokens,
                      "finished_in_window": len(ended),
                      "pool_blocks_held_mean": sum(held) / len(held),
                      "engine_step_s": run.evidence["engine_step_s"],
                      "longest_engine_steps": [
                          {"start_s": a, "seconds": d} for a, d in sorted(
                              run.evidence["engine_steps"],
                              key=lambda step: -step[1])[:5]],
                      "engine": {k: run.evidence["engine_stats"][k] for k in
                                 ("steps", "prefills", "occupancy_mean",
                                  "p50_step_ms", "evictions", "failed")}}),
          flush=True)
    return {"t_open": t_open, "window_s": t_close - t_open,
            "tokens": tokens, "attempted": len(ended), "failed": len(failed),
            "streams": serving.sample_streams(run, ended)}


def run(run):
    served = serve(run)
    # the peak is the program's own: read before the reference exists, and
    # the reference's weights are made when the engine's are gone
    run.read_memory_peak()
    serving.released(run)
    serving.check_served(run, served["streams"])
    end_to_end = {"serve_tokens_per_s": served["tokens"] / served["window_s"],
                  "setup_s": run.setup_seconds(served["t_open"])}
    return run.result(end_to_end, attempted=served["attempted"],
                      failed=served["failed"])
