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


def run(run):
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
                      "engine": {k: run.evidence["engine_stats"][k] for k in
                                 ("steps", "prefills", "occupancy_mean",
                                  "p50_step_ms", "evictions", "failed")}}),
          flush=True)
    serving.check_served(run, ended)
    end_to_end = {"serve_tokens_per_s": tokens / (t_close - t_open),
                  "setup_s": run.setup_seconds(t_open)}
    return run.result(end_to_end, attempted=len(ended), failed=len(failed))
