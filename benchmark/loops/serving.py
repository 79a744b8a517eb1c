"""What serving loops share: the engine with every program compiled, a
client that times requests from its own side, and the comparison of served
tokens with the reference."""
from __future__ import annotations

import gc
import importlib
import json
import time

from .. import correct, seeded, traffic as gen
from ..harness import clock


class Client:
    """Sends requests into the engine and keeps the request log. Times are
    the client's: a token's time is when its callback ran."""

    def __init__(self, engine, spans):
        self.engine, self.spans = engine, spans
        self.log = []

    def send(self, prompt, want, due=None, measured=False):
        now = clock()
        rec = {"due": now if due is None else due, "sent": now,
               "first": None, "last": None, "tokens": 0, "want": want,
               "token_s": [], "measured": measured, "handle": None,
               "refused": False}

        def on_token(req, tok, text):
            t = clock()
            rec["token_s"].append(t)
            rec["tokens"] += 1
            rec["last"] = t
            if rec["first"] is None:
                rec["first"] = t

        try:
            rec["handle"] = self.engine.add_request(
                prompt, max_new_tokens=want, on_token=on_token)
        except ValueError as refusal:
            rec["refused"] = str(refusal)
        self.log.append(rec)
        return rec

    def step(self):
        with self.spans("bench.engine_step"):
            return self.engine.step()

    @staticmethod
    def done(rec):
        return rec["refused"] or rec["handle"].finished

    @staticmethod
    def served(rec):
        return (not rec["refused"]) and rec["tokens"] >= rec["want"]


def set_up(run):
    """Engine built from the seed's weights, one decode program and one
    prefill program per bucket compiled. Returns (program, engine, client,
    filler requests)."""
    cfg, mix = run.config, run.traffic
    program = importlib.import_module(cfg["program"])
    program.enable_compile_cache()
    run.claim_devices()
    t_claimed = clock()
    engine = program.build_engine(cfg, mix,
                                  correct.weight_maker(cfg, run.seed))
    t_built = clock()
    client = Client(engine, run.spans)
    rng = seeded.host_rng(run.seed, 4)
    # every shape the window will use: one prompt per prefill bucket
    for bucket in mix["prefill_buckets"]:
        client.send(seeded.token_ids(rng, bucket, cfg["vocab_size"]),
                    mix["compile_tokens"])
        while client.step():
            pass
    compiled = engine.stats()
    if (compiled["decode_compiles"], compiled["prefill_compiles"]) != \
            (1, len(mix["prefill_buckets"])):
        raise RuntimeError(f"compiled {compiled['decode_compiles']} decode "
                           f"and {compiled['prefill_compiles']} prefill "
                           f"programs, want 1 and "
                           f"{len(mix['prefill_buckets'])}")
    print(json.dumps({"setup_phases_s": {
        "imports_and_device": t_claimed - run.t_start,
        "model_and_weights": t_built - t_claimed,
        "five_programs_compiled": clock() - t_built}}), flush=True)
    run.evidence["engine_facts"] = program.engine_facts(engine)
    filler = gen.filler_requests(mix, run.seed, cfg["vocab_size"], 5)
    return program, engine, client, filler


def nothing_compiled(engine):
    """After `reset_stats()` at window open the compile counters count
    only what the window compiled: nothing may."""
    s = engine.stats()
    if s["decode_compiles"] or s["prefill_compiles"]:
        raise RuntimeError(f"compiled inside the window: {s}")


def sample_streams(run, finished):
    """A seeded sample of the requests the window finished, the longest
    among them, as plain lists [(prompt ids, served ids)]: all that the
    comparison needs, and nothing that keeps the engine alive."""
    rng = seeded.host_rng(run.seed, 6)
    pool = [r for r in finished if Client.served(r)]
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(r["handle"].prompt) + r["tokens"])
    rest = [r for r in pool if r is not longest]
    picks = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :run.traffic["checked_requests"] - 1]]
    return [(list(r["handle"].prompt), list(r["handle"].generated))
            for r in picks]


def released(run):
    """Called when the loop has dropped the engine, the client and the
    request handles: collect what cycles still hold, and report what is
    left on the devices, where the reference's weights go next."""
    import jax
    gc.collect()
    left = jax.live_arrays()
    print(json.dumps({"released": {
        "live_arrays": len(left),
        "live_array_bytes": sum(a.nbytes for a in left),
        "device_bytes_in_use": max(
            (d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in run.devices)}}), flush=True)


# what a serve cell compares, each number against its own limit: a
# cell's limits file holds both or the cell is never correct
COMPARED = ("logit_gap", "logit_gap_mean")


def check_served(run, streams, control=None):
    """Hold the sampled streams [(prompt ids, served ids)] to the
    reference: the widest gap and the mean gap over all their served
    tokens (`correct.gap_numbers`; and, for the seed check, the control's
    at the same positions)."""
    if not streams:
        for name in COMPARED:
            run.check(name, float("nan"))
        return
    with run.reference_time():
        t0 = time.perf_counter()
        numbers = correct.served_gaps(run.config, run.seed, streams,
                                      run.traffic["reference_pad_to"],
                                      control)
        numbers["reference_s"] = time.perf_counter() - t0
    print(json.dumps({"checked_requests": len(streams), **numbers}),
          flush=True)
    for name in COMPARED:
        run.check(name, numbers[name])
    return numbers


def window_evidence(run, program, engine, t_open, t_close):
    """What the readers read of a serving window."""
    stats = engine.stats()
    steps = [(a, b) for n, a, b in run.spans.records
             if n == "bench.engine_step" and t_open <= a and b <= t_close]
    run.evidence.update({
        "engine_stats": stats,
        # (start after the window opened, seconds) of every engine step,
        # on the host's clock: a window that loses time says where
        "engine_steps": [(a - t_open, b - a) for a, b in steps],
        "engine_step_s": sum(b - a for a, b in steps),
        "decode_s": program.decode_seconds(engine),
        "window": (t_open, t_close)})
