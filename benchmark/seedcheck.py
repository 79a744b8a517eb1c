"""Read, in one process with one set-up, what the limits of a cell's
`correct` are set from: over many seeds the numbers the sound program
gives, and over a few the numbers the control gives (the reference put in
the program's place, computed in the precision below the configuration's).

    python3 -m benchmark.seedcheck --workload <name> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--seconds 25] [--out chiprun_out/x.jsonl]

Not part of a benchmark run. Train cells build a fresh step for every seed
(weights and batches from it). Serving cells keep one engine, whose weights
come from the first seed, and draw each seed's traffic anew; a row holds
both numbers a serve cell compares, `logit_gap` and `logit_gap_mean` (and
the 99th percentile), and on a control seed the control's under
`control_...`, so one set of runs sets both limits. Every number compared
goes through `Run.check` against the cell's limits file as it stands, the
control's too: a row holds each under `checks` beside its limit, and the
verdicts `correct` and, on a control seed, `control_correct`, which has
to read false."""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os

from . import correct, harness, seeded, traffic as gen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def train(run, seeds, control_seeds, emit, with_program=True, steps=None):
    from .loops import train as loop
    cfg, mix = run.config, run.traffic
    program = importlib.import_module(cfg["program"])
    program.enable_compile_cache()
    devices = run.claim_devices()
    steps = steps or loop.CHECKED_STEPS
    for seed in seeds:
        want = correct.reference_train(cfg, mix, seed, devices, steps)
        row = {"seed": seed}
        if seed in control_seeds:
            low = correct.reference_train(cfg, mix, seed, devices, steps,
                                          cfg["precision"]["control"])
            row["control"] = correct.train_numbers(low, want)
        if not with_program:
            emit(row)
            continue
        trainer = program.build_trainer(
            cfg, mix, correct.weight_maker(cfg, seed), devices)
        try:
            ring = seeded.make_batches(loop.CHECKED_STEPS, mix["batch_rows"],
                                       mix["seq"], cfg["vocab_size"], seed,
                                       trainer.batch_sharding())
            got = loop.checked_steps(run, trainer, ring)
        finally:
            trainer.close()
        row["program"] = correct.train_numbers(got, want)
        row["losses"] = {"program": got["losses"],
                         "reference": want["losses"]}
        emit(row)
        del trainer, ring, got
        gc.collect()


def serve(run, seeds, control_seeds, seconds, emit):
    from .loops import serve_backlog, serving
    mix, cfg = run.traffic, run.config
    program, engine, client, _ = serving.set_up(run)
    for seed in seeds:
        filler = gen.filler_requests(mix, seed, cfg["vocab_size"], 5)
        first = len(client.log)
        serve_backlog.serve_for(engine, client, filler, mix["queue_depth"],
                                seconds)
        ended = [r for r in client.log[first:] if serving.Client.done(r)]
        controlled = seed in control_seeds
        mark = len(run.checks)
        numbers = serving.check_served(
            run, serving.sample_streams(run, ended),
            cfg["precision"]["control"] if controlled else None) or {}
        row = {"seed": seed, "finished": len(ended), **numbers,
               "correct": verdict(run.checks[mark:])}
        if controlled and numbers:
            # the control in the program's place: its own two numbers
            # against the same limits, by the same comparison
            at = len(run.checks)
            for name in serving.COMPARED:
                run.check("control_" + name, numbers["control_" + name],
                          limit_key=name)
            row["control_correct"] = verdict(run.checks[at:])
        row["checks"] = {
            name: {"value": value if value == value else None,
                   "limit": limit, "ok": ok}
            for name, value, limit, ok in run.checks[mark:]}
        emit(row)


def verdict(checks):
    """`correct` as `Run.result` decides it, of some of a run's checks."""
    return bool(checks) and all(ok for *_, ok in checks)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out")
    ap.add_argument("--control-only", action="store_true",
                    help="train cells: read the reference and the control, "
                         "not the program (its numbers come from the runs)")
    ap.add_argument("--steps", type=int,
                    help="with --control-only: follow fewer steps than the "
                         "run's three (the first gradient needs one)")
    args = ap.parse_args(argv)
    if args.steps and not args.control_only:
        ap.error("--steps needs --control-only: the program is always "
                 "checked over the run's own steps")
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    run = harness.Run(ROOT, args.workload, seeds[0], args.seconds, False)
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps({"seedcheck": row}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps({"workload": args.workload, **row})
                        + "\n")

    if run.traffic["loop"] == "train":
        train(run, seeds, control, emit, not args.control_only, args.steps)
    else:
        serve(run, seeds, control, args.seconds, emit)


if __name__ == "__main__":
    main()
