"""`benchmark.seedcheck` for a serving cell whose weights fill the chip:
the reference's weights do not fit beside the engine's, so every seed's
traffic is served FIRST (one engine, weights from the first seed, as
`seedcheck.serve` keeps it), the sampled streams are kept as plain lists,
the engine is dropped as the `serve_backlog` loop drops it, and only then
is each seed's sample held to the reference and, on a control seed, the
control to the cell's limits, by the harness's own `serving.check_served`
and `Run.check`. Rows as `seedcheck` writes them.

    python3 -m benchmark.seedcheck_released --workload <name> \\
        --seeds 1,2,... --control-seeds 1,2 [--seconds 25] [--out x.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os

from . import harness, seedcheck, traffic as gen
from .loops import serve_backlog, serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def serve_all(run, seeds, seconds):
    """{seed: (finished, sampled streams)}; nothing of the engine is
    alive when this returns."""
    mix, cfg = run.traffic, run.config
    _, engine, client, _ = serving.set_up(run)
    out = {}
    for seed in seeds:
        filler = gen.filler_requests(mix, seed, cfg["vocab_size"], 5)
        first = len(client.log)
        serve_backlog.serve_for(engine, client, filler, mix["queue_depth"],
                                seconds)
        ended = [r for r in client.log[first:] if serving.Client.done(r)]
        out[seed] = (len(ended), serving.sample_streams(run, ended))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    run = harness.Run(ROOT, args.workload, seeds[0], args.seconds, False)
    served = serve_all(run, seeds, args.seconds)
    run.read_memory_peak()
    serving.released(run)
    precision = run.config["precision"]["control"]
    for seed in seeds:
        finished, streams = served[seed]
        controlled = seed in control
        mark = len(run.checks)
        numbers = serving.check_served(
            run, streams, precision if controlled else None) or {}
        row = {"seed": seed, "finished": finished, **numbers,
               "correct": seedcheck.verdict(run.checks[mark:])}
        if controlled and numbers:
            at = len(run.checks)
            for name in serving.COMPARED:
                run.check("control_" + name, numbers["control_" + name],
                          limit_key=name)
            row["control_correct"] = seedcheck.verdict(run.checks[at:])
        row["checks"] = {
            name: {"value": value if value == value else None,
                   "limit": limit, "ok": ok}
            for name, value, limit, ok in run.checks[mark:]}
        row["memory_peak_bytes"] = run.memory_peak
        print(json.dumps({"seedcheck": row}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps({"workload": args.workload, **row})
                        + "\n")


if __name__ == "__main__":
    main()
