"""Make the runs a bound is set from: for one cell, sets of runs of
`python3 -m benchmark.run`, every set on the same seeds, each run a fresh
process, one after another. Keeps every line the runs print in `--out`
(JSON lines) and prints, for each end-to-end metric, each set's median and
spread: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median.

    python3 -m benchmark.proof --workload <name> --seeds 1,2,3,4,5,6 \\
        --sets 2 --seconds 40 --out chiprun_out/proof_<name>.jsonl

Not part of a benchmark run; this process never touches JAX, so each run
has the chip to itself."""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def one_run(workload, seed, seconds, traced):
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(int(traced))], cwd=ROOT, capture_output=True, text=True)
    lines = []
    for text in p.stdout.splitlines():
        try:
            lines.append(json.loads(text))
        except ValueError:
            pass
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "trace": int(traced), "rc": p.returncode,
            "wall_s": time.perf_counter() - t0, "lines": lines,
            "stderr_tail": p.stderr[-1500:] if p.returncode else ""}


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traced-seed", type=int)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    plan = [(k, s, False) for k in range(args.sets) for s in seeds]
    if args.traced_seed is not None:
        plan.append((args.sets, args.traced_seed, True))
    sets = {}
    for k, seed, traced in plan:
        rec = dict(one_run(args.workload, seed, args.seconds, traced), set=k)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        last = rec["lines"][-1] if rec["lines"] else {}
        print(json.dumps({"set": k, "seed": seed, "trace": int(traced),
                          "rc": rec["rc"], "wall_s": round(rec["wall_s"], 1),
                          "correct": last.get("correct"),
                          "metrics": {n: m["value"] for n, m in
                                      last.get("metrics", {}).items()},
                          "stderr": rec["stderr_tail"][-400:]}), flush=True)
        if not traced and rec["rc"] == 0:
            for name, m in last["metrics"].items():
                sets.setdefault(name, {}).setdefault(k, []).append(
                    m["value"])
    for name, by_set in sets.items():
        for k, values in sorted(by_set.items()):
            if len(values) >= 2:
                print(json.dumps({"metric": name, "set": k, "runs": len(values),
                                  "median": statistics.median(values),
                                  "spread": spread(values),
                                  "values": values}), flush=True)


if __name__ == "__main__":
    main()
