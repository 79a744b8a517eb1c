"""`python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`: one cell, once; the contract's JSON object is the last
line of standard output. Without the TPU chips the cell asks for it exits
non-zero and prints no result."""
from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up counts from here

import argparse                    # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cell(root, workload, seed, seconds, traced, require_chip=True,
             t_start=None):
    """Drive one cell; returns the result line as a dict."""
    from . import harness
    run = harness.Run(root, workload, seed, seconds, traced,
                      require_chip=require_chip, t_start=t_start)
    loop = importlib.import_module(
        f"{__package__}.loops.{run.traffic['loop']}")
    return loop.run(run)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
