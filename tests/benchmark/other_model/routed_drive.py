"""Read, on the chip, what a serve check can tell apart in a model that
routes: the routed fixture (`routed_reference.py`) at published widths,
held to itself in the two arithmetics below float32 that
`benchmark.reference.common.rounder` knows: bfloat16 (a sound program's
stand-in) and fp8 (the control). The gaps are read by the harness's own
`correct.served_gaps(..., control=arithmetic)` over ROWS seeded rows of
SEQ random ids, each row a stream of one prompt token and SEQ - 1 served
ones: `control_logit_gap`, `_mean` and `_p99` say how far below the
float32 reference's best lies the token that the arithmetic puts first.
Beside them, the share of those positions at which some layer chose
other experts than float32 did (`routed_reference.forward_and_choices`).
The rows' "served" tokens are random ids, so `logit_gap`, `_mean` and
`_p99` read what an ALTERED token reads.

    python3 tests/benchmark/other_model/routed_drive.py --seeds 1,2,3 \\
        --out chiprun_out/pr28_routed_v5e.jsonl

Not a benchmark cell: no program is served, nothing is timed."""
import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(os.path.dirname(HERE)))]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import routed_reference as ref  # noqa: E402
from benchmark import correct, seeded  # noqa: E402

# widths, experts, experts a token, scaling and vocabulary of
# https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json
# (catalog of the `model-configs` guide); depth cut to the leading dense
# layer and six expert layers; the attention is the fixture's own (16
# heads of 128), not the source's latent one
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 10240,
    "moe_intermediate_size": 1536, "n_routed_experts": 64,
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True,
    "first_k_dense_replace": 1, "num_hidden_layers": 7,
    "num_attention_heads": 16, "head_dim": 128, "rms_norm_eps": 1e-5,
    "vocab_size": 154880, "initializer_range": 0.02,
    "precision": {"params": "bfloat16", "control": "fp8"},
    "reference": "routed_reference",
}
ROWS, SEQ = 4, 2048           # 4 x 2,047 = 8,188 positions a seed
ARITHMETICS = ("bfloat16", "fp8")


def flipped(cfg, seed, ids):
    """{arithmetic: [expert layers, positions] bool}: where a layer chose
    other experts than the float32 fixture did, at the positions whose
    logits `served_gaps` reads (all but each row's last)."""
    weights = correct.weight_maker(cfg, seed)()

    @functools.partial(jax.jit, static_argnames=("precision",))
    def choices(w, row, precision):
        return ref.forward_and_choices(w, row[None], cfg, precision)[1][:, 0]

    rows = [jnp.asarray(row) for row in ids]
    want = [choices(weights, row, "float32") for row in rows]
    return {p: np.concatenate(
        [np.asarray(jnp.any(choices(weights, row, p) != w, -1))[:, :-1]
         for row, w in zip(rows, want)], -1) for p in ARITHMETICS}


def read_seed(cfg, seed, rows, seq):
    ids = seeded.host_rng(seed, 7).integers(
        0, cfg["vocab_size"], (rows, seq)).astype(np.int32)
    flips = flipped(cfg, seed, ids)      # its weights are freed on return
    streams = [(row[:1].tolist(), row[1:].tolist()) for row in ids]
    out = []
    for arithmetic in ARITHMETICS:
        t0 = time.perf_counter()
        numbers = correct.served_gaps(cfg, seed, streams, seq,
                                      control=arithmetic)
        out.append({
            "seed": seed, "arithmetic": arithmetic, "rows": rows, "seq": seq,
            **numbers,
            "flipped_share": float(np.mean(np.any(flips[arithmetic], 0))),
            "flipped_share_by_layer":
                np.mean(flips[arithmetic], -1).tolist(),
            "seconds": time.perf_counter() - t0})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if args.out and device.platform != "tpu":
        raise SystemExit(f"rows kept under --out are chip readings; JAX "
                         f"found {device.platform!r}")
    print(json.dumps({"platform": device.platform, "kind": device.device_kind,
                      "parameters": ref.num_params(PUBLISHED),
                      "config": PUBLISHED}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in read_seed(PUBLISHED, seed, ROWS, SEQ):
            row = {**row, "device": device.device_kind}
            print(json.dumps(row), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
