"""Warm-start child of tests/test_aot_cache.py: a tiny fwd+bwd+SGD loop in
a FRESH process with the AOT executable store armed. Writes the compile
and store counters the parent asserts on — run once against an empty
store (cold), then against the store that run left (warm).

    python tests/fixtures/aot_child.py <store dir> <report.json>
"""
import json
import sys

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.profiler import (aot_cache_stats, chain_fusion_stats,
                                 dispatch_cache_stats, step_fusion_stats)


def main(aot_dir, out_path, steps=12):
    set_flags({"FLAGS_aot_cache": True,
               "FLAGS_aot_cache_dir": aot_dir,
               "FLAGS_eager_chain_fusion_min_count": 3,
               "FLAGS_eager_step_fusion_min_count": 5})
    paddle.seed(0)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((16, 32)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((32, 32)).astype(np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(32).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w, b])
    opt.clear_grad()        # steady-state cycle signature from cycle 1
    for _ in range(steps):
        loss = F.gelu(paddle.add(paddle.matmul(x, w), b)).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    with open(out_path, "w") as f:
        json.dump({
            "dispatch_retraces": dispatch_cache_stats()["retraces"],
            "chain_retraces": chain_fusion_stats()["retraces"],
            "step_retraces": step_fusion_stats()["retraces"],
            "steps_promoted": step_fusion_stats()["steps_promoted"],
            "fused_steps": step_fusion_stats()["fused_steps"],
            "aot": aot_cache_stats(),
        }, f)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
