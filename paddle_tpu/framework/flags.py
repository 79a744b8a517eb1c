"""Global flags. Reference analog: paddle/fluid/platform/flags.cc (76 exported
FLAGS via PADDLE_DEFINE_EXPORTED_*) + paddle.set_flags/get_flags
(global_value_getter_setter.cc). Env vars `FLAGS_*` seed initial values.
"""
from __future__ import annotations

import os
import threading

__all__ = ["define_flag", "set_flags", "get_flags", "FLAGS"]

_lock = threading.Lock()
_FLAGS: dict[str, object] = {}
_DEFS: dict[str, tuple] = {}
# bumped on every mutation: caches derived from flag values (the AOT
# store's environment fingerprint, ops/aot_cache.py) key on it so a
# mid-run set_flags can never leave them stale
_GENERATION = 0


def define_flag(name, default, help_str=""):
    env = os.environ.get(name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _DEFS[name] = (default, help_str)
    _FLAGS[name] = value
    return value


# Core flags mirroring the reference set (platform/flags.cc)
define_flag("FLAGS_check_nan_inf", False,
            "scan op outputs for NaN/Inf (nan_inf_utils.h analog). STRICT "
            "debug mode: forces per-op dispatch with a device sync per "
            "inexact output, flushing any chain/step fusion — use it to "
            "LOCALIZE a known blowup. For always-on production checking "
            "see FLAGS_check_numerics, which keeps the fusion stack "
            "engaged")
define_flag("FLAGS_check_nan_inf_level", 0, "0: fail on nan/inf")

# Non-finite step guardian (ops/guardian.py). Unlike FLAGS_check_nan_inf —
# which drops dispatch to the per-op debug path and flushes every chain —
# this mode compiles a cheap all-finite reduction INTO the cached
# executables of all three fusion tiers: a per-op launch, a fused chain
# launch, and a fused whole-step launch each emit ONE extra scalar. The
# scalars are checked lazily (a small per-thread queue flushed at backward
# / optimizer-step boundaries), so there is no per-op host sync and the
# chain/step fusion wins survive. A promoted whole-step executable
# additionally computes a global grads-finite predicate and applies the
# update as where(finite, new_state, old_state): a poisoned batch becomes
# a bitwise no-op step (`nonfinite_skip` in the fusion flight recorder)
# instead of corrupted parameters. The eager (unfused) optimizer path
# applies the same skip-step semantics for parity.
define_flag("FLAGS_check_numerics", False,
            "fused in-graph numerics guardian: compile an all-finite "
            "reduction into per-op/chain/step executables (one scalar per "
            "launch, no per-op sync, fusion stays engaged), raise/warn on "
            "non-finite forward outputs at the next backward/step "
            "boundary, and turn a non-finite-gradient step into a bitwise "
            "no-op update (skip-step rescue). FLAGS_check_nan_inf remains "
            "the strict per-op fallback and takes precedence when set")
define_flag("FLAGS_check_numerics_level", 0,
            "0: raise FloatingPointError on a non-finite forward output; "
            ">=1: warn and continue. Gradient non-finiteness never raises "
            "— it skips the step (and backs off the GradScaler loss scale "
            "when one is attached)")
define_flag("FLAGS_benchmark", False, "sync after each op for timing")

# Serving resilience (paddle_tpu/serving/resilience.py). The watchdog
# bounds every decode/prefill fire: the step's result futures are waited
# on through a monitored completion (spin-then-sleep readiness poll, no
# extra threads or host syncs beyond the step's own result read). A step
# that blows the budget emits `serve.hang`, marks the engine degraded and
# runs the recovery ladder: retry the step, rebuild the decode
# executable, then fail the active requests with attributed reasons —
# never wedging the process on a step that does not come back.
define_flag("FLAGS_serve_step_timeout_ms", 0,
            "hung-step watchdog budget for one serving decode/prefill "
            "step, in milliseconds. 0 (default) disarms the watchdog: "
            "the engine blocks on the step result exactly as before. "
            "Size it at ~100x the expected p99 step latency so a real "
            "hang is caught in well under a second of TPU time while a "
            "GC pause or host hiccup never trips it")
define_flag("FLAGS_use_flash_attention", True,
            "route eligible attention through the Pallas flash kernel")
define_flag("FLAGS_serve_attention_kernel", "",
            "an explicit paged decode attention variant for the serving "
            "engine. Unset (the default) the engine chooses from what it "
            "observes (nn/functional/attention.py resolve_paged_kernel): "
            "'pallas' on a TPU over a per-head fp pool whose row and block "
            "are whole tiles, 'blockwise' for everything else. Set, it "
            "names one: 'pallas' (the TPU kernel that copies only the "
            "pages that hold tokens; falls back to blockwise off-TPU, on "
            "ineligible shapes and over an int8 pool with an attributed "
            "kernel.fallback event), 'blockwise' (the pure-JAX "
            "length-bounded loop with online softmax, the CPU/parity path, "
            "which never materializes the [S, T, H, D] context), or "
            "'reference' (the dense gather-by-block-table oracle). The "
            "value is keyed into the per-op dispatch cache key (the op fn "
            "closes over the resolved variant) and the AOT store's "
            "environment fingerprint, so flips re-key cleanly instead of "
            "replaying stale programs")
define_flag("FLAGS_use_fused_cross_entropy", False,
            "route large-vocab CE through the vocab-blocked Pallas kernel. "
            "Off by default: measured on v5e GPT-2 (V=50304), XLA's CE fused "
            "with the lm-head matmul wins end-to-end (86.7k vs 82.5k tok/s) "
            "because the kernel's vocab padding copies the logits; enable "
            "for memory-bound cases (very large vocab or long sequence)")
define_flag("FLAGS_use_fused_layer_norm", True,
            "route eligible bias+residual+LN through the Pallas row kernel")

# Compiled eager dispatch (ops/dispatch.py). The cache key is
# (op name, fn token, input (shape, dtype, weak_type) avals, diff mask,
# AMP-state token, registry override token); values are jitted forward /
# forward+vjp executables, so a repeated eager op sequence stops re-tracing
# after its first iteration. Telemetry — hits, misses, bypasses, retraces,
# evictions, cumulative dispatch wall time — is read with
# paddle_tpu.profiler.dispatch_cache_stats().
define_flag("FLAGS_eager_op_cache", True,
            "per-op executable cache in eager dispatch: repeated ops reuse "
            "compiled forward and VJP executables instead of re-tracing. "
            "Un-keyable calls (fns closing over arrays/Tensors, tracer "
            "inputs, jit-incompatible ops) bypass the cache, so numerics "
            "never change — only whether jax re-traces")
define_flag("FLAGS_eager_op_cache_size", 512,
            "LRU capacity (entries) of the eager op executable cache; the "
            "least-recently-used entry is evicted past this size. 0 disables "
            "caching entirely (keyable calls take the uncached path and are "
            "counted as bypasses in telemetry). Bounds forward entries only "
            "— backward applier traces (keyed by vjp residual treedef) live "
            "for the process unless ops.dispatch.clear_dispatch_cache() is "
            "called")
define_flag("FLAGS_eager_op_cache_donate", False,
            "EXPERIMENTAL: donate VJP residual buffers to the cached "
            "backward executable on the final (non-retained) backward. Off "
            "by default because residuals commonly alias buffers that are "
            "still live — op inputs/outputs the caller holds (weights!), "
            "or the same buffer saved as a residual by a sibling node that "
            "has not fired yet in the same backward pass — and donation "
            "invalidates them. Only safe when the graph is a chain whose "
            "intermediates are not referenced after backward; donation is "
            "a warn-and-skip no-op on CPU")

# Eager chain fusion (ops/fusion.py), the layer above the per-op cache:
# repeated op *sequences* (matmul→add→gelu, ...) are detected from the
# dispatch stream and compiled into ONE fused executable per chain — one
# XLA launch instead of N, one fused GradNode instead of N tape nodes.
# Replay is speculative: ops matching a hot chain are deferred and the
# fused executable fires when the chain completes; any mid-chain mismatch
# or an intermediate escaping the chain (a `.numpy()`, an unrelated op, a
# mutated stop_gradient) splits the chain back onto the per-op cached
# path (the same values up to the rounding by which a program compiled
# whole may differ from its ops run one by one). Telemetry:
# paddle_tpu.profiler.chain_fusion_stats().
define_flag("FLAGS_eager_chain_fusion", True,
            "fuse repeated eager op sequences into single compiled chain "
            "executables on top of the per-op cache. Chains are keyed by "
            "the constituent per-op cache keys plus the dataflow wiring "
            "between them, so every invalidation rule of the per-op cache "
            "(registry generation bump, AMP state, clear_dispatch_cache) "
            "applies to chains too. Falls back to per-op dispatch with "
            "bitwise-identical results whenever a chain breaks")
define_flag("FLAGS_eager_chain_fusion_min_count", 25,
            "hotness threshold: a candidate op sequence must repeat this "
            "many times before a fused chain executable is compiled for "
            "it. Compiling a chain costs O(seconds); a replay saves "
            "O(100us) — the default only fuses loops long enough to "
            "amortize the compile (any real training loop crosses it in "
            "the first second). Lower it in micro-benchmarks that want "
            "fusion to settle during a short warmup")
define_flag("FLAGS_eager_chain_cache_size", 128,
            "LRU capacity (chains) of the fused-chain executable cache; "
            "least-recently-replayed chains are evicted past this size. "
            "0 disables chain fusion (same semantics as the flag off)")
define_flag("FLAGS_eager_chain_stitching", True,
            "stitch adjacent hot chains whose boundary wiring matches into "
            "one longer chain: when chain B replays on the very next "
            "dispatch after chain A fired and B's external inputs wire to "
            "A's outputs, A+B is registered as a single chain — so "
            "sequences longer than the rolling detection window (whole "
            "transformer blocks) fuse into one launch without growing "
            "detection cost. Stitched chains obey every chain-fusion "
            "invalidation and fallback rule")

# Whole-step eager fusion (ops/step_fusion.py), the layer above chain
# fusion: a stable per-step cycle — forward ops, `loss.backward()`,
# optimizer `step()`/`clear_grad()` — repeated identically for
# FLAGS_eager_step_fusion_min_count iterations is promoted to ONE fused
# executable (forward + backward + grad clip/regularization + optimizer
# update) with donated optimizer-slot buffers: the auto-TrainStep. Replay
# is speculative and transactional exactly like chain fusion — any
# cycle-shape mismatch, a mid-step value peek, a changed optimizer/param
# set, or an execution fault splits back to chain/per-op dispatch (same
# values up to that rounding). The LR-schedule value and the optimizer step
# count are hoisted to scalar arguments, so schedulers never split.
# Telemetry: paddle_tpu.profiler.step_fusion_stats().
define_flag("FLAGS_eager_step_fusion", True,
            "promote a stable eager fwd+bwd+optimizer cycle to one fused "
            "whole-step executable (auto-TrainStep). Falls back to "
            "chain/per-op dispatch with identical numerics whenever the "
            "cycle diverges; requires the per-op cache "
            "(FLAGS_eager_op_cache with a nonzero cache size) to key the "
            "cycle's ops")
define_flag("FLAGS_eager_step_fusion_min_count", 40,
            "cycle-stability threshold: the per-step op/backward/optimizer "
            "cycle must repeat identically this many consecutive times "
            "before the whole-step executable is compiled. Whole-step "
            "compiles cost O(seconds) and the observation pass is cheap, "
            "so the default only promotes genuinely steady training loops; "
            "lower it in micro-benchmarks with a short warmup")
define_flag("FLAGS_eager_step_fusion_cache_size", 8,
            "LRU capacity (promoted step programs) kept per thread so a "
            "loop that temporarily diverges and re-stabilizes reuses its "
            "compiled whole-step executable instead of recompiling. 0 "
            "disables step fusion")
define_flag("FLAGS_eager_step_fusion_spmd", True,
            "distributed lowering of promoted steps (ops/spmd_fusion.py): "
            "when a cycle's batch lives sharded on a device mesh, compile "
            "the whole step through shard_map with the collectives fused "
            "in — gradient pmean over the batch axes, ZeRO-sharded "
            "optimizer update (slice/update/all-gather) when the slots "
            "carry a 'sharding' NamedSharding, and all-reduced guardian/"
            "GradScaler found-inf predicates. The first fire runs under "
            "probation (eager results commit, fused compared); a "
            "divergence demotes the program to the plain jit lowering. "
            "Off: sharded cycles promote through plain jit (GSPMD "
            "placement)")
# Fusion flight recorder (profiler/events.py): a bounded, thread-aware
# ring-buffer event log for the dispatch/fusion pipeline. Every decision
# point that bumps a telemetry counter — cache hit/miss/bypass, chain
# detect/compile/fire/split/stitch, step record/promote/fire/split/
# deactivate — also emits a typed event carrying the op name, a cache-key
# digest, and a machine-readable reason code, so a loop that silently
# never promotes (or splits mid-step) can be root-caused with
# paddle_tpu.profiler.explain / tools/fusion_doctor.py instead of staring
# at aggregate counters. Near-zero cost when off (one flag check per
# decision point); the profiler drains the ring into chrome-trace lanes.
define_flag("FLAGS_profiler_events", False,
            "record dispatch/chain/step fusion lifecycle events into the "
            "bounded in-process ring buffer (profiler/events.py). Off by "
            "default: every emission site degenerates to a single flag "
            "check. Enabled automatically inside a Profiler window and by "
            "tools/fusion_doctor.py")
define_flag("FLAGS_profiler_events_capacity", 65536,
            "ring-buffer capacity (events) of the fusion flight recorder; "
            "oldest events are dropped past this size. Applied when the "
            "ring is (re)created — clear_fusion_events() picks up a "
            "changed value")

# Production telemetry plane (profiler/metrics.py + profiler/goodput.py):
# a typed, thread-safe metrics registry (counters, gauges, bounded
# log-bucket streaming histograms with labels) plus a live training
# accountant deriving rolling MFU / tokens-per-second / goodput from the
# step stream. Follows the flight recorder's cost discipline: when off,
# every instrumentation site degenerates to a single flag check; when on,
# an observation is O(1) work against preallocated bucket arrays — memory
# never grows with run length. Exposed via registry.exposition()
# (Prometheus text format), tools/metrics_export.py (crash-safe JSONL
# sink, mergeable across processes), and `fusion_doctor --metrics`.
define_flag("FLAGS_metrics", False,
            "record production metrics (counters/gauges/histograms) into "
            "the in-process registry (profiler/metrics.py) and run the "
            "live MFU/goodput accountant (profiler/goodput.py). Off by "
            "default: every site is one flag check")
define_flag("FLAGS_metrics_window", 100_000,
            "sliding-window size (observations) of the registry's "
            "streaming histograms: percentiles are computed over the "
            "current + previous window bands, so a long-running process "
            "reports FRESH p50/p99 instead of an all-of-history average "
            "that froze hours ago. 0 = cumulative (never rotate)")

# Persistent AOT executable cache (ops/aot_cache.py): content-addressed
# on-disk store of `jax.export`-serialized fused executables — per-op
# forward / forward+vjp pairs, fused chains, promoted whole-step programs,
# the serving decode step — keyed by the existing cache-key digests plus an
# environment fingerprint (jax/jaxlib/numpy versions, backend, device
# kind, PRNG-key export form), so a restarting worker deserializes
# yesterday's executables instead of paying the full trace+compile warmup.
# Writes are atomic (tmp + fsync + rename, CRC-32 trailer shared with the
# checkpoint writer); torn or corrupt artifacts are detected on load,
# quarantined, and transparently recompiled — the store can never crash a
# training or serving process, only make its warmup cheaper.
# Live HTTP observability plane (profiler/telemetry_server.py). Off by
# default: 0 means no server thread, no socket, and every heartbeat site
# costs one module-bool check. A nonzero port starts the stdlib
# ThreadingHTTPServer at import (paddle_tpu/__init__) / engine build and
# serves /metrics, /metrics.json, /goodput, /doctor, /events, /healthz,
# /readyz on 127.0.0.1.
define_flag("FLAGS_telemetry_port", 0,
            "port for the zero-dependency telemetry HTTP server "
            "(profiler/telemetry_server.py). 0 (default) = off: no "
            "thread, no socket, heartbeats are one bool check. Seeded "
            "from the environment like every flag, so "
            "`FLAGS_telemetry_port=9100 python train.py` arms a live "
            "/metrics scrape surface")
define_flag("FLAGS_telemetry_host", "127.0.0.1",
            "bind address for the telemetry HTTP server. The loopback "
            "default keeps the surface node-local; set 0.0.0.0 (or a "
            "NIC address) for a cross-host Prometheus / fleet_metrics "
            "scrape")
define_flag("FLAGS_telemetry_stale_s", 120.0,
            "liveness window for /healthz heartbeat sources when the "
            "serving watchdog is disarmed: an open (un-finalized) "
            "training accountant or a busy engine whose last step is "
            "older than this reports unhealthy. Armed serving engines "
            "use the FLAGS_serve_step_timeout_ms budget instead")

# Performance regression sentinel (profiler/sentinel.py). Disarmed by
# default: every tick site costs one module-bool check. Armed, the
# sentinel snapshots the goodput accountant / metrics registry once per
# evaluation window, classifies drift against a named leg of the
# operator's baseline file — or against its own first clean window when
# no leg is named — and flips the /readyz degraded latch
# with the finding attached.
define_flag("FLAGS_sentinel", False,
            "arm the performance regression sentinel "
            "(profiler/sentinel.py): per-window drift verdicts "
            "(perf_drift / split_regression / compile_storm / "
            "latency_drift), a /sentinel endpoint on the telemetry "
            "server, and a /readyz flip on confirmed drift. Disarmed "
            "= one bool check per step")
define_flag("FLAGS_sentinel_window_s", 10.0,
            "sentinel evaluation window in seconds: drift is judged "
            "over whole windows (one registry/accountant snapshot per "
            "window), so smaller windows detect faster but judge "
            "noisier statistics")
define_flag("FLAGS_sentinel_baseline", "",
            "path to the operator's per-leg perf baseline JSON "
            "(sentinel.PerfBaseline) for the sentinel; none is "
            "shipped, and a named leg is refused without it")
define_flag("FLAGS_sentinel_leg", "",
            "leg of FLAGS_sentinel_baseline the live sentinel "
            "compares against; empty = self-calibrate: the "
            "first completed clean window becomes the reference band")

define_flag("FLAGS_aot_cache", False,
            "persist fused executables (per-op/chain/whole-step/serving "
            "decode) to a content-addressed on-disk store via jax.export "
            "and reload them on restart: a preempted worker re-promotes "
            "its fused train step on the first cycle with zero fresh "
            "traces (warm start). Off by default: storing exports each "
            "executable once at build time (extra trace cost in COLD "
            "processes); enable it for fleet workers that restart under "
            "traffic. Corrupt/version-skewed artifacts are quarantined "
            "and recompiled, never trusted")
define_flag("FLAGS_aot_cache_dir", "",
            "root directory of the AOT executable store. Empty (default): "
            "$PADDLE_TPU_CACHE_DIR/aot when the env var is set (tests "
            "share this root with the persistent XLA compile cache), "
            "else /tmp/paddle_tpu_cache/aot. Content addressing makes "
            "concurrent multi-process writers safe: same key -> same "
            "bytes, last atomic rename wins")
define_flag("FLAGS_aot_cache_max_bytes", 1 << 30,
            "size budget of the AOT store; past it, eviction removes "
            "oldest-mtime artifacts first (loads refresh mtime, so the "
            "policy is LRU-ish). Checked opportunistically after stores "
            "and by `fusion_doctor --cache --gc`. 0 disables the size "
            "bound")
define_flag("FLAGS_aot_cache_max_age_s", 14 * 86400,
            "age bound of the AOT store (seconds since last use); older "
            "artifacts and quarantined *.corrupt files are removed by "
            "eviction. 0 disables the age bound")

define_flag("FLAGS_eager_step_fusion_donate_params", False,
            "EXPERIMENTAL: donate parameter buffers (in addition to the "
            "optimizer-slot buffers, which are always donated exactly as "
            "the eager optimizer's own fused update donates them) to the "
            "whole-step executable. Off by default for the same aliasing "
            "hazard as jit.TrainStep's donate='all': user-held aliases of "
            "p._value (detach() shares storage) would be invalidated. "
            "Donation is a warn-and-skip no-op on CPU")


class _FlagsView:
    def __getattr__(self, name):
        full = name if name.startswith("FLAGS_") else f"FLAGS_{name}"
        try:
            return _FLAGS[full]
        except KeyError:
            raise AttributeError(name)

    def __setattr__(self, name, value):
        global _GENERATION
        full = name if name.startswith("FLAGS_") else f"FLAGS_{name}"
        with _lock:
            _FLAGS[full] = value
            _GENERATION += 1


FLAGS = _FlagsView()


def set_flags(flags: dict):
    global _GENERATION
    with _lock:
        for k, v in flags.items():
            _FLAGS[k] = v
        _GENERATION += 1


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    return {k: _FLAGS.get(k) for k in flags}
