"""Persistent AOT executable cache: preemption-proof warm start.

Every process today pays the full trace+compile warmup before any fusion
tier fires — the per-op executables (ops/dispatch.py), the fused chains
(ops/fusion.py), the promoted whole-step program (ops/step_fusion.py), and
the serving decode step (serving/engine.py) are all built from scratch. A
preempted or kill-9'd worker restarting under traffic therefore loses the
entire fusion stack exactly when latency matters most. This module is the
fix: a content-addressed on-disk store of `jax.export`-serialized
executables, so a restarting worker deserializes yesterday's programs and
re-promotes its fused train step on the FIRST training cycle with zero
fresh traces. Reference analog: Paddle's save/load_inference_model +
Predictor serialized-program path, scaled down to individual fused
executables and up to the whole training step.

Keying. Artifacts are addressed by a SHA-256 digest of the existing cache
keys — the per-op dispatch key (op name, fn value-token, input avals, diff
mask, AMP state, registry override, guardian flag), the chain signature
(per-op keys + wiring), the step cycle signature (op entries + backward/
optimizer events + optimizer binding constants) — canonicalized so only
process-local identities (object ids, interned ints, registry generation
counters) are erased and everything semantic survives: code objects digest
by their bytecode + consts + names, module-level functions by
module:qualname, scalars by value. Anything that cannot be canonicalized
safely simply opts out of the store (the live compiled path is untouched).
The filename additionally carries an ENVIRONMENT FINGERPRINT digest
(jax/jaxlib/numpy versions, backend platform, device kind, the PRNG-key
export form, kernel-routing flags), so version skew invalidates by
construction instead of deserializing garbage — a mismatched artifact is
reported (`aot.version_skew`) and recompiled, never trusted.

Durability. Writes go tmp + fsync + atomic rename with the same CRC-32
trailer the crash-safe checkpoint writer uses (framework/io.py), so a
crash mid-store can never leave a torn artifact under a live name, and
concurrent multi-process writers are safe by construction: content
addressing means same key -> same bytes, and the last rename wins. Loads
verify the trailer and the pickle envelope; any corruption quarantines the
file (renamed to *.corrupt for the doctor) and falls back to a transparent
recompile — `aot.corrupt` in the flight recorder, never a crash. The store
is size- and age-bounded (FLAGS_aot_cache_max_bytes / _max_age_s), evicted
oldest-mtime-first (loads refresh mtime); `fusion_doctor --cache [--gc]`
lists and collects it manually.

Grad-path decomposition. jax.export can only serialize array-in/array-out
programs, but the live fwd+vjp executables return their pullback as a
`tree_util.Partial` (residual buffers + a closure) that cannot cross a
process boundary. Stored grad artifacts therefore ship as TWO programs:
the primal forward, and a rematerializing backward `(inputs, cotangent) ->
input grads` that recomputes the forward inside the backward. The warm
process pays one extra forward FLOP per op during its single observation
cycle — after which the whole step replays as the ONE restored fused-step
program and the per-op path is idle — in exchange for zero Python-level
retraces at restart. Telemetry: profiler/aot.py counters
(`profiler.aot_cache_stats()`) +
`aot.{hit,miss,store,corrupt,version_skew,evict}`
flight-recorder events.
"""
from __future__ import annotations

import enum
import hashlib
import os
import pickle
import socket
import threading
import time
import types

import numpy as np
import jax

from ..framework.flags import _FLAGS
from ..framework.io import (CheckpointCorruptError, _write_atomic,
                            read_verified_payload)
from ..profiler.aot import STATS as _STATS
from ..profiler.events import EVENTS as _EVENTS

__all__ = ["enabled", "cache_dir", "env_fingerprint", "fingerprint_digest",
           "op_key_digest", "store_entries", "gc_store", "AotPullback"]

_SCHEMA = 1                 # bump to orphan every existing artifact
_DIGEST_CHARS = 40          # hex chars of the key digest in the filename
_EVICT_EVERY = 16           # opportunistic eviction cadence (stores)


class Undigestable(Exception):
    """A cache-key component has no stable cross-process canonical form;
    the entry opts out of the AOT store (the live path is unaffected)."""


# ---------------------------------------------------------------------------
# canonicalization: erase process-local identity, keep semantics
# ---------------------------------------------------------------------------

def _canon_code(code, depth):
    return ("code", code.co_name, code.co_argcount,
            code.co_kwonlyargcount, code.co_flags, code.co_code,
            _canon(code.co_consts, depth + 1), code.co_names,
            code.co_varnames, code.co_freevars, code.co_cellvars)


def _canon_callable(v):
    """Module-level functions/classes/ufuncs token by module:qualname —
    the same stability contract dispatch's identity keying relies on (a
    module-level def cannot change under the key within one code
    version; cross-version drift is accepted and documented)."""
    mod = getattr(v, "__module__", None)
    qual = getattr(v, "__qualname__", None) or getattr(v, "__name__", None)
    if not mod or not qual:
        raise Undigestable(f"anonymous callable {type(v).__name__}")
    return ("fn", mod, qual)


def _canon(v, depth=0):
    """Canonical (picklable, cross-process-stable) form of a cache-key
    component. Raises Undigestable for anything identity-bound."""
    if depth > 10:
        raise Undigestable("nesting too deep")
    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        return v
    if v is Ellipsis or v is NotImplemented:
        # stable interpreter singletons (Ellipsis rides in the bytecode
        # consts of any fn using `...` indexing — the embedding kernel)
        return ("singleton", repr(v))
    if isinstance(v, types.CodeType):
        return _canon_code(v, depth)
    if isinstance(v, np.dtype):
        return ("npdtype", str(v))
    if isinstance(v, np.generic):
        return ("npscalar", str(v.dtype), v.tobytes())
    if isinstance(v, enum.Enum):
        return ("enum", type(v).__module__, type(v).__qualname__, v.name)
    if isinstance(v, type):
        return ("type", v.__module__, v.__qualname__)
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(_canon(i, depth + 1) for i in v)
    if isinstance(v, dict):
        # keys canonicalize too (they could carry code objects or other
        # unpicklables); sort by the canonical repr so ordering never
        # depends on cross-type comparability
        items = [(_canon(k, depth + 1), _canon(i, depth + 1))
                 for k, i in v.items()]
        return ("dict",) + tuple(sorted(items, key=repr))
    if callable(v):
        return _canon_callable(v)
    # jax dtype-like objects (extended dtypes) stringify stably
    if hasattr(v, "dtype") and not hasattr(v, "shape"):
        return ("dtypelike", str(v))
    raise Undigestable(type(v).__name__)


def _digest_of(canonical) -> str:
    try:
        payload = pickle.dumps((canonical, _SCHEMA), protocol=4)
    except Exception as e:
        # a canonical form that still fails to pickle (an exotic scalar
        # subtype, a recursive structure) opts the key out — the store
        # must degrade, never crash a training boundary
        raise Undigestable(f"unpicklable canonical form: {e}")
    return hashlib.sha256(payload).hexdigest()


def op_key_digest(key):
    """Stable digest of a PR 1 per-op cache key, or None when the key has
    no cross-process canonical form. The registry token (component 5) is
    canonicalized to the active override NAME only: the generation counter
    is a process-local invalidation serial (the override's own fn token
    already keys the implementation by value)."""
    if key is None:
        return None
    try:
        name, ftok, avals, diff_mask, amp, reg, check = key
        canonical = ("op", name, _canon(ftok), _canon(avals), diff_mask,
                     _canon(amp), ("reg", reg[0] if reg else None),
                     bool(check))
        return _digest_of(canonical)
    except (Undigestable, ValueError, TypeError):
        return None


def op_key_canonical(key):
    """The canonical structure itself (for embedding into chain/step
    digests without double-hashing). Raises Undigestable."""
    name, ftok, avals, diff_mask, amp, reg, check = key
    return ("op", name, _canon(ftok), _canon(avals), diff_mask,
            _canon(amp), ("reg", reg[0] if reg else None), bool(check))


# ---------------------------------------------------------------------------
# environment fingerprint: version skew invalidates by construction
# ---------------------------------------------------------------------------

_fp_cache = None
_fp_generation = (-1, -1)  # (flags._GENERATION, mesh generation) of the memo
_fp_lock = threading.Lock()

# Flags read on the compiled-op path that are DELIBERATELY absent from
# `env_fingerprint` (lint rule R7 requires every FLAGS_* used under ops/
# or nn/ to be fingerprinted here or declared below). Two families only:
#   * cache-shape knobs (eager_* tier gates/sizes, aot_cache_* storage
#     limits): they decide WHETHER a cache/fusion tier engages, never the
#     lowered program for a given cache key — each program is keyed by
#     its own op/avals key, so flipping these cannot alias artifacts;
#   * host-side validation/debug toggles (check_nan_inf*,
#     check_numerics*, benchmark): they run on host values around the
#     dispatch, outside the compiled program.
# A flag that changes which kernel an op lowers to does NOT belong here —
# it goes into the fingerprint's flags tuple.
FUSION_NEUTRAL_FLAGS = frozenset({
    "FLAGS_aot_cache",
    "FLAGS_aot_cache_dir",
    "FLAGS_aot_cache_max_age_s",
    "FLAGS_aot_cache_max_bytes",
    "FLAGS_benchmark",
    "FLAGS_check_nan_inf",
    "FLAGS_check_nan_inf_level",
    "FLAGS_check_numerics",
    "FLAGS_check_numerics_level",
    "FLAGS_eager_chain_cache_size",
    "FLAGS_eager_chain_fusion",
    "FLAGS_eager_chain_fusion_min_count",
    "FLAGS_eager_chain_stitching",
    "FLAGS_eager_op_cache",
    "FLAGS_eager_op_cache_donate",
    "FLAGS_eager_op_cache_size",
    "FLAGS_eager_step_fusion",
    "FLAGS_eager_step_fusion_cache_size",
    "FLAGS_eager_step_fusion_donate_params",
    "FLAGS_eager_step_fusion_min_count",
    "FLAGS_eager_step_fusion_spmd",
})


def env_fingerprint() -> dict:
    """What must match for a stored executable to be trusted: serializer
    schema, jax/jaxlib/numpy versions, backend platform, device kind, the
    PRNG-key export form, the kernel-routing flags that steer which
    implementation an op dispatches to, AND the mesh topology (global
    device count + axis layout of the global mesh) — a single-chip
    artifact must never deserialize into a sharded process, and a dp=8
    artifact must never deserialize into a dp=2×sharding=4 one. Memoized
    against the flag-store AND mesh-generation counters, so a mid-run
    set_flags/set_global_mesh re-fingerprints instead of stamping new
    artifacts with stale state."""
    global _fp_cache, _fp_digest_cache, _fp_generation
    from ..framework import flags as _flags_mod
    from ..distributed import mesh as _mesh_mod
    gen = (_flags_mod._GENERATION, _mesh_mod.mesh_generation())
    if _fp_cache is not None and gen == _fp_generation:
        return _fp_cache
    with _fp_lock:
        if _fp_cache is not None and gen == _fp_generation:
            return _fp_cache
        _fp_digest_cache = None
        _fp_generation = gen
        try:
            import jaxlib
            jaxlib_v = getattr(jaxlib, "__version__", "?")
        except Exception:
            jaxlib_v = "?"
        try:
            dev = jax.devices()[0]
            platform, kind = dev.platform, getattr(dev, "device_kind", "?")
        except Exception:
            platform, kind = "?", "?"
        fp = {
            "schema": _SCHEMA,
            "jax": jax.__version__,
            "jaxlib": jaxlib_v,
            "numpy": np.__version__,
            "platform": platform,
            "device_kind": kind,
            "mesh": _mesh_mod.topology_token(),
            "flags": tuple(sorted(
                [(k, bool(_FLAGS.get(k)))
                 for k in ("FLAGS_use_flash_attention",
                           "FLAGS_use_fused_layer_norm",
                           "FLAGS_use_fused_cross_entropy")]
                # the serving kernel tier is a string-valued routing flag:
                # a blockwise artifact must never deserialize into a
                # pallas (or reference) process
                + [("FLAGS_serve_attention_kernel",
                    str(_FLAGS.get("FLAGS_serve_attention_kernel")))])),
        }
        _fp_cache = fp
        return fp


_fp_digest_cache = None


def fingerprint_digest() -> str:
    """Memoized: the digest sits on the hot path (every artifact path
    construction, including the per-boundary has_step probe). The
    env_fingerprint() call comes first — it invalidates this memo when
    the flag store mutated."""
    global _fp_digest_cache
    fp = env_fingerprint()
    if _fp_digest_cache is None:
        _fp_digest_cache = hashlib.sha256(
            pickle.dumps(fp, protocol=4)).hexdigest()[:12]
    return _fp_digest_cache


def _reset_fingerprint_cache():
    """Test hook: kernel-routing flag flips re-fingerprint."""
    global _fp_cache, _fp_digest_cache
    _fp_cache = None
    _fp_digest_cache = None


# ---------------------------------------------------------------------------
# the store: content-addressed files, atomic writes, quarantine on corrupt
# ---------------------------------------------------------------------------

def enabled() -> bool:
    return bool(_FLAGS.get("FLAGS_aot_cache")) and _export_available()


_export_ok = None


def _export_available():
    global _export_ok
    if _export_ok is None:
        try:
            from jax import export as _  # noqa: F401
            _export_ok = True
        except Exception:
            _export_ok = False
    return _export_ok


def cache_dir() -> str:
    d = _FLAGS.get("FLAGS_aot_cache_dir") or ""
    if d:
        return os.fspath(d)
    root = os.environ.get("PADDLE_TPU_CACHE_DIR")
    if root:
        return os.path.join(root, "aot")
    return "/tmp/paddle_tpu_cache/aot"


def _artifact_path(kind, digest, root=None):
    return os.path.join(root or cache_dir(),
                        f"{kind}-{digest[:_DIGEST_CHARS]}-"
                        f"{fingerprint_digest()}.aot")


def has_artifact(kind, digest) -> bool:
    return digest is not None and os.path.exists(_artifact_path(kind,
                                                                digest))


def _quarantine(path):
    """Move a failed artifact aside (kept as *.corrupt for the doctor;
    eviction removes quarantined files). Best-effort: a concurrent writer
    may have already replaced or removed it."""
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass


_store_count = 0
_evict_lock = threading.Lock()


def store_artifact(kind, digest, label, blobs, meta=None) -> bool:
    """Serialize `blobs` (already-exported program bytes) under the
    content address. Atomic (tmp+fsync+rename with the shared CRC-32
    trailer): concurrent writers of the same key race to an identical
    result, disjoint keys never interfere. Returns True on a write."""
    global _store_count
    path = _artifact_path(kind, digest)
    payload = pickle.dumps({
        "v": 1, "kind": kind, "digest": digest, "label": label,
        "fingerprint": env_fingerprint(), "created": time.time(),
        # which fleet host exported this: on a shared store the doctor's
        # provenance column — who paid the compile the others reuse
        "host": socket.gethostname(),
        "meta": meta or {}, "blobs": list(blobs),
    }, protocol=4)
    try:
        _write_atomic(path, payload)
    except OSError:
        _STATS.store_failures += 1
        return False
    _STATS.stores += 1
    _STATS.bytes_written += len(payload)
    _EVENTS.emit("aot.store", label,
                 detail={"kind": kind, "bytes": len(payload),
                         "digest": digest[:12]})
    _store_count += 1
    if _store_count % _EVICT_EVERY == 1:
        _maybe_evict()
    return True


def load_artifact(kind, digest, label):
    """Read + verify + unpickle an artifact. Returns the payload dict, or
    None on a miss / version skew / corruption — the latter two with the
    file quarantined and the decision attributed in the flight recorder,
    so the caller's only job is a transparent recompile."""
    if digest is None:
        return None
    path = _artifact_path(kind, digest)
    try:
        payload = read_verified_payload(path, require_trailer=True)
        art = pickle.loads(payload)
        if not isinstance(art, dict) or "blobs" not in art:
            raise CheckpointCorruptError(f"{path}: not an AOT artifact")
    except FileNotFoundError:
        _STATS.misses += 1
        _EVENTS.emit("aot.miss", label, detail={"kind": kind,
                                                "digest": digest[:12]})
        _note_skew(kind, digest, label)
        return None
    except Exception as e:
        # CRC mismatch, truncation, an unreadable pickle stream, a stale
        # class in the envelope — all the same outcome: quarantine and
        # recompile, never trust the bytes
        _STATS.corrupt += 1
        _EVENTS.emit("aot.corrupt", label, reason="artifact_corrupt",
                     detail={"kind": kind, "error": repr(e)[:200]})
        _quarantine(path)
        return None
    if art.get("fingerprint") != env_fingerprint():
        # filename collisions on the fingerprint digest are astronomically
        # unlikely but the full check is one dict compare — never
        # deserialize a program built for a different environment
        _STATS.version_skew += 1
        _EVENTS.emit("aot.version_skew", label, reason="version_skew",
                     detail={"kind": kind,
                             "theirs": art.get("fingerprint")})
        return None
    try:
        os.utime(path)          # refresh mtime: eviction is LRU-ish
    except OSError:
        pass
    _STATS.bytes_loaded += sum(len(b) for b in art["blobs"])
    return art


_skew_scan = (0.0, None, frozenset())    # (ts, root, names)
_SKEW_SCAN_TTL_S = 60.0


def _store_names():
    """Directory listing for the skew probe, cached with a short TTL: a
    cold warmup misses once per key, and an O(store) listdir per miss is
    real money on a shared NFS/GCS store. Staleness only delays a
    diagnostic event, never a load decision."""
    global _skew_scan
    ts, root, names = _skew_scan
    now = time.time()
    cur = cache_dir()
    if root != cur or now - ts > _SKEW_SCAN_TTL_S:
        try:
            names = frozenset(os.listdir(cur))
        except OSError:
            names = frozenset()
        _skew_scan = (now, cur, names)
    return names


def _note_skew(kind, digest, label):
    """An exact-fingerprint miss where artifacts for the same key exist
    under OTHER fingerprints is version skew worth reporting (the worker
    fleet is running mixed versions, or an upgrade just orphaned the
    store)."""
    prefix = f"{kind}-{digest[:_DIGEST_CHARS]}-"
    for fn in _store_names():
        if fn.startswith(prefix) and fn.endswith(".aot"):
            _STATS.version_skew += 1
            _EVENTS.emit("aot.version_skew", label,
                         reason="version_skew",
                         detail={"kind": kind, "file": fn})
            return


# ---------------------------------------------------------------------------
# eviction: size/mtime bounded, quarantined files first
# ---------------------------------------------------------------------------

# a tmp file this old can only be the leftover of a writer that died
# between open() and rename() — exactly the preemption this store exists
# to survive; sweep it so kill-9'd fleets don't leak disk
_STALE_TMP_S = 3600.0


def gc_store(root=None, max_bytes=None, max_age_s=None,
             purge_quarantine=False):
    """Evict over-age and over-budget artifacts (oldest mtime first),
    stale `*.tmp.*` leftovers of killed writers, and — past the age bound
    or with `purge_quarantine` (the explicit `fusion_doctor --cache
    --gc` path) — quarantined `*.corrupt` files. Fresh quarantines
    survive the automatic post-store sweep so the doctor can still list
    and explain them. Returns the removed file names."""
    root = root or cache_dir()
    if max_bytes is None:
        max_bytes = int(_FLAGS.get("FLAGS_aot_cache_max_bytes", 1 << 30)
                        or 0)
    if max_age_s is None:
        max_age_s = float(_FLAGS.get("FLAGS_aot_cache_max_age_s",
                                     14 * 86400) or 0)
    removed = []
    try:
        names = os.listdir(root)
    except OSError:
        return removed
    now = time.time()
    rows = []
    for fn in names:
        p = os.path.join(root, fn)
        try:
            st = os.stat(p)
        except OSError:
            continue
        if ".aot.tmp." in fn:
            if now - st.st_mtime > _STALE_TMP_S:
                rows.append((fn, p, st.st_size, st.st_mtime, "tmp"))
            continue
        if fn.endswith(".corrupt"):
            rows.append((fn, p, st.st_size, st.st_mtime, "corrupt"))
        elif fn.endswith(".aot"):
            rows.append((fn, p, st.st_size, st.st_mtime, "aot"))

    def _drop(fn, p, size, why, age):
        try:
            os.unlink(p)
        except OSError:
            return
        removed.append(fn)
        _STATS.evictions += 1
        _EVENTS.emit("aot.evict", fn,
                     detail={"bytes": size, "age_s": round(age, 1),
                             "why": why})

    live = []
    for fn, p, size, mtime, kind in rows:
        age = now - mtime
        if kind == "tmp":
            _drop(fn, p, size, "stale_tmp", age)
        elif kind == "corrupt":
            if purge_quarantine or (max_age_s and age > max_age_s):
                _drop(fn, p, size, "quarantined", age)
            else:
                # fresh quarantines survive for the doctor, but they DO
                # count against (and yield to) the size budget — a flaky
                # disk must not grow the store past its bound
                live.append((mtime, fn, p, size))
        elif max_age_s and age > max_age_s:
            _drop(fn, p, size, "age", age)
        else:
            live.append((mtime, fn, p, size))
    if max_bytes:
        total = sum(size for _, _, _, size in live)
        for mtime, fn, p, size in sorted(live):
            if total <= max_bytes:
                break
            _drop(fn, p, size, "size", now - mtime)
            total -= size
    return removed


def _maybe_evict():
    if not _evict_lock.acquire(blocking=False):
        return
    try:
        gc_store()
    finally:
        _evict_lock.release()


def store_entries(root=None, verify=True):
    """Doctor listing: one dict per artifact file (kind, digest,
    fingerprint match, label, size, age, corrupt flag). With `verify`,
    each file's CRC trailer and envelope are checked so torn writes show
    up as corrupt instead of as healthy rows."""
    root = root or cache_dir()
    out = []
    try:
        names = sorted(os.listdir(root))
    except OSError:
        return out
    now = time.time()
    my_fp = fingerprint_digest()
    for fn in names:
        if not (fn.endswith(".aot") or fn.endswith(".corrupt")):
            continue
        p = os.path.join(root, fn)
        try:
            st = os.stat(p)
        except OSError:
            continue
        row = {"file": fn, "bytes": st.st_size,
               "age_s": round(now - st.st_mtime, 1),
               "quarantined": fn.endswith(".corrupt"),
               "kind": fn.split("-", 1)[0] if "-" in fn else "?",
               "label": None, "host": None,
               "fingerprint_match": None, "corrupt": None}
        stem = fn[:-len(".aot")] if fn.endswith(".aot") else fn
        parts = stem.split("-")
        if len(parts) >= 3:
            row["digest"] = parts[1]
            row["fingerprint_match"] = parts[2].split(".")[0] == my_fp
        if verify and not row["quarantined"]:
            try:
                art = pickle.loads(
                    read_verified_payload(p, require_trailer=True))
                row["label"] = art.get("label")
                row["host"] = art.get("host")
                row["corrupt"] = False
                row["fingerprint_match"] = \
                    art.get("fingerprint") == env_fingerprint()
            except Exception:
                row["corrupt"] = True
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# export / import of executables
# ---------------------------------------------------------------------------

def _spec_of(v):
    return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                weak_type=getattr(v, "weak_type", False))


def _specs_of(vals):
    return jax.tree_util.tree_map(_spec_of, vals)


def export_bytes(jitted, specs) -> bytes:
    """Trace+lower `jitted` at `specs` via jax.export and serialize. The
    export IS a trace (any compile-counting side effects inside the traced
    fn run once more) — honest accounting, paid only in processes that
    write the store."""
    from jax import export as jexport
    return jexport.export(jitted)(*specs).serialize()


def _deserialize_callable(blob, donate_argnums=()):
    from jax import export as jexport
    exported = jexport.deserialize(bytes(blob))
    # jit around the opaque call: the wrapper traces once (trivially — the
    # body is one pre-lowered module) and the XLA compile of the stablehlo
    # shares the persistent compilation cache; donation re-applies at the
    # wrapper so TPU buffer reuse survives the round trip
    if donate_argnums:
        return jax.jit(exported.call, donate_argnums=tuple(donate_argnums))
    return jax.jit(exported.call)


class _Healing:
    """A deserialized executable that can never take the process down: any
    non-runtime failure (argument/signature mismatch from a hash
    collision, a stale module, a deserializer edge) quarantines the
    artifact, rebuilds the REAL executable via the fallback builder, and
    replays the call — transparent recompile, identical contract. Genuine
    XLA runtime faults propagate unchanged so the callers' existing
    exec_fault handling stays truthful."""

    __slots__ = ("_impl", "_fallback", "_path", "_label", "healed")

    def __init__(self, impl, fallback, path, label):
        self._impl = impl
        self._fallback = fallback
        self._path = path
        self._label = label
        self.healed = False

    def __call__(self, *args):
        try:
            return self._impl(*args)
        except jax.errors.JaxRuntimeError:
            raise
        except Exception as e:
            if self.healed:
                raise
            _STATS.corrupt += 1
            _EVENTS.emit("aot.corrupt", self._label,
                         reason="artifact_corrupt",
                         detail={"stage": "call",
                                 "error": repr(e)[:200]})
            _quarantine(self._path)
            self._impl = self._fallback()
            self.healed = True
            return self._impl(*args)


def load_callable(kind, digest, label, fallback, donate_argnums=(),
                  accept=None):
    """One-program artifact -> a healing callable, or None (miss / skew /
    corrupt — all attributed; the caller builds live). `accept` is an
    optional predicate over the artifact meta: a False verdict is a miss
    (the stored program has an incompatible calling convention — e.g. a
    plain-jit lowering where the live program wants shard_map), never a
    quarantine."""
    art = load_artifact(kind, digest, label)
    if art is None:
        return None
    if accept is not None and not accept(art.get("meta") or {}):
        _STATS.misses += 1
        _EVENTS.emit("aot.miss", label,
                     detail={"kind": kind, "digest": digest[:12],
                             "why": "lowering_mismatch"})
        return None
    try:
        impl = _deserialize_callable(art["blobs"][0], donate_argnums)
    except Exception as e:
        _STATS.corrupt += 1
        _EVENTS.emit("aot.corrupt", label, reason="artifact_corrupt",
                     detail={"kind": kind, "stage": "deserialize",
                             "error": repr(e)[:200]})
        _quarantine(_artifact_path(kind, digest))
        return None
    _STATS.hits += 1
    _EVENTS.emit("aot.hit", label, detail={"kind": kind,
                                           "digest": digest[:12]})
    return _Healing(impl, fallback, _artifact_path(kind, digest), label)


# ---------------------------------------------------------------------------
# grad-path artifacts: primal + rematerializing backward
# ---------------------------------------------------------------------------

def _live_vjp(fn, vals, diff_idx):
    """The uncached pullback over the differentiable subset (the
    _slow_vjp partial-fn contract) — the healing fallback for a stored
    backward program."""
    if len(diff_idx) == len(vals):
        return jax.vjp(fn, *vals)[1]

    def pf(*dv):
        full = list(vals)
        for i, v in zip(diff_idx, dv):
            full[i] = v
        return fn(*full)
    return jax.vjp(pf, *(vals[i] for i in diff_idx))[1]


class AotPullback:
    """Per-call pullback handle produced by a restored grad executable.

    Recognized by dispatch._make_cached_vjp / fusion._make_chain_vjp in
    place of the live `tree_util.Partial`: `make_wrapped` yields the same
    engine-facing pullback contract, backed by the stored rematerializing
    backward program instead of the in-process residual applier. On any
    non-runtime failure it falls back to a live jax.vjp over the captured
    inputs (memoized — a retained-graph double backward pays one trace,
    not one per call) AND tells the owning executable to quarantine the
    artifact and heal, so future forwards — and future restarts — take
    the live compiled path instead of re-failing forever."""

    __slots__ = ("_bwd", "_vals", "_fn", "_diff_idx", "_label", "_owner",
                 "_live")

    def __init__(self, bwd, vals, fn, diff_idx, label, owner=None):
        self._bwd = bwd
        self._vals = vals
        self._fn = fn
        self._diff_idx = diff_idx
        self._label = label
        self._owner = owner
        self._live = None

    def make_wrapped(self, diff_idx, n_in, multi):
        pb = self

        def wrapped(g, donate=False):
            # donation of residuals does not apply: the stored backward
            # rematerializes from the (still live) inputs
            if multi and not isinstance(g, tuple):
                g = (g,)
            if pb._live is not None:
                partial = pb._live(g)
            else:
                try:
                    partial = pb._bwd(pb._vals, g)
                except jax.errors.JaxRuntimeError:
                    raise
                except Exception as e:
                    _STATS.corrupt += 1
                    _EVENTS.emit("aot.corrupt", pb._label,
                                 reason="artifact_corrupt",
                                 detail={"stage": "backward",
                                         "error": repr(e)[:200]})
                    if pb._owner is not None:
                        pb._owner.mark_bwd_broken()
                    pb._live = _live_vjp(pb._fn, pb._vals, pb._diff_idx)
                    partial = pb._live(g)
            full = [None] * n_in
            for i, pg in zip(diff_idx, partial):
                full[i] = pg
            return tuple(full)
        wrapped._supports_donate = True
        return wrapped


class _AotGradExe:
    """Restored grad-path executable with the `_build_fwd_vjp` call
    contract: exe(*vals) -> (out, pullback) — or ((out, pullback), fin)
    under the guardian — where the pullback is an AotPullback over the
    stored backward. Self-healing: a failing primal swaps in the real
    compiled executable (whose Partial pullback then takes the normal
    applier path)."""

    __slots__ = ("_primal", "_bwd", "_fn", "_diff_idx", "_check", "_label",
                 "_path", "_fallback", "_healed")

    def __init__(self, primal, bwd, fn, diff_idx, check, label, path,
                 fallback):
        self._primal = primal
        self._bwd = bwd
        self._fn = fn
        self._diff_idx = diff_idx
        self._check = check
        self._label = label
        self._path = path
        self._fallback = fallback
        self._healed = None

    def __call__(self, *vals):
        if self._healed is not None:
            return self._healed(*vals)
        try:
            res = self._primal(*vals)
        except jax.errors.JaxRuntimeError:
            raise
        except Exception as e:
            _STATS.corrupt += 1
            _EVENTS.emit("aot.corrupt", self._label,
                         reason="artifact_corrupt",
                         detail={"stage": "primal",
                                 "error": repr(e)[:200]})
            _quarantine(self._path)
            self._healed = self._fallback()
            return self._healed(*vals)
        if self._check:
            out, fin = res
        else:
            out = res
        pb = AotPullback(self._bwd, vals, self._fn, self._diff_idx,
                         self._label, owner=self)
        return ((out, pb), fin) if self._check else (out, pb)

    def mark_bwd_broken(self):
        """A pullback's stored backward failed: quarantine the artifact
        and swap in the real compiled executable so every FUTURE forward
        (and restart) takes the live path."""
        if self._healed is None:
            _quarantine(self._path)
            try:
                self._healed = self._fallback()
            except Exception:
                pass


def _wrap_check_primal(fn, check):
    """The forward program to export: `fn` itself, or — under the
    guardian — `fn` plus the ONE fused all-finite scalar, mirroring the
    live `_build_fwd[_vjp]` / chain-build output contract. One helper so
    the op/chain/grad variants cannot drift."""
    if not check:
        return fn
    from . import guardian

    def primal(*xs):
        out = fn(*xs)
        outs = out if isinstance(out, tuple) else (out,)
        return out, guardian.finite_all(outs)
    return primal


def _export_primal_bwd(fn, diff_idx, check, in_specs, label):
    """Export the (primal, remat-backward) program pair for a grad-path
    fn. The cotangent signature comes from an abstract eval of `fn` — no
    concrete execution, no device work."""
    primal = _wrap_check_primal(fn, check)

    def bwd(xs, g):
        return _live_vjp(fn, xs, diff_idx)(g)

    out_specs = jax.eval_shape(fn, *in_specs)
    return [export_bytes(jax.jit(primal), in_specs),
            export_bytes(jax.jit(bwd), (tuple(in_specs), out_specs))]


# ---------------------------------------------------------------------------
# per-op tier (ops/dispatch.py hooks)
# ---------------------------------------------------------------------------

def store_op(key, name, fn, diff_idx, check, vals):
    """Persist a freshly built per-op executable. Store-if-absent: the
    export (a re-trace) is only paid when the artifact does not already
    exist — a warm process that loaded the artifact never re-exports."""
    digest = op_key_digest(key)
    if digest is None or has_artifact("op", digest):
        return
    in_specs = tuple(_spec_of(v) for v in vals)
    try:
        if diff_idx is None:
            blobs = [export_bytes(jax.jit(_wrap_check_primal(fn, check)),
                                  in_specs)]
        else:
            blobs = _export_primal_bwd(fn, diff_idx, check, in_specs, name)
    except Exception as e:
        _STATS.store_failures += 1
        _EVENTS.emit("aot.store", name,
                     detail={"kind": "op", "failed": repr(e)[:200]})
        return
    store_artifact("op", digest, name, blobs,
                   meta={"grad": diff_idx is not None, "check": check})


def load_op(key, name, fn, diff_idx, check):
    """Restore a per-op executable with the exact `_cached_call` value
    contract, or None. The returned object drops into the dispatch LRU
    like a live jitted executable."""
    digest = op_key_digest(key)
    art = load_artifact("op", digest, name)
    if art is None:
        return None
    path = _artifact_path("op", digest)
    try:
        if diff_idx is None:
            impl = _deserialize_callable(art["blobs"][0])
        else:
            primal = _deserialize_callable(art["blobs"][0])
            bwd = _deserialize_callable(art["blobs"][1])
    except Exception as e:
        _STATS.corrupt += 1
        _EVENTS.emit("aot.corrupt", name, reason="artifact_corrupt",
                     detail={"kind": "op", "stage": "deserialize",
                             "error": repr(e)[:200]})
        _quarantine(path)
        return None
    _STATS.hits += 1
    _EVENTS.emit("aot.hit", name, detail={"kind": "op",
                                          "grad": diff_idx is not None,
                                          "digest": digest[:12]})
    from .dispatch import _build_fwd, _build_fwd_vjp
    if diff_idx is None:
        return _Healing(impl, lambda: _build_fwd(name, fn, check), path,
                        name)
    return _AotGradExe(primal, bwd, fn, diff_idx, check, name, path,
                       lambda: _build_fwd_vjp(name, fn, diff_idx, check))


# ---------------------------------------------------------------------------
# chain tier (ops/fusion.py hooks)
# ---------------------------------------------------------------------------

def chain_digest(chain):
    """Digest of a chain's signature — per-op canonical keys + wiring —
    memoized on the Chain (None = opted out)."""
    if chain.aot_digest != 0:
        return chain.aot_digest
    try:
        canonical = ("chain",
                     tuple((op_key_canonical(op.key), op.wiring,
                            op.diff_mask, op.num_outputs)
                           for op in chain.ops),
                     chain.grad_mode, chain.check)
        chain.aot_digest = _digest_of(canonical)
    except (Undigestable, ValueError, TypeError):
        chain.aot_digest = None
    return chain.aot_digest


def store_chain(chain, ext_vals):
    digest = chain_digest(chain)
    if digest is None or has_artifact("chain", digest):
        return
    in_specs = tuple(_spec_of(v) for v in ext_vals)
    run = chain.pure_fn
    try:
        if chain.grad_mode:
            blobs = _export_primal_bwd(run, chain.diff_ext_idx,
                                       chain.check, in_specs, chain.label)
        else:
            blobs = [export_bytes(
                jax.jit(_wrap_check_primal(run, chain.check)), in_specs)]
    except Exception as e:
        _STATS.store_failures += 1
        _EVENTS.emit("aot.store", chain.label,
                     detail={"kind": "chain", "failed": repr(e)[:200]})
        return
    store_artifact("chain", digest, chain.label, blobs,
                   meta={"ops": len(chain.ops), "grad": chain.grad_mode,
                         "check": chain.check})


def load_chain(chain, grad):
    """Restore a chain executable in the `_build_chain_fwd[_vjp]` call
    contract, or None. The variant (fwd vs fwd+vjp) rides the same
    artifact: grad chains store the primal+backward pair, and the
    forward-only variant just uses the primal program."""
    digest = chain_digest(chain)
    art = load_artifact("chain", digest, chain.label)
    if art is None:
        return None
    path = _artifact_path("chain", digest)
    try:
        primal = _deserialize_callable(art["blobs"][0])
        bwd = _deserialize_callable(art["blobs"][1]) \
            if grad and len(art["blobs"]) > 1 else None
    except Exception as e:
        _STATS.corrupt += 1
        _EVENTS.emit("aot.corrupt", chain.label,
                     reason="artifact_corrupt",
                     detail={"kind": "chain", "stage": "deserialize",
                             "error": repr(e)[:200]})
        _quarantine(path)
        return None
    if grad and bwd is None:
        return None          # stored forward-only, caller wants the vjp
    _STATS.hits += 1
    _EVENTS.emit("aot.hit", chain.label,
                 detail={"kind": "chain", "grad": grad,
                         "digest": digest[:12]})
    from .fusion import _build_chain_fwd, _build_chain_fwd_vjp
    if not grad:
        return _Healing(primal, lambda: _build_chain_fwd(chain), path,
                        chain.label)
    return _AotGradExe(primal, bwd, chain.pure_fn, chain.diff_ext_idx,
                       chain.check, chain.label, path,
                       lambda: _build_chain_fwd_vjp(chain))


# ---------------------------------------------------------------------------
# whole-step tier (ops/step_fusion.py hooks)
# ---------------------------------------------------------------------------

def _canon_cycle_entries(sig):
    entries = []
    for e in sig:
        if e[0] == "op":
            # trailing components past the canonical five are stable
            # value tuples (hoisted-RNG stream marks): digest as-is
            entries.append(("op", op_key_canonical(e[1]), e[2], e[3],
                            e[4]) + tuple(e[5:]))
        elif e[0] == "bwd":
            entries.append(("bwd", e[1]))
        elif e[0] == "cg":
            entries.append(("cg",))
        elif e[0] == "scaler":
            entries.append(("scaler", _canon(e[2], 1)))
        elif e[0] == "step":
            entries.append(("step", len(e[2])))
        else:
            raise Undigestable(f"cycle entry {e[0]!r}")
    return tuple(entries)


def step_digest(sig, opt, updated):
    """Digest of a promoted-step identity: the cycle signature (op keys +
    wiring + backward/clear_grad/scaler/step events, process-local ids
    erased) plus every constant `_build` bakes into the traced program —
    optimizer type and hyper-param key, accumulator structure, clip/
    regularizer snapshots, parameter binding, donation flag. A canonical
    super-cycle signature (ops/step_fusion._super_sig) digests its ONE
    segment plus the event frame — k-independent, like the programs it
    addresses. Returns None when any component has no stable form (the
    step opts out)."""
    from .step_fusion import _snapshot_obj
    try:
        if sig and sig[0] == "super":
            _tag, cg_e, seg_entries, scaler_e, step_e = sig[:5]
            entries = ("super", _canon_cycle_entries(tuple(seg_entries)),
                       cg_e is not None,
                       None if scaler_e is None
                       else ("scaler", _canon(scaler_e[2], 1)),
                       ("step", len(step_e[2])))
            if len(sig) > 5:
                # ragged tail: the tail segment joins the digest so a
                # ragged program never aliases its uniform twin (the main
                # sub/update pair restores from the store; the tail sub
                # compiles live)
                entries += (("tail",
                             _canon_cycle_entries(tuple(sig[5]))),)
        else:
            entries = _canon_cycle_entries(sig)
        accs = tuple(sorted(getattr(opt, "_accumulators", {}).keys()))
        canonical = (
            "step", tuple(entries),
            ("params", tuple(p.name for p in updated),
             tuple(bool(getattr(p, "need_clip", True)) for p in updated),
             tuple(_canon(_snapshot_obj(getattr(p, "regularizer", None)),
                          1) for p in updated)),
            ("opt", type(opt).__qualname__,
             _canon(tuple(opt._extra_cache_key()), 1), accs),
            ("clip", _canon(_snapshot_obj(opt._grad_clip), 1)),
            ("reg", _canon(_snapshot_obj(opt.regularization), 1)),
            ("donate",
             bool(_FLAGS.get("FLAGS_eager_step_fusion_donate_params"))),
        )
        return _digest_of(canonical)
    except (Undigestable, ValueError, TypeError, AttributeError):
        return None


def has_step(digest) -> bool:
    return has_artifact("step", digest)


def store_step(program, args):
    """Persist the ONE fused whole-step executable right after its first
    successful fire (`args` are the concrete fire arguments — shapes are
    readable even off donated buffers). Skipped when the executable was
    itself restored from the store."""
    digest = program.aot_digest
    if digest is None or has_artifact("step", digest):
        return
    exe = program._exe
    if exe is None or isinstance(exe, _Healing):
        return
    try:
        specs = tuple(_specs_of(a) for a in args)
        blobs = [export_bytes(exe, specs)]
    except Exception as e:
        _STATS.store_failures += 1
        _EVENTS.emit("aot.store", program.label,
                     detail={"kind": "step", "failed": repr(e)[:200]})
        return
    store_artifact("step", digest, program.label, blobs,
                   meta={"ops": len(program.chain.ops),
                         "params": len(program.param_names),
                         "check": program.check,
                         "scaler": program.scaler_consts is not None,
                         "spmd": program.spmd_plan is not None})


def load_step(program, fallback, donate_argnums):
    """Restore the fused whole-step executable (healing; donation
    re-applied at the wrapper), or None. The artifact must match the live
    program's LOWERING: a plain-jit export (stored by a process whose
    probation demoted the mesh plan) cannot serve a shard_map caller —
    the arg conventions differ — so a spmd-ness mismatch is a miss."""
    want_spmd = program.spmd_plan is not None
    return load_callable(
        "step", program.aot_digest, program.label, fallback,
        donate_argnums,
        accept=lambda meta: bool(meta.get("spmd")) == want_spmd)


def store_super_step(program, sub_args, upd_args):
    """Persist a super-cycle program's executable PAIR — the micro-batch
    sub-executable and the boundary update executable — as one two-blob
    artifact, right after the first successful boundary fire. A restarting
    worker then replays its accumulation loop with zero fresh compiles at
    any k."""
    digest = program.aot_digest
    if digest is None or has_artifact("step", digest):
        return
    sub, upd = program._sub_exe, program._upd_exe
    if sub is None or upd is None \
            or isinstance(sub, _Healing) or isinstance(upd, _Healing):
        return
    try:
        blobs = [export_bytes(sub, tuple(_specs_of(a) for a in sub_args)),
                 export_bytes(upd, tuple(_specs_of(a) for a in upd_args))]
    except Exception as e:
        _STATS.store_failures += 1
        _EVENTS.emit("aot.store", program.label,
                     detail={"kind": "step", "super": True,
                             "failed": repr(e)[:200]})
        return
    store_artifact("step", digest, program.label, blobs,
                   meta={"super": True, "ops": len(program.chain.ops),
                         "params": len(program.param_names),
                         "check": program.check,
                         "scaler": program.scaler_consts is not None,
                         "spmd": program.spmd_plan is not None})


def load_super_step(program, sub_fallback, upd_fallback, upd_donate):
    """Restore the (sub, update) executable pair of a super-cycle
    program as healing callables, or (None, None)."""
    art = load_artifact("step", program.aot_digest, program.label)
    if art is None or len(art.get("blobs", ())) != 2 \
            or not (art.get("meta") or {}).get("super"):
        return None, None
    if bool((art.get("meta") or {}).get("spmd")) \
            != (program.spmd_plan is not None):
        # lowering mismatch (plain-jit pair vs shard_map caller or vice
        # versa): the arg conventions differ — a miss, not corruption
        _STATS.misses += 1
        _EVENTS.emit("aot.miss", program.label,
                     detail={"kind": "step",
                             "digest": program.aot_digest[:12],
                             "why": "lowering_mismatch"})
        return None, None
    path = _artifact_path("step", program.aot_digest)
    try:
        sub = _deserialize_callable(art["blobs"][0])
        upd = _deserialize_callable(art["blobs"][1], upd_donate)
    except Exception as e:
        _STATS.corrupt += 1
        _EVENTS.emit("aot.corrupt", program.label,
                     reason="artifact_corrupt",
                     detail={"kind": "step", "stage": "deserialize",
                             "error": repr(e)[:200]})
        _quarantine(path)
        return None, None
    _STATS.hits += 1
    _EVENTS.emit("aot.hit", program.label,
                 detail={"kind": "step", "digest": program.aot_digest[:12],
                         "super": True})
    return (_Healing(sub, sub_fallback, path, program.label),
            _Healing(upd, upd_fallback, path, program.label))
