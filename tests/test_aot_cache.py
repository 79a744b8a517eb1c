"""Persistent AOT executable cache (PR 9, ops/aot_cache.py).

Covers the warm-start contract end to end:
  * stable content addressing — structurally identical op keys digest
    identically (code objects by bytecode, fns by module:qualname,
    process-local ids erased); undigestable components opt out cleanly;
  * in-process warm round trip — with a populated store, clearing every
    compiled cache and re-running the same loop reloads per-op AND
    whole-step executables with ZERO fresh traces, and the step promotes
    at the FIRST clean boundary (`warm_start` promotion, min_count
    bypassed) — the restart path minus the process boundary;
  * durability — a corrupted artifact (bit flip or truncation) is
    detected, quarantined as *.corrupt, attributed `artifact_corrupt`,
    and transparently recompiled with identical numerics; version skew
    (a different environment fingerprint) is reported and never
    deserialized;
  * concurrent writers — two subprocesses racing `store()` on the SAME
    keys and on disjoint keys leave only complete, loadable artifacts
    (atomic tmp+fsync+rename; content addressing makes last-writer-wins
    correct);
  * size/age-bounded eviction + the `fusion_doctor --cache [--gc]`
    subcommand;
  * the serving decode step round-trips too: a second engine over the
    same model deserializes the decode program (decode_compiles == 0)
    and stays token-identical;
  * warm start across the process boundary: a fresh subprocess against
    a warm store reaches a promoted fused step with zero compile events
    (tests/fixtures/aot_child.py).
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops import aot_cache
from paddle_tpu.ops.dispatch import clear_dispatch_cache
from paddle_tpu.profiler import (aot_cache_stats, chain_fusion_stats,
                                 dispatch_cache_stats,
                                 reset_aot_cache_stats,
                                 reset_chain_fusion_stats,
                                 reset_dispatch_cache_stats,
                                 reset_step_fusion_stats,
                                 step_fusion_stats)
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
_TOOLS = os.path.join(_ROOT, "tools")

_DEFAULT_FLAGS = {
    "FLAGS_aot_cache": False,
    "FLAGS_aot_cache_dir": "",
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 512,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
    "FLAGS_profiler_events": False,
}


@pytest.fixture(autouse=True)
def _fresh():
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    clear_fusion_events()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    reset_aot_cache_stats()
    yield
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    clear_fusion_events()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    reset_aot_cache_stats()


def _arm(tmp_path):
    set_flags({"FLAGS_aot_cache": True,
               "FLAGS_aot_cache_dir": str(tmp_path),
               "FLAGS_profiler_events": True})


def _make_state(seed=0):
    rng = np.random.default_rng(seed)
    x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32),
                         stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(8).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w, b])
    return x, w, b, opt


def _loop(state, n):
    x, w, b, opt = state
    opt.clear_grad()
    losses = []
    for _ in range(n):
        loss = F.gelu(paddle.add(paddle.matmul(x, w), b)).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


def _clear_compiled():
    """Drop every in-process compiled executable (dispatch LRU, chains,
    promoted steps) WITHOUT touching the on-disk store — the in-process
    analog of a process restart."""
    clear_dispatch_cache()
    clear_fusion_events()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    reset_aot_cache_stats()


def _events(cat):
    return [e for e in fusion_events() if e["cat"] == cat]


# ---------------------------------------------------------------------------
# content addressing
# ---------------------------------------------------------------------------

class TestDigests:
    def test_structurally_equal_keys_digest_identically(self):
        def make_key(scale):
            fn = lambda a, b: a * scale + b          # noqa: E731
            from paddle_tpu.ops.dispatch import _fn_token
            ftok = _fn_token(fn)
            avals = (((4, 8), np.dtype(np.float32), False),)
            return ("mul_add", ftok, avals, (True,), None, (None, 0),
                    False)
        # two closures from the same code with the same cell value are one
        # artifact; a different constant is a different artifact; the
        # registry GENERATION (process-local) must not matter
        d1 = aot_cache.op_key_digest(make_key(2.0))
        d2 = aot_cache.op_key_digest(make_key(2.0))
        d3 = aot_cache.op_key_digest(make_key(3.0))
        assert d1 == d2 and d1 is not None
        assert d3 != d1
        k = make_key(2.0)
        bumped = k[:5] + ((None, 7),) + k[5 + 1:]
        assert aot_cache.op_key_digest(bumped) == d1

    def test_undigestable_key_opts_out(self):
        key = ("weird", object(), (), None, None, (None, 0), False)
        assert aot_cache.op_key_digest(key) is None

    def test_fingerprint_changes_filename(self, tmp_path):
        _arm(tmp_path)
        fp = aot_cache.fingerprint_digest()
        assert fp in os.path.basename(
            aot_cache._artifact_path("op", "ab" * 20))


# ---------------------------------------------------------------------------
# warm round trip (the restart path minus the process boundary)
# ---------------------------------------------------------------------------

class TestWarmRoundTrip:
    def test_zero_retrace_warm_start_with_first_boundary_promotion(
            self, tmp_path):
        _arm(tmp_path)
        state = _make_state()
        _loop(state, 8)
        assert step_fusion_stats()["steps_promoted"] == 1
        assert aot_cache_stats()["stores"] >= 5   # 4 ops + step (+ chain)
        kinds = {os.path.basename(p).split("-")[0]
                 for p in glob.glob(str(tmp_path / "*.aot"))}
        assert {"op", "step"} <= kinds

        # "restart": same live objects, every compiled cache dropped
        _clear_compiled()
        _loop(state, 3)
        d, s, a = (dispatch_cache_stats(), step_fusion_stats(),
                   aot_cache_stats())
        assert d["retraces"] == 0, "warm per-op path traced"
        assert s["retraces"] == 0, "warm whole-step path traced"
        assert chain_fusion_stats()["retraces"] == 0
        assert a["hits"] >= 5 and a["misses"] == 0
        # promoted at the FIRST boundary (min_count 4 bypassed), fired on
        # the second cycle
        assert s["steps_promoted"] == 1 and s["fused_steps"] >= 2
        promo = _events("step.promote")
        assert promo and promo[0]["detail"]["warm_start"] is True
        assert not _events("dispatch.retrace")
        assert not _events("chain.compile")

    def test_warm_trajectory_matches_cold(self, tmp_path):
        _arm(tmp_path)
        ref = _loop(_make_state(), 8)
        _clear_compiled()
        paddle.seed(0)
        warm = _loop(_make_state(), 8)
        # fresh params re-derive the same trajectory through restored
        # executables; the restored ONE-program step may differ from the
        # cold build in the last ULP (the PR 3 layout contract)
        np.testing.assert_allclose(ref, warm, rtol=0, atol=1e-5)

    def test_disabled_flag_means_no_store_io(self, tmp_path):
        set_flags({"FLAGS_aot_cache_dir": str(tmp_path)})
        _loop(_make_state(), 6)
        assert not os.path.exists(str(tmp_path)) \
            or not os.listdir(str(tmp_path))
        assert aot_cache_stats()["stores"] == 0


# ---------------------------------------------------------------------------
# durability: corruption, torn writes, version skew
# ---------------------------------------------------------------------------

class TestDurability:
    def _populate(self, tmp_path, seed=0):
        _arm(tmp_path)
        ref = _loop(_make_state(seed), 8)
        return ref

    def test_bitflip_quarantines_and_recompiles(self, tmp_path):
        ref = self._populate(tmp_path)
        for p in glob.glob(str(tmp_path / "*.aot")):
            data = bytearray(open(p, "rb").read())
            data[len(data) // 2] ^= 0xFF
            open(p, "wb").write(data)
        _clear_compiled()
        paddle.seed(0)
        res = _loop(_make_state(), 8)
        a = aot_cache_stats()
        assert a["corrupt"] >= 4 and a["hits"] == 0
        assert glob.glob(str(tmp_path / "*.corrupt"))
        ev = _events("aot.corrupt")
        assert ev and all(e["reason"] == "artifact_corrupt" for e in ev)
        np.testing.assert_allclose(ref, res, rtol=0, atol=1e-5)
        # the recompiled executables re-stored fresh artifacts
        assert aot_cache_stats()["stores"] >= 4

    def test_truncated_artifact_is_corrupt_not_fatal(self, tmp_path):
        self._populate(tmp_path)
        victim = sorted(glob.glob(str(tmp_path / "op-*.aot")))[0]
        data = open(victim, "rb").read()
        open(victim, "wb").write(data[:len(data) // 2])   # torn write
        _clear_compiled()
        paddle.seed(0)
        _loop(_make_state(), 4)
        assert aot_cache_stats()["corrupt"] >= 1
        assert os.path.exists(victim + ".corrupt")

    def test_version_skew_reported_never_deserialized(self, tmp_path):
        self._populate(tmp_path)
        # a worker on a different jax: same key digests, different
        # fingerprint -> exact filename misses, the foreign artifact is
        # reported as skew and left for its own environment
        old_fp = dict(aot_cache.env_fingerprint())
        try:
            aot_cache._fp_cache = {**old_fp, "jax": "99.99.99"}
            aot_cache._fp_digest_cache = None      # re-derive the digest
            aot_cache._skew_scan = (0.0, None, frozenset())
            _clear_compiled()
            paddle.seed(0)
            _loop(_make_state(), 4)
            a = aot_cache_stats()
            assert a["hits"] == 0 and a["version_skew"] >= 1
            ev = _events("aot.version_skew")
            assert ev and all(e["reason"] == "version_skew" for e in ev)
        finally:
            aot_cache._fp_cache = old_fp
            aot_cache._fp_digest_cache = None
            aot_cache._skew_scan = (0.0, None, frozenset())
        # the original artifacts are untouched (not quarantined)
        assert not glob.glob(str(tmp_path / "*.corrupt"))


# ---------------------------------------------------------------------------
# eviction + doctor CLI
# ---------------------------------------------------------------------------

class TestEvictionAndDoctor:
    def test_size_bounded_eviction_oldest_first(self, tmp_path):
        _arm(tmp_path)
        for i in range(4):
            aot_cache.store_artifact("op", f"{i:02d}" * 20, f"fake{i}",
                                     [b"x" * 1024])
            os.utime(aot_cache._artifact_path("op", f"{i:02d}" * 20),
                     (1000 + i, 1000 + i))
        sizes = [os.path.getsize(p)
                 for p in glob.glob(str(tmp_path / "*.aot"))]
        budget = sum(sizes) - 2 * max(sizes) + 1   # forces out exactly 2
        removed = aot_cache.gc_store(str(tmp_path), max_bytes=budget,
                                     max_age_s=0)
        assert len(removed) == 2
        left = {os.path.basename(p).split("-")[1]
                for p in glob.glob(str(tmp_path / "*.aot"))}
        assert left == {"02" * 20, "03" * 20}   # oldest two evicted
        assert aot_cache_stats()["evictions"] == 2

    def test_age_bound_quarantine_and_stale_tmp(self, tmp_path):
        _arm(tmp_path)
        aot_cache.store_artifact("op", "aa" * 20, "old", [b"x"])
        p = aot_cache._artifact_path("op", "aa" * 20)
        os.utime(p, (1, 1))
        open(str(tmp_path / "op-dead-beef.aot.corrupt"), "wb").write(b"?")
        stale_tmp = str(tmp_path / "op-dead-beef.aot.tmp.123")
        open(stale_tmp, "wb").write(b"?")
        os.utime(stale_tmp, (1, 1))
        fresh_tmp = str(tmp_path / "op-cafe-f00d.aot.tmp.456")
        open(fresh_tmp, "wb").write(b"?")      # an in-flight writer
        removed = aot_cache.gc_store(str(tmp_path), max_bytes=0,
                                     max_age_s=3600)
        # over-age artifact + kill-9'd writer's stale tmp go; the FRESH
        # quarantine survives the automatic sweep (the doctor must still
        # be able to list it), as does the in-flight tmp
        assert sorted(removed) == ["op-aaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaa"
                                   "aaaaaaaa-"
                                   + aot_cache.fingerprint_digest()
                                   + ".aot",
                                   "op-dead-beef.aot.tmp.123"]
        assert os.path.exists(fresh_tmp)
        # the explicit --gc path purges quarantines immediately
        removed = aot_cache.gc_store(str(tmp_path), max_bytes=0,
                                     max_age_s=3600,
                                     purge_quarantine=True)
        assert removed == ["op-dead-beef.aot.corrupt"]

    def test_doctor_cache_subcommand(self, tmp_path, capsys):
        _arm(tmp_path)
        _loop(_make_state(), 6)
        victim = sorted(glob.glob(str(tmp_path / "op-*.aot")))[0]
        open(victim, "ab").write(b"junk")        # break its trailer
        sys.path.insert(0, _TOOLS)
        try:
            import fusion_doctor
            rc = fusion_doctor.main(["--cache", "--cache-dir",
                                     str(tmp_path)])
            out = capsys.readouterr().out
            assert rc == 0
            assert "AOT executable store" in out
            assert "CORRUPT" in out and " ok" in out
            rc = fusion_doctor.main(["--cache", "--cache-dir",
                                     str(tmp_path), "--gc", "--json"])
            rep = json.loads(capsys.readouterr().out)
            assert rc == 0
        finally:
            sys.path.remove(_TOOLS)
        # --gc leaves only intact artifacts behind
        assert all(not e["corrupt"] and not e["quarantined"]
                   for e in rep["entries"])


# ---------------------------------------------------------------------------
# concurrent multi-process writers (satellite)
# ---------------------------------------------------------------------------

_CHILD_SRC = r"""
import os, sys
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags

set_flags({"FLAGS_aot_cache": True,
           "FLAGS_aot_cache_dir": sys.argv[1],
           "FLAGS_eager_chain_fusion_min_count": 3,
           "FLAGS_eager_step_fusion_min_count": 4})
dim = int(sys.argv[2])
paddle.seed(0)
rng = np.random.default_rng(0)
x = paddle.to_tensor(rng.standard_normal((4, dim)).astype(np.float32))
w = paddle.to_tensor(rng.standard_normal((dim, dim)).astype(np.float32),
                     stop_gradient=False)
b = paddle.to_tensor(rng.standard_normal(dim).astype(np.float32),
                     stop_gradient=False)
opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w, b])
opt.clear_grad()
for _ in range(7):
    loss = F.gelu(paddle.add(paddle.matmul(x, w), b)).sum()
    loss.backward(); opt.step(); opt.clear_grad()
print("DONE", float(loss))
"""


class TestConcurrentWriters:
    def _spawn(self, store, dim):
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _ROOT}
        return subprocess.Popen(
            [sys.executable, "-c", _CHILD_SRC, str(store), str(dim)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)

    def test_same_and_disjoint_key_races(self, tmp_path):
        store = tmp_path / "store"
        # two writers on the SAME keys (dim 8) + one on disjoint keys
        # (dim 16), all racing the same directory
        procs = [self._spawn(store, 8), self._spawn(store, 8),
                 self._spawn(store, 16)]
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-800:]
            assert "DONE" in out
        # no torn files: every artifact verifies (CRC + envelope), no
        # quarantines, and both key families are present exactly once
        entries = aot_cache.store_entries(str(store), verify=True)
        assert entries
        assert all(not e["corrupt"] and not e["quarantined"]
                   for e in entries)
        step_arts = [e for e in entries if e["kind"] == "step"]
        assert len(step_arts) == 2   # one per dim — no lost entries
        # ...and a warm reader actually loads the per-op artifacts with
        # zero traces. (The STEP artifact only matches from a fresh
        # process: its digest includes the auto-generated parameter
        # names, which this long-lived pytest process has already
        # advanced past — the chaos warm_restart scenario proves the
        # cross-process step path.)
        _arm(store)
        paddle.seed(0)
        _loop(_make_state_dim(8), 3)
        assert aot_cache_stats()["hits"] >= 4
        assert dispatch_cache_stats()["retraces"] == 0


class TestSharedStoreFleet:
    """Cross-host shared-store contracts the elastic fabric leans on
    (distributed/fabric.py): store-if-absent races on one key converge
    to a single loadable artifact, every artifact records which host
    exported it, and a stored lowering that does not match the live
    program's calling convention is a MISS — never a quarantine of a
    healthy artifact (the plain-jit vs shard_map aliasing a probation
    demotion can create under one step digest)."""

    @staticmethod
    def _blob():
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda a: a * 2.0)
        return f, aot_cache.export_bytes(
            f, (jax.ShapeDtypeStruct((4,), jnp.float32),))

    def test_same_key_race_converges_with_host_provenance(self, tmp_path):
        import socket
        import threading
        _arm(tmp_path)
        _, blob = self._blob()
        digest = "f" * 40
        errors, results = [], []

        def writer():
            try:
                results.append(aot_cache.store_artifact(
                    "step", digest, "race", [blob], meta={"spmd": False}))
            except Exception as e:          # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=writer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # no raise, ever. A loser of the tmp-file race reports False
        # (accounted store_failure) — it must never tear the artifact
        assert not errors
        assert any(results)
        entries = [e for e in aot_cache.store_entries(str(tmp_path))
                   if e["kind"] == "step"]
        assert len(entries) == 1            # same key -> ONE file
        e = entries[0]
        assert not e["corrupt"] and not e["quarantined"]
        assert e["host"] == socket.gethostname()
        art = aot_cache.load_artifact("step", digest, "race")
        assert bytes(art["blobs"][0]) == bytes(blob)
        assert art["host"] == socket.gethostname()

    def test_lowering_mismatch_is_miss_not_quarantine(self, tmp_path):
        _arm(tmp_path)
        f, blob = self._blob()
        digest = "e" * 40
        assert aot_cache.store_artifact("step", digest, "mm", [blob],
                                        meta={"spmd": False})
        m0 = aot_cache_stats()["misses"]
        got = aot_cache.load_callable(
            "step", digest, "mm", fallback=lambda: f,
            accept=lambda meta: bool(meta.get("spmd")))
        assert got is None
        assert aot_cache_stats()["misses"] == m0 + 1
        assert aot_cache_stats()["corrupt"] == 0
        misses = [ev for ev in _events("aot.miss")
                  if ev["detail"].get("why") == "lowering_mismatch"]
        assert misses and misses[-1]["detail"]["digest"] == digest[:12]
        # the artifact survives untouched and a MATCHING caller loads it
        entries = [e for e in aot_cache.store_entries(str(tmp_path))
                   if e["kind"] == "step"]
        assert len(entries) == 1 and not entries[0]["quarantined"]
        got2 = aot_cache.load_callable(
            "step", digest, "mm", fallback=lambda: f,
            accept=lambda meta: not meta.get("spmd"))
        assert got2 is not None
        out = got2(np.full((4,), 3.0, np.float32))
        assert np.allclose(np.asarray(out), 6.0)


def _make_state_dim(dim):
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((4, dim)).astype(np.float32))
    w = paddle.to_tensor(
        rng.standard_normal((dim, dim)).astype(np.float32),
        stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal(dim).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w, b])
    return x, w, b, opt


# ---------------------------------------------------------------------------
# serving decode warm start
# ---------------------------------------------------------------------------

class TestServingDecode:
    def test_decode_round_trip_token_identical(self, tmp_path):
        from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving import LLMEngine

        _arm(tmp_path)
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=16,
                        num_hidden_layers=2, num_attention_heads=2,
                        intermediate_size=32,
                        max_position_embeddings=32,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        model.eval()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, n).tolist() for n in (5, 7)]

        eng_a = LLMEngine(model, max_batch_size=2, block_size=4)
        ref = eng_a.generate(prompts, max_new_tokens=6)
        # exactly ONE trace even while storing: jax.export reuses the
        # jit's cached trace for the already-seen avals
        assert eng_a.stats()["decode_compiles"] == 1
        assert any(os.path.basename(p).startswith("decode-")
                   for p in glob.glob(str(tmp_path / "*.aot")))

        reset_aot_cache_stats()
        eng_b = LLMEngine(model, max_batch_size=2, block_size=4)
        out = eng_b.generate(prompts, max_new_tokens=6)
        assert eng_b.stats()["decode_compiles"] == 0, \
            "warm engine traced decode"
        assert aot_cache_stats()["hits"] >= 1
        assert out == ref


# ---------------------------------------------------------------------------
# warm start across the process boundary
# ---------------------------------------------------------------------------

def test_warm_start_subprocess_compiles_nothing(tmp_path):
    """A fresh subprocess against a warm store must fire a promoted
    fused step with ZERO compile activity: every executable it needs is
    a store hit. (How much sooner it fires is a `setup_s` reading on the
    chip, not a CPU clock's.)"""
    child = os.path.join(_ROOT, "tests", "fixtures", "aot_child.py")
    store = str(tmp_path / "store")

    def run(tag):
        out = str(tmp_path / f"{tag}.json")
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _ROOT}
        r = subprocess.run(
            [sys.executable, child, store, out], capture_output=True,
            text=True, timeout=300, env=env)
        assert r.returncode == 0, r.stderr[-800:]
        with open(out) as f:
            return json.load(f)

    cold = run("cold")
    assert cold["fused_steps"] > 0 and cold["aot"]["stores"] > 0
    warm = run("warm")
    assert warm["fused_steps"] > 0
    assert warm["dispatch_retraces"] == 0
    assert warm["chain_retraces"] == 0
    assert warm["step_retraces"] == 0
    assert warm["aot"]["hits"] >= 5 and warm["aot"]["misses"] == 0
