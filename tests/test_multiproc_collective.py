"""Multi-process collective harness: REAL processes, not a virtual mesh.

Reference analog: unittests/test_dist_base.py:901 (TestDistBase Popens
trainer subprocesses at :1150 with env-crafted endpoints) and the
per-primitive scripts under unittests/collective/. Here the ranks are
tests/multiproc_runner.py processes: native-TCPStore rendezvous →
jax.distributed.initialize → every eager collective asserted cross-process.
"""
import os
import socket
import subprocess
import sys

import pytest

_RUNNER = os.path.join(os.path.dirname(__file__), "multiproc_runner.py")


def _cpu_multiproc_collectives_supported():
    """Capability probe: can the CPU backend run CROSS-PROCESS collectives?

    jax 0.4.x's CPU client has no cross-process collective implementation
    (the Gloo-backed CPU collectives landed in the 0.5 line), so the ranks
    rendezvous fine and then hang/fail inside the first psum. Probe the
    version instead of burning the 240 s harness timeout per test.
    """
    import jax
    try:
        major, minor = (int(v) for v in jax.__version__.split(".")[:2])
    except ValueError:
        return True          # unparseable future scheme: assume capable
    return (major, minor) >= (0, 5)


pytestmark = pytest.mark.skipif(
    not _cpu_multiproc_collectives_supported(),
    reason="jax CPU backend lacks multiprocess collectives before 0.5.x")


def _free_port():
    """A port P with P and P+1 both currently bindable (the coordinator
    deterministically uses store port + 1)."""
    for _ in range(32):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        try:
            with socket.socket() as s2:
                s2.bind(("127.0.0.1", p + 1))
            return p
        except OSError:
            continue
    raise RuntimeError("no consecutive free port pair found")


def _launch(world_size, timeout=240):
    port = _free_port()
    procs = []
    for rank in range(world_size):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world_size),
            "PADDLE_MASTER": f"127.0.0.1:{port}",
            # one CPU device per rank — the children force the cpu platform
            # in-process
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
        repo_root = os.path.dirname(os.path.dirname(_RUNNER))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, _RUNNER], env=env, cwd=repo_root,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, outs


def test_two_rank_collectives():
    procs, outs = _launch(2)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"RANK {rank} OK" in out, f"rank {rank} output:\n{out}"


def test_four_rank_collectives():
    procs, outs = _launch(4)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"RANK {rank} OK" in out, f"rank {rank} output:\n{out}"
