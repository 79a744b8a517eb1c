"""Native runtime core: TCPStore, BoundedQueue, ThreadPool, host tracer.

Reference analogs: store/tcp_store.h TCPStore tests, workqueue tests
(new_executor/workqueue/workqueue_test.cc), host_event_recorder. Multi-process
store rendezvous follows the TestDistBase pattern (test_dist_base.py:901):
subprocess ranks on localhost.
"""
import queue
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.core import (TCPStore, ThreadPool, BoundedQueue,
                             native_available, host_tracer, parallel_collate)

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native core not built (no g++)")


def test_store_set_get_add():
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    client = TCPStore("127.0.0.1", master.port, is_master=False, world_size=1)
    master.set("alpha", b"value-1")
    assert client.get("alpha") == b"value-1"
    assert client.add("counter", 3) == 3
    assert master.add("counter", 4) == 7
    with pytest.raises(KeyError):
        client.get("missing", wait=False)
    assert client.delete_key("alpha")


def test_store_wait_blocks_until_set():
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    client = TCPStore("127.0.0.1", master.port, is_master=False, world_size=1)

    def later():
        time.sleep(0.15)
        master.set("slow", b"done")
    t = threading.Thread(target=later)
    t.start()
    t0 = time.monotonic()
    client.wait(["slow"])
    assert time.monotonic() - t0 >= 0.1
    t.join()
    with pytest.raises(TimeoutError):
        client.wait(["never"], timeout=0.1)


def test_store_barrier_two_parties():
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    client = TCPStore("127.0.0.1", master.port, is_master=False, world_size=2)
    order = []

    def party(store, name):
        store.barrier("sync")
        order.append(name)

    t = threading.Thread(target=party, args=(client, "client"))
    t.start()
    time.sleep(0.05)
    assert not order          # client must be blocked until master arrives
    party(master, "master")
    t.join()
    assert sorted(order) == ["client", "master"]


_WORKER = r"""
import importlib.util
import os
import sys

# load paddle_tpu.core standalone (skip the full framework import: jax
# bring-up per subprocess would dominate the test)
core_dir = os.path.join(sys.argv[4], "paddle_tpu", "core")
spec = importlib.util.spec_from_file_location(
    "ptcore", os.path.join(core_dir, "__init__.py"),
    submodule_search_locations=[core_dir])
ptcore = importlib.util.module_from_spec(spec)
sys.modules["ptcore"] = ptcore
spec.loader.exec_module(ptcore)
TCPStore = ptcore.TCPStore
rank, world, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
store = TCPStore("127.0.0.1", port, is_master=False, world_size=world)
store.set(f"rank/{rank}", str(rank).encode())
store.wait([f"rank/{r}" for r in range(world)])
vals = sorted(int(store.get(f"rank/{r}")) for r in range(world))
assert vals == list(range(world)), vals
store.barrier("exit")
print("RANK_OK", rank)
"""


def test_store_multiprocess_rendezvous(tmp_path):
    """Three subprocess ranks rendezvous through one master store."""
    world = 3
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=world + 1)
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    import os
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(world), str(master.port),
         repo_root],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(world)]
    master.barrier("exit")
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=60)
        assert p.returncode == 0, out
        assert f"RANK_OK {r}" in out


def test_bounded_queue_blocking_and_close():
    q = BoundedQueue(2)
    assert q.is_native
    q.push("a")
    q.push("b")
    with pytest.raises(queue.Full):
        q.push("c", timeout=0.05)
    assert q.pop() == "a"
    assert q.pop() == "b"
    with pytest.raises(queue.Empty):
        q.pop(timeout=0.05)
    q.push("tail")
    q.close()
    assert q.pop() == "tail"       # close drains remaining items first
    with pytest.raises(StopIteration):
        q.pop()


def test_bounded_queue_producer_consumer():
    q = BoundedQueue(4)
    n = 200
    got = []

    def producer():
        for i in range(n):
            q.push(i)
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    while True:
        try:
            got.append(q.pop())
        except StopIteration:
            break
    t.join()
    assert got == list(range(n))


def test_parallel_collate_matches_stack():
    arrays = [np.random.default_rng(i).standard_normal(
        (128, 512)).astype("float32") for i in range(16)]
    np.testing.assert_array_equal(parallel_collate(arrays), np.stack(arrays))
    small = [np.arange(4, dtype=np.int32) + i for i in range(3)]
    np.testing.assert_array_equal(parallel_collate(small), np.stack(small))


def test_host_tracer_spans_roundtrip():
    host_tracer.enable(True)
    try:
        t0 = host_tracer.now_ns()
        t1 = host_tracer.now_ns()
        host_tracer.span("unit_event", t0, t1)
        events = host_tracer.harvest()
    finally:
        host_tracer.enable(False)
    names = [e[0] for e in events]
    assert "unit_event" in names
    ev = events[names.index("unit_event")]
    assert ev[2] >= ev[1]


def test_profiler_uses_native_tracer():
    import paddle_tpu.profiler as profiler
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    with profiler.RecordEvent("traced_region"):
        time.sleep(0.01)
    prof.stop()
    names = [e["name"] for e in prof._events]
    assert "traced_region" in names


def test_host_tracer_worker_thread_events_visible():
    """Events recorded on other live threads must appear in harvest
    (reference: host_event_recorder harvests all thread buffers)."""
    host_tracer.enable(True)
    try:
        def worker():
            t0 = host_tracer.now_ns()
            host_tracer.span("worker_span", t0, host_tracer.now_ns())
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        # also record from a thread that stays alive during harvest
        alive_done = threading.Event()
        release = threading.Event()

        def long_lived():
            t0 = host_tracer.now_ns()
            host_tracer.span("live_span", t0, host_tracer.now_ns())
            alive_done.set()
            release.wait(5)
        t2 = threading.Thread(target=long_lived)
        t2.start()
        alive_done.wait(5)
        names = [e[0] for e in host_tracer.harvest()]
        release.set()
        t2.join()
    finally:
        host_tracer.enable(False)
    assert "worker_span" in names
    assert "live_span" in names


def test_dataloader_early_abandon_no_crash():
    """Breaking out of a DataLoader loop with a full prefetch queue must not
    crash when the iterator is dropped (producer joined before queue free)."""
    import gc
    from paddle_tpu.io import DataLoader, Dataset

    class Big(Dataset):
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return np.zeros((64, 64), np.float32)

    import threading
    import weakref
    before = threading.active_count()
    for _ in range(5):
        it = iter(DataLoader(Big(), batch_size=4))
        next(it)
        gone, producer = weakref.ref(it), it._thread
        del it          # abandon with producer likely blocked on full queue
        gc.collect()
        # the producer holds the queue, not the iterator: the iterator is
        # collected, its queue closed, and the producer ends with its
        # batches released (it used to block for good, holding them)
        assert gone() is None
        producer.join(timeout=5.0)
        assert not producer.is_alive()
    assert threading.active_count() <= before
