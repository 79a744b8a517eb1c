"""DataLoader with threaded prefetch and multiprocess workers.

Reference analog: python/paddle/fluid/reader.py:312 (DataLoader),
fluid/dataloader/dataloader_iter.py (_DataLoaderIterMultiProcess: index
queue -> worker subprocesses -> reorder-by-batch-index), and the C++
double-buffering reader (operators/reader/buffered_reader.cc).

TPU-first: with num_workers > 0 batches are assembled in worker PROCESSES
started via a FORKSERVER (numpy-only in the children — a worker must never
touch the parent's initialized XLA runtime, and forking the multithreaded
JAX parent directly is a deadlock hazard the reference avoids with
spawn-capable worker plumbing), reordered by batch index in the parent, and
staged through a bounded prefetch queue so host input processing overlaps
device compute. Device transfer happens lazily on first use (jnp.asarray),
which XLA pipelines.
"""
from __future__ import annotations

import itertools
import multiprocessing
import queue
import threading
from time import monotonic as _monotonic

import numpy as np

from ..framework.core import Tensor
from .dataset import IterableDataset
from .sampler import BatchSampler

__all__ = ["DataLoader", "default_collate_fn", "get_worker_info", "WorkerInfo"]


def _np_collate(batch):
    """Numpy-only collation for worker processes (no jax in forked
    children)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        # unwrap to host numpy — a forked child must not run jax ops, but
        # np.asarray on an existing device buffer is a read
        batch = [np.asarray(b._value) for b in batch]
        sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        return tuple(_np_collate(list(items)) for items in zip(*batch))
    if isinstance(sample, dict):
        return {k: _np_collate([b[k] for b in batch]) for k in sample}
    return batch


def _to_tensors(data):
    if isinstance(data, np.ndarray):
        return Tensor(data)
    if isinstance(data, tuple):
        return tuple(_to_tensors(d) for d in data)
    if isinstance(data, dict):
        return {k: _to_tensors(v) for k, v in data.items()}
    return data


def _worker_loop(dataset, task_q, result_q, worker_id, worker_init_fn,
                 raw_samples, num_workers=0, base_seed=0):
    """Body of one worker subprocess (reference:
    dataloader_iter.py _worker_loop). Pulls (batch_idx, indices), pushes
    (batch_idx, payload) — numpy only."""
    global _worker_info
    # per-worker distinct seed (reference: base_seed + worker_id), so
    # random augmentations differ across workers but are reproducible for
    # a given worker index (base_seed derives from the framework seed, not
    # time/pid)
    seed = (base_seed + worker_id) % (2 ** 31)
    _worker_info = WorkerInfo(worker_id, num_workers, dataset, seed=seed)
    np.random.seed(seed)
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        task = task_q.get()
        if task is None:
            return
        bidx, indices = task
        try:
            samples = [dataset[i] for i in indices]
            payload = samples if raw_samples else _np_collate(samples)
            result_q.put((bidx, payload, None))
        except BaseException as e:       # ship the error to the parent
            result_q.put((bidx, None, f"{type(e).__name__}: {e}"))


_WORKER_CTX = None
# monotonic epoch counter feeding per-producer base seeds (deterministic,
# unlike SeedSequence entropy)
_epoch_counter = itertools.count()


def _worker_context():
    """Worker process context. Forking the parent is unsafe once JAX's
    runtime threads exist (CPython 3.12 warns it may deadlock), so workers
    come from a FORKSERVER: one clean server process preloads this module
    (paying the import once), then forks cheap numpy-only children from
    its single-threaded state. Falls back to spawn where forkserver is
    unavailable. Reference analog: fluid/dataloader/dataloader_iter.py's
    spawn-capable worker plumbing."""
    global _WORKER_CTX
    if _WORKER_CTX is None:
        try:
            ctx = multiprocessing.get_context("forkserver")
            ctx.set_forkserver_preload(["paddle_tpu.io.dataloader"])
        except ValueError:                        # platform without it
            ctx = multiprocessing.get_context("spawn")
        _WORKER_CTX = ctx
    return _WORKER_CTX


class _MultiprocessProducer:
    """Fan out index batches to forked workers; yield results IN ORDER.

    In-flight work is windowed to num_workers * prefetch_factor batches
    (like the reference _DataLoaderIterMultiProcess outstanding-batch
    cap), so a slow consumer doesn't let workers race through the epoch
    and pile every collated batch into host memory."""

    def __init__(self, dataset, batches, num_workers, worker_init_fn,
                 timeout, raw_samples, prefetch_factor=2):
        ctx = _worker_context()
        self._task_q = ctx.SimpleQueue()
        self._result_q = ctx.Queue()
        self._timeout = timeout
        self._depth = max(1, num_workers * max(prefetch_factor, 1))
        self._workers = []
        # deterministic per-worker seeding: a SEEDED program (paddle.seed)
        # derives the base seed from the framework seed plus an epoch
        # counter — NOT from time/pid entropy — so worker k's augmentation
        # stream is reproducible run-to-run; an unseeded program keeps
        # per-run entropy (independent hyper-parameter workers must not
        # all see the same "random" augmentations)
        from ..framework.random import default_generator
        if default_generator.seeded:
            base_seed = (int(default_generator.initial_seed) * 1000003
                         + next(_epoch_counter) * 10007) % (2 ** 31)
        else:
            base_seed = int(np.random.SeedSequence().entropy % (2 ** 31))
        for w in range(num_workers):
            p = ctx.Process(target=_worker_loop,
                            args=(dataset, self._task_q, self._result_q, w,
                                  worker_init_fn, raw_samples, num_workers,
                                  base_seed),
                            daemon=True)
            p.start()
            self._workers.append(p)
        self._batches = list(batches)

    def _get_result(self):
        """Wait for one result, polling worker liveness (a SIGKILLed or
        fork-deadlocked worker must surface as an error, not a hang)."""
        import time as _time
        deadline = (_time.monotonic() + self._timeout) if self._timeout \
            else None
        while True:
            try:
                return self._result_q.get(timeout=1.0)
            except queue.Empty:
                if any(not p.is_alive() for p in self._workers):
                    raise RuntimeError(
                        "a DataLoader worker process died unexpectedly "
                        "(killed or crashed before reporting)") from None
                if deadline is not None and _time.monotonic() > deadline:
                    raise RuntimeError(
                        f"DataLoader worker timed out after "
                        f"{self._timeout}s") from None

    def __iter__(self):
        try:
            n = len(self._batches)
            submitted = 0
            while submitted < min(self._depth, n):
                self._task_q.put((submitted,
                                  list(self._batches[submitted])))
                submitted += 1
            pending = {}
            for want in range(n):
                while want not in pending:
                    bidx, payload, err = self._get_result()
                    if err is not None:
                        raise RuntimeError(
                            f"DataLoader worker failed on batch {bidx}: "
                            f"{err}")
                    pending[bidx] = payload
                    if submitted < n:
                        self._task_q.put(
                            (submitted, list(self._batches[submitted])))
                        submitted += 1
                yield pending.pop(want)
        finally:
            self.close()

    def close(self):
        # graceful first: sentinels let a worker still inside startup run
        # its worker_init_fn and exit cleanly (terminate() could kill it
        # BEFORE init ran — the old worker_init flake); stragglers are
        # terminated after a bounded join
        for _ in self._workers:
            try:
                self._task_q.put(None)
            except Exception:
                break
        deadline = _monotonic() + 5.0
        for p in self._workers:
            p.join(timeout=max(0.1, deadline - _monotonic()))
        for p in self._workers:
            if p.is_alive():
                p.terminate()
        for p in self._workers:
            p.join(timeout=1.0)
        self._workers = []


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        from ..core import parallel_collate
        return Tensor(parallel_collate(batch))
    if isinstance(sample, Tensor):
        import jax.numpy as jnp
        return Tensor(jnp.stack([b._value for b in batch]))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, np.float32))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return tuple(default_collate_fn(list(items)) for items in transposed)
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


class _PrefetchIterator:
    """Producer thread fills a bounded queue; blocking/wakeup runs in the
    native core's BoundedQueue (reference: buffered_reader.cc +
    lod_tensor_blocking_queue.h) with a queue.Queue fallback."""

    def __init__(self, produce_batches, prefetch=2):
        from ..core import BoundedQueue
        self._q = BoundedQueue(max(prefetch, 1))
        # the producer is handed the queue and this box, never the
        # iterator: one dropped mid-epoch (`next(iter(loader))`) is then
        # collected, `__del__` closes the queue, and the producer blocked
        # on it lets go of its batches instead of holding them for good
        self._failed = []
        self._thread = threading.Thread(
            target=self._run, args=(self._q, self._failed, produce_batches),
            daemon=True)
        self._thread.start()

    @staticmethod
    def _run(q, failed, produce_batches):
        try:
            for b in produce_batches():
                if not q.push(b):
                    return  # consumer closed the queue
        except BaseException as e:  # propagate to consumer
            failed.append(e)
        finally:
            q.close()

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return self._q.pop()
        except StopIteration:
            if self._failed:
                raise self._failed[0] from None
            raise

    def close(self):
        """Wake a blocked producer and join it; must run before the native
        queue is freed (an abandoned producer blocked in push would
        otherwise race queue destruction)."""
        self._q.close()
        self._thread.join(timeout=5.0)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self._custom_collate = collate_fn is not None
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        self.use_buffer_reader = use_buffer_reader
        self._iterable_mode = isinstance(dataset, IterableDataset)
        self.batch_size = batch_size
        self.drop_last = drop_last
        if self._iterable_mode:
            self.batch_sampler = None
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no length")
        return len(self.batch_sampler)

    def _produce(self):
        if self._iterable_mode:
            batch = []
            for sample in self.dataset:
                batch.append(sample)
                if self.batch_size and len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
            return
        if self.num_workers > 0 and hasattr(multiprocessing, "get_context"):
            # subprocess workers (reference _DataLoaderIterMultiProcess).
            # Default collate: workers collate numpy, the parent wraps
            # Tensors. Custom collate_fn runs in the PARENT on the raw
            # samples (jax must never run in a forked child).
            raw = self._custom_collate
            producer = _MultiprocessProducer(
                self.dataset, iter(self.batch_sampler), self.num_workers,
                self.worker_init_fn, self.timeout, raw,
                prefetch_factor=self.prefetch_factor)
            for payload in producer:
                yield self.collate_fn(payload) if raw \
                    else _to_tensors(payload)
        else:
            for indices in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in indices])

    def __iter__(self):
        if self.use_buffer_reader:
            return _PrefetchIterator(self._produce,
                                     prefetch=self.prefetch_factor)
        return self._produce()


class WorkerInfo:
    """Reference: fluid/dataloader/worker.py WorkerInfo — identifies the
    current DataLoader worker process."""

    def __init__(self, id, num_workers, dataset, seed=0):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info = None


def get_worker_info():
    """Inside a DataLoader worker: its WorkerInfo; in the main process:
    None (reference: fluid/dataloader/worker.py get_worker_info)."""
    return _worker_info
