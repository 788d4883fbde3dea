"""DataLoader (ref: python/paddle/io/dataloader/dataloader_iter.py (U)).

TPU-native design: the reference's multiprocess workers + pinned-memory +
CUDA-stream H2D pipeline becomes a threaded prefetch pipeline feeding
device_put — on TPU VMs the host is roomy and jax transfers are async, so
worker *threads* (NumPy releases the GIL) plus a bounded prefetch queue give
the same overlap without fork/IPC fragility. A native C++ prefetcher can slot
under `paddle_tpu.utils.hostloader` for decode-heavy pipelines.

`use_shared_memory` is accepted for API compatibility and ignored: process
workers ship batches by pickling through mp.Queue; the reference's
shared-memory ring is a CUDA-pinned-memory optimization with no TPU analog
worth its fork-safety cost.

Threads against processes: on a single core, workers cannot add
parallelism — threads cost nothing while spawn processes pay start-up
and pickling, which is why threads are the default. On the many-core
hosts of a TPU VM process workers win only for decode that holds the
GIL, when cores are plentiful (samples/s of either: not measured; the
benchmark's train cell runs two process workers and reads
`trainer.input_wait_ms`).

For decode-heavy Python datasets that DON'T release the GIL (jpeg decode,
tokenization), `use_process_workers=True` switches to spawn-based process
workers, the analog of the reference's default multiprocess mode: workers
fetch+collate to NumPy and ship batches back over a queue; the parent wraps
them into Tensors (device transfer stays in the parent, where the TPU
client lives). Threads remain the default — on low-core hosts process
startup dominates."""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from ..core.tensor import Tensor
from ..observability import events as _obs_events
from ..observability import metrics as _obs_metrics
from .dataset import IterableDataset
from .sampler import BatchSampler

# input-pipeline health: queue_depth says how many prefetched batches sit
# ready (0 while training = the loader is the bottleneck); stall_seconds
# is how long the consumer blocked waiting for the next batch (producer
# stall). Stalls > 1 ms also land on the event timeline, so a slow step
# in the chrome trace shows WHETHER the host pipeline caused it.
_DL_QUEUE_DEPTH = _obs_metrics.gauge(
    "dataloader.queue_depth", "prefetched batches ready at consume time")
_DL_STALL_SECONDS = _obs_metrics.histogram(
    "dataloader.stall_seconds",
    "consumer wall seconds blocked waiting for the next batch")
_DL_BATCHES = _obs_metrics.counter(
    "dataloader.batches", "batches delivered to the consumer")
_STALL_EVENT_THRESHOLD_S = 1e-3


def _note_delivery(stall, depth, mode, batch_index):
    _DL_STALL_SECONDS.observe(stall, workers=mode)
    _DL_QUEUE_DEPTH.set(depth, workers=mode)
    _DL_BATCHES.inc(workers=mode)
    if stall > _STALL_EVENT_THRESHOLD_S:
        _obs_events.instant("dataloader.stall", cat="io", workers=mode,
                            seconds=round(stall, 6), batch=batch_index,
                            queue_depth=depth)


class WorkerInfo:
    """ref io/dataloader/worker.py WorkerInfo: identifies the calling
    worker inside dataset code — the contract IterableDataset.__iter__
    uses to shard itself across workers."""

    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset

    def __repr__(self):
        return (f"WorkerInfo(id={self.id}, "
                f"num_workers={self.num_workers})")


_worker_tls = threading.local()
_PROC_WORKER_INFO = None  # set in spawned children


def get_worker_info():
    """Inside a worker (thread or spawned process): that worker's
    WorkerInfo; in the main process: None (reference contract)."""
    info = getattr(_worker_tls, "info", None)
    if info is not None:
        return info
    return _PROC_WORKER_INFO


def _collate_np(batch):
    """Collate to a NumPy pytree — the single collate policy; the Tensor
    variant is this plus a leaf wrap. Process workers ship these trees over
    the queue (Tensors don't cross the process boundary)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._data) for s in batch])
    if isinstance(sample, (int, np.integer)):
        # int32, not the reference's int64: x64 is disabled jax-side, and
        # int32 indices are what TPU embedding/gather kernels want
        return np.asarray(batch, np.int32)
    if isinstance(sample, float):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(_collate_np(list(items)) for items in transposed)
    if isinstance(sample, dict):
        return {k: _collate_np([d[k] for d in batch]) for k in sample}
    if isinstance(sample, str):
        return list(batch)
    return np.asarray(batch)


def _np_to_tensor_tree(x):
    if isinstance(x, np.ndarray):
        return Tensor(x)
    if isinstance(x, (list, tuple)) and not (x and isinstance(x[0], str)):
        return type(x)(_np_to_tensor_tree(v) for v in x)
    if isinstance(x, dict):
        return {k: _np_to_tensor_tree(v) for k, v in x.items()}
    return x


def _tensor_to_np_tree(x):
    """Inverse of _np_to_tensor_tree: user collate_fns return Tensors, but a
    spawned child must ship NumPy (the TPU client lives in the parent)."""
    if isinstance(x, Tensor):
        return np.asarray(x._data)
    if isinstance(x, (list, tuple)) and not (x and isinstance(x[0], str)):
        return type(x)(_tensor_to_np_tree(v) for v in x)
    if isinstance(x, dict):
        return {k: _tensor_to_np_tree(v) for k, v in x.items()}
    return x


def _process_worker(dataset, collate_fn, worker_init_fn, worker_id,
                    num_workers, task_q, result_q):
    """Top-level for spawn picklability."""
    global _PROC_WORKER_INFO
    _PROC_WORKER_INFO = WorkerInfo(worker_id, num_workers, dataset)
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        item = task_q.get()
        if item is None:
            return
        seq, indices = item
        try:
            out = _tensor_to_np_tree(collate_fn([dataset[i] for i in indices]))
        except Exception as e:  # noqa: BLE001 — propagate to the consumer
            out = RuntimeError(f"DataLoader worker {worker_id} failed: "
                               f"{type(e).__name__}: {e}")
        result_q.put((seq, out))


def _process_worker_iterable(dataset, collate_fn, worker_init_fn,
                             worker_id, num_workers, batch_size, drop_last,
                             result_q):
    """Iterable-dataset child: iterate THIS worker's replica (sharded by
    the dataset via get_worker_info), collate, ship NumPy batches."""
    global _PROC_WORKER_INFO
    _PROC_WORKER_INFO = WorkerInfo(worker_id, num_workers, dataset)
    try:
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        for batch in _batches_from(dataset, batch_size, drop_last):
            result_q.put(("b", _tensor_to_np_tree(collate_fn(batch))))
    except Exception as e:  # noqa: BLE001
        result_q.put(("e", RuntimeError(
            f"DataLoader worker {worker_id} failed: "
            f"{type(e).__name__}: {e}")))
    result_q.put(("done", worker_id))


def _batches_from(sample_iter, batch_size, drop_last):
    """Accumulate samples into batch-size lists (tail kept unless
    drop_last) — the one batching policy shared by the sync, threaded and
    process iterable paths."""
    batch = []
    for sample in sample_iter:
        batch.append(sample)
        if len(batch) == batch_size:
            yield batch
            batch = []
    if batch and not drop_last:
        yield batch


def default_collate_fn(batch):
    return _np_to_tensor_tree(_collate_np(batch))


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False, drop_last=False,
                 collate_fn=None, num_workers=0, use_buffer_reader=True,
                 prefetch_factor=2, use_shared_memory=True, timeout=0,
                 worker_init_fn=None, persistent_workers=False,
                 use_process_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 1)
        self.timeout = timeout
        self.use_process_workers = use_process_workers
        self.worker_init_fn = worker_init_fn
        self._proc_collate = collate_fn or _collate_np
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            self.batch_size = batch_size
            self.batch_sampler = None
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            if batch_size is None:
                self.batch_sampler = None
                self.batch_size = None
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
                )

    def __len__(self):
        if self._iterable:
            raise TypeError("length of IterableDataset DataLoader is unknown")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    # ---------------- iteration ----------------
    def _fetch(self, indices):
        return self.collate_fn([self.dataset[i] for i in indices])

    def _iter_sync(self):
        if self._iterable:
            batch = []
            for sample in self.dataset:
                batch.append(sample)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
            return
        if self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield self.collate_fn([self.dataset[i]])
            return
        for indices in self.batch_sampler:
            yield self._fetch(indices)

    def _iter_threaded(self):
        """num_workers>0: worker threads fetch+collate; a bounded queue keeps
        `num_workers * prefetch_factor` batches in flight, preserving order."""
        index_iter = iter(self.batch_sampler)
        max_inflight = self.num_workers * self.prefetch_factor
        results = {}
        results_lock = threading.Condition()
        task_q = queue.Queue()
        n_submitted = 0
        n_consumed = 0
        done_submitting = False

        def worker(wid):
            _worker_tls.info = WorkerInfo(wid, self.num_workers,
                                          self.dataset)
            init_err = None
            if self.worker_init_fn is not None:
                try:
                    self.worker_init_fn(wid)
                except Exception as e:  # noqa: BLE001 — surface, don't die
                    init_err = e
            while True:
                item = task_q.get()
                if item is None:
                    return
                seq, indices = item
                if init_err is not None:
                    out = init_err
                else:
                    try:
                        out = self._fetch(indices)
                    except Exception as e:  # propagate to consumer
                        out = e
                with results_lock:
                    results[seq] = out
                    results_lock.notify_all()

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        for t in threads:
            t.start()
        try:
            # prime
            for _ in range(max_inflight):
                try:
                    task_q.put((n_submitted, next(index_iter)))
                    n_submitted += 1
                except StopIteration:
                    done_submitting = True
                    break
            while n_consumed < n_submitted or not done_submitting:
                stall_t0 = time.perf_counter()
                with results_lock:
                    while n_consumed not in results:
                        got_notify = results_lock.wait(timeout=self.timeout or None)
                        # re-check the predicate before timing out: wait() can
                        # return False even though the batch landed just as the
                        # deadline elapsed
                        if not got_notify and self.timeout \
                                and n_consumed not in results:
                            raise RuntimeError(
                                f"DataLoader worker timed out after "
                                f"{self.timeout}s waiting for batch {n_consumed}")
                    out = results.pop(n_consumed)
                    depth = len(results)
                _note_delivery(time.perf_counter() - stall_t0, depth,
                               "threads", n_consumed)
                n_consumed += 1
                if isinstance(out, Exception):
                    raise out
                if not done_submitting:
                    try:
                        task_q.put((n_submitted, next(index_iter)))
                        n_submitted += 1
                    except StopIteration:
                        done_submitting = True
                yield out
        finally:
            for _ in threads:
                task_q.put(None)

    def _iter_process(self):
        """Spawn-based process workers (opt-in): fetch+collate to NumPy in
        children, convert to Tensors in the parent, preserve batch order."""
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        task_q = ctx.Queue()
        result_q = ctx.Queue()
        procs = [
            ctx.Process(
                target=_process_worker,
                args=(self.dataset, self._proc_collate, self.worker_init_fn,
                      wid, self.num_workers, task_q, result_q),
                daemon=True)
            for wid in range(self.num_workers)
        ]
        for p in procs:
            p.start()
        index_iter = iter(self.batch_sampler)
        max_inflight = self.num_workers * self.prefetch_factor
        results = {}
        n_submitted = 0
        n_consumed = 0
        done_submitting = False
        try:
            for _ in range(max_inflight):
                try:
                    task_q.put((n_submitted, list(next(index_iter))))
                    n_submitted += 1
                except StopIteration:
                    done_submitting = True
                    break
            while n_consumed < n_submitted or not done_submitting:
                waited = 0.0
                stall_t0 = time.perf_counter()
                while n_consumed not in results:
                    # poll in short slices so a dead worker (segfault/OOM
                    # kill) raises instead of blocking forever
                    try:
                        seq, out = result_q.get(timeout=1.0)
                        results[seq] = out
                        continue
                    except queue.Empty:
                        waited += 1.0
                    if not all(p.is_alive() for p in procs):
                        raise RuntimeError(
                            "DataLoader process worker died unexpectedly "
                            f"while batch {n_consumed} was in flight")
                    if self.timeout and waited >= self.timeout:
                        raise RuntimeError(
                            f"DataLoader process worker timed out after "
                            f"{self.timeout}s waiting for batch {n_consumed}")
                out = results.pop(n_consumed)
                _note_delivery(time.perf_counter() - stall_t0, len(results),
                               "procs", n_consumed)
                n_consumed += 1
                if isinstance(out, Exception):
                    raise out
                if not done_submitting:
                    try:
                        task_q.put((n_submitted, list(next(index_iter))))
                        n_submitted += 1
                    except StopIteration:
                        done_submitting = True
                yield _np_to_tensor_tree(out)
        finally:
            for _ in procs:
                task_q.put(None)
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    p.terminate()

    def _iter_threaded_iterable(self):
        """IterableDataset with worker threads: each worker iterates its
        own SHALLOW COPY of the dataset with its WorkerInfo installed —
        the dataset shards itself via get_worker_info() (reference
        contract; the copy keeps the mutate-winfo.dataset sharding idiom
        safe across threads; an unsharded dataset is replicated
        num_workers times, exactly as in the reference). Batches arrive
        in completion order."""
        import copy as _copy

        out_q = queue.Queue(maxsize=self.num_workers * self.prefetch_factor)
        stop = threading.Event()

        def _put(item):
            # bounded put that gives up when the consumer is gone
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def worker(wid):
            try:
                ds = _copy.copy(self.dataset)
            except Exception:  # uncopyable datasets fall back to shared
                ds = self.dataset
            _worker_tls.info = WorkerInfo(wid, self.num_workers, ds)
            try:
                if self.worker_init_fn is not None:
                    self.worker_init_fn(wid)
                for batch in _batches_from(ds, self.batch_size,
                                           self.drop_last):
                    if not _put(("b", self.collate_fn(batch))):
                        return
            except Exception as e:  # noqa: BLE001
                _put(("e", e))
            finally:
                _put(("done", wid))  # bounded; gives up once stop is set

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        for t in threads:
            t.start()
        live = self.num_workers
        waited = 0.0
        try:
            while live:
                try:
                    kind, payload = out_q.get(timeout=1.0)
                    waited = 0.0
                except queue.Empty:
                    waited += 1.0
                    if self.timeout and waited >= self.timeout:
                        raise RuntimeError(
                            f"DataLoader worker timed out after "
                            f"{self.timeout}s")
                    continue
                if kind == "done":
                    live -= 1
                elif kind == "e":
                    raise payload
                else:
                    yield payload
        finally:
            # early exit (consumer break / error): unblock queue-blocked
            # workers, then wait briefly. A thread stuck in USER code
            # (dataset __iter__) cannot be interrupted — after the
            # deadline it is abandoned as a daemon (it gives up its next
            # _put once stop is set)
            stop.set()
            deadline = 2.0
            import time as _time

            t0 = _time.time()
            for t in threads:
                while t.is_alive() and _time.time() - t0 < deadline:
                    try:
                        out_q.get_nowait()
                    except queue.Empty:
                        pass
                    t.join(timeout=0.1)

    def _iter_process_iterable(self):
        """IterableDataset with spawn workers: each child iterates its own
        dataset replica (WorkerInfo installed before iteration) and ships
        collated NumPy batches through a BOUNDED queue (children block at
        num_workers*prefetch_factor pending batches — backpressure); the
        parent wraps them into Tensors."""
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        result_q = ctx.Queue(maxsize=self.num_workers * self.prefetch_factor
                             + self.num_workers)
        procs = [
            ctx.Process(
                target=_process_worker_iterable,
                args=(self.dataset, self._proc_collate, self.worker_init_fn,
                      wid, self.num_workers, self.batch_size, self.drop_last,
                      result_q),
                daemon=True)
            for wid in range(self.num_workers)
        ]
        for p in procs:
            p.start()
        done = set()
        waited = 0.0
        dead_polls = 0
        try:
            while len(done) < self.num_workers:
                try:
                    kind, payload = result_q.get(timeout=1.0)
                    waited = 0.0
                    dead_polls = 0
                except queue.Empty:
                    waited += 1.0
                    # a worker that exited WITHOUT delivering its 'done'
                    # died; workers already done are allowed to be gone.
                    # A cleanly-exited (exitcode 0) worker's final batches
                    # and 'done' sentinel can still sit in the feeder pipe
                    # while the queue transiently reports empty — only
                    # treat exitcode 0 as death after several consecutive
                    # empty polls give the feeder time to flush.
                    dead = [i for i, p in enumerate(procs)
                            if i not in done and not p.is_alive()]
                    crashed = [i for i in dead if procs[i].exitcode]
                    if crashed and result_q.empty():
                        raise RuntimeError(
                            f"DataLoader process worker {crashed[0]} died "
                            "unexpectedly "
                            f"(exitcode {procs[crashed[0]].exitcode})")
                    dead_polls = dead_polls + 1 if dead else 0
                    if dead and dead_polls >= 3 and result_q.empty():
                        raise RuntimeError(
                            f"DataLoader process worker {dead[0]} died "
                            "unexpectedly")
                    if self.timeout and waited >= self.timeout:
                        raise RuntimeError(
                            f"DataLoader process worker timed out after "
                            f"{self.timeout}s")
                    continue
                if kind == "done":
                    done.add(payload)
                elif kind == "e":
                    raise payload
                else:
                    yield _np_to_tensor_tree(payload)
        finally:
            # early exit: children may be blocked on the bounded queue —
            # terminate them rather than strand them
            for p in procs:
                p.join(timeout=0.2)
                if p.is_alive():
                    p.terminate()
            for p in procs:
                p.join(timeout=5)

    def __iter__(self):
        if self.num_workers and self.num_workers > 0:
            if self._iterable:
                if self.use_process_workers:
                    return self._iter_process_iterable()
                return self._iter_threaded_iterable()
            if self.batch_sampler is not None:
                if self.use_process_workers:
                    return self._iter_process()
                return self._iter_threaded()
        return self._iter_sync()


