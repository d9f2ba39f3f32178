"""DataLoader (reference: fluid/reader.py:149; fluid/dataloader/
dataloader_iter.py:100,230 — multiprocess workers, mmap shared memory,
blocking queue; operators/reader/buffered_reader.cc — async host→device
double buffering).

TPU-native, three feed paths by cost:
1. **Native array path**: TensorDataset-style contiguous arrays are batch-
   assembled by the csrc gather engine (csrc/datafeed.cc) — one C call per
   batch, no per-row Python.
2. **Process workers** (num_workers>0, use_shared_memory): forked worker
   processes fetch+collate and ship batches through posix shared memory
   (dataloader_iter.py:230 _DataLoaderIterMultiProcess analog) — Python
   transform pipelines escape the GIL.
3. **Thread workers**: the fallback for cheap datasets / platforms without
   fork.
`prefetch_to_device` stages the next batch onto the accelerator while the
current step computes (buffered_reader.cc analog).
"""
from __future__ import annotations

import multiprocessing as mp
import queue
import threading
from typing import Optional

import numpy as np

from ..tensor import Tensor
from .dataset import Dataset, IterableDataset, TensorDataset
from .sampler import BatchSampler


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        return np.stack([b.numpy() for b in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return batch
    return np.asarray(batch)


def _to_tensor_tree(obj):
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_tensor_tree(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_tensor_tree(v) for k, v in obj.items()}
    if isinstance(obj, np.ndarray):
        if obj.dtype == np.float64:
            obj = obj.astype(np.float32)
        if obj.dtype == np.object_ or obj.dtype.kind in "US":
            return obj
        return Tensor(obj)
    return obj


_SENTINEL = object()


def _dataset_arrays(ds):
    """numpy views of a TensorDataset's columns, or None."""
    if not isinstance(ds, TensorDataset):
        return None
    cols = []
    for t in ds.tensors:
        if isinstance(t, Tensor):
            cols.append(np.asarray(t._value))
        elif isinstance(t, np.ndarray):
            cols.append(t)
        else:
            return None
    return cols


class _NativeArrayIter:
    """Feed path 1: whole-batch gather through the csrc engine (or numpy
    fancy-indexing fallback) — no workers, no queues."""

    def __init__(self, loader, cols):
        from . import native_feed

        self._nf = native_feed
        self._cols = [np.ascontiguousarray(c) for c in cols]
        self._batches = iter(loader.batch_sampler)
        self._loader = loader

    def __iter__(self):
        return self

    def __next__(self):
        idxs = np.asarray(next(self._batches), np.int64)
        out = []
        for c in self._cols:
            scale = 1.0 / 255.0 if c.dtype == np.uint8 else None
            out.append(self._nf.gather_rows(c, idxs, u8_scale=scale))
        return _to_tensor_tree(list(out))


def _mp_worker(dataset, collate_fn, index_q, result_q, use_shm,
               worker_init_fn, worker_id):
    """Worker process body (dataloader_iter.py:100 _worker_loop analog).
    Lives for the pool's lifetime (persistent_workers); a bad sample
    reports an error for ITS batch and the worker keeps serving."""
    from multiprocessing import shared_memory

    if worker_init_fn is not None:
        try:
            worker_init_fn(worker_id)
        except Exception as e:
            result_q.put((("__init__", worker_id), "error", repr(e)))
            return
    while True:
        item = index_q.get()
        if item is None:
            return
        i, idxs = item  # i = (epoch, index) tag, echoed back verbatim
        try:
            batch = collate_fn([dataset[j] for j in idxs])
            flat, spec = _flatten_np(batch)
            if use_shm:
                blocks = []
                for arr in flat:
                    arr = np.ascontiguousarray(arr)
                    shm = shared_memory.SharedMemory(create=True,
                                                     size=max(arr.nbytes, 1))
                    np.ndarray(arr.shape, arr.dtype,
                               buffer=shm.buf)[...] = arr
                    blocks.append((shm.name, arr.shape, arr.dtype.str))
                    shm.close()
                result_q.put((i, "shm", (blocks, spec)))
            else:
                result_q.put((i, "pickle", (flat, spec)))
        except Exception as e:  # report, but keep the worker alive
            result_q.put((i, "error", repr(e)))


def _flatten_np(batch):
    """Flatten a collated batch (nested list/tuple/dict of arrays) into
    (arrays, spec) for shared-memory transport."""
    flat = []

    def go(x):
        if isinstance(x, (list, tuple)):
            return ("seq", type(x).__name__, [go(v) for v in x])
        if isinstance(x, dict):
            return ("dict", sorted(x), [go(x[k]) for k in sorted(x)])
        flat.append(np.asarray(x))
        return ("leaf", len(flat) - 1, None)

    spec = go(batch)
    return flat, spec


def _unflatten_np(flat, spec):
    kind, a, b = spec
    if kind == "leaf":
        return flat[a]
    if kind == "seq":
        seq = [_unflatten_np(flat, s) for s in b]
        return tuple(seq) if a == "tuple" else seq
    return {k: _unflatten_np(flat, s) for k, s in zip(a, b)}


def _discard_result(kind, payload):
    """Free shared memory of a result that will never be consumed."""
    if kind != "shm":
        return
    from multiprocessing import shared_memory

    blocks, _spec = payload
    for name, _shape, _dtype in blocks:
        try:
            shm = shared_memory.SharedMemory(name=name)
            shm.close()
            shm.unlink()
        except Exception:
            pass


class _WorkerPool:
    """Forked worker processes + shared-memory transport, reusable across
    epochs (persistent_workers) with a BOUNDED in-flight window — workers
    cannot race ahead and materialize the epoch in shared memory
    (reference _DataLoaderIterMultiProcess outstanding-capacity logic,
    dataloader_iter.py:230).

    Fork and the accelerator: the pool usually forks AFTER the parent's
    jax runtime is up (the first epoch starts after the model was
    built).  That is safe for exactly what the workers do here — index
    the dataset, collate with numpy, write shared memory — because a
    forked child inherits the parent's runtime handles but none of its
    threads, and a chip belongs to the one process that opened it: a
    worker that calls into jax (a ``jnp`` op in ``dataset[i]``,
    ``collate_fn`` or ``worker_init_fn``, a Tensor built in the worker)
    fails or hangs on the parent's chip.  Keep datasets and collate
    functions host-only (numpy in, numpy out); the parent turns batches
    into device arrays."""

    def __init__(self, loader):
        from multiprocessing import shared_memory  # noqa: F401 (probe)

        ctx = mp.get_context("fork")
        self.n_workers = max(1, loader.num_workers)
        # in-flight cap: prefetch_factor batches per worker
        self.capacity = max(2, loader.prefetch_factor) * self.n_workers
        self._index_q = ctx.Queue()
        self._result_q = ctx.Queue(maxsize=self.capacity + self.n_workers)
        self._procs = [
            ctx.Process(target=_mp_worker,
                        args=(loader.dataset, loader.collate_fn,
                              self._index_q, self._result_q,
                              loader.use_shared_memory,
                              loader.worker_init_fn, wid),
                        daemon=True)
            for wid in range(self.n_workers)]
        for p in self._procs:
            p.start()
        self.alive = True
        self.epoch = 0

    def submit(self, i, idxs):
        self._index_q.put((i, list(idxs)))

    def get(self, timeout):
        deadline = (None if not timeout
                    else __import__("time").monotonic() + timeout)
        while True:
            try:
                return self._result_q.get(timeout=1.0)
            except queue.Empty:
                if deadline is not None and \
                        __import__("time").monotonic() > deadline:
                    raise RuntimeError(
                        f"DataLoader timed out after {timeout}s waiting "
                        "for a worker batch (timeout= parameter)")
                if not any(p.is_alive() for p in self._procs):
                    raise RuntimeError(
                        "all DataLoader workers died (did worker_init_fn "
                        "or the dataset crash the processes?)")

    def _drain(self):
        """Free shm of results that will never be consumed."""
        while True:
            try:
                _tag, kind, payload = self._result_q.get_nowait()
            except queue.Empty:
                return
            _discard_result(kind, payload)

    def shutdown(self):
        if not self.alive:
            return
        self.alive = False
        for _ in self._procs:
            try:
                self._index_q.put(None)
            except Exception:
                pass
        self._drain()
        for p in self._procs:
            p.join(timeout=1)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
        self._drain()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


class _ProcessIter:
    """One epoch over a _WorkerPool: indices stream into the pool as
    results are consumed (window = pool.capacity)."""

    def __init__(self, loader, pool):
        self.loader = loader
        self.pool = pool
        pool.epoch += 1
        self._epoch = pool.epoch
        self._batches = list(iter(loader.batch_sampler))
        self._n_batches = len(self._batches)
        self._sent = 0
        self._next_out = 0
        self._pending = {}
        while self._sent < min(pool.capacity, self._n_batches):
            pool.submit((self._epoch, self._sent), self._batches[self._sent])
            self._sent += 1

    def __iter__(self):
        return self

    def __next__(self):
        from multiprocessing import shared_memory

        if self._next_out >= self._n_batches:
            if not self.loader.persistent_workers:
                self.pool.shutdown()
            raise StopIteration
        while self._next_out not in self._pending:
            tag, kind, payload = self.pool.get(self.loader.timeout)
            epoch, i = tag
            if epoch == "__init__":
                self.pool.shutdown()
                raise RuntimeError(
                    f"DataLoader worker_init_fn failed in worker {i}: "
                    f"{payload}")
            if epoch != self._epoch:
                _discard_result(kind, payload)  # stale abandoned-epoch batch
                continue
            self._pending[i] = (kind, payload)
        kind, payload = self._pending[self._next_out]
        if kind == "error":
            # poison stays pending: a retried next() re-raises instead of
            # hanging on a result that will never arrive
            if not self.loader.persistent_workers:
                self.pool.shutdown()
            raise RuntimeError(f"DataLoader worker failed: {payload}")
        del self._pending[self._next_out]
        self._next_out += 1
        # backpressure: one new index per consumed batch
        if self._sent < self._n_batches:
            self.pool.submit((self._epoch, self._sent),
                             self._batches[self._sent])
            self._sent += 1
        if kind == "shm":
            blocks, spec = payload
            flat = []
            for name, shape, dtype in blocks:
                shm = shared_memory.SharedMemory(name=name)
                arr = np.ndarray(shape, np.dtype(dtype),
                                 buffer=shm.buf).copy()
                shm.close()
                shm.unlink()
                flat.append(arr)
        else:
            flat, spec = payload
        batch = _unflatten_np(flat, spec)
        out = _to_tensor_tree(batch)
        if isinstance(out, tuple):
            out = list(out)
        return out


def prefetch_to_device(iterator, depth=2):
    """Double-buffered host→device staging (buffered_reader.cc analog):
    device_put of batch N+1 overlaps step N's compute (jax transfers are
    async)."""
    import jax

    from ..tensor import Tensor as _T

    def stage(batch):
        if isinstance(batch, (list, tuple)):
            return [stage(b) for b in batch]
        if isinstance(batch, _T):
            return _T(jax.device_put(batch._value))
        return batch

    buf = []
    it = iter(iterator)
    try:
        for _ in range(depth):
            buf.append(stage(next(it)))
    except StopIteration:
        pass
    while buf:
        out = buf.pop(0)
        try:
            buf.append(stage(next(it)))
        except StopIteration:
            pass
        yield out


class _LoaderIter:
    def __init__(self, loader):
        self.loader = loader
        self.batch_sampler_iter = (iter(loader.batch_sampler)
                                   if loader.batch_sampler is not None else None)
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(2, loader.prefetch_factor))
        self._threads = []
        self._done = threading.Event()
        self._err = None
        n_workers = max(1, loader.num_workers)
        if isinstance(loader.dataset, IterableDataset):
            t = threading.Thread(target=self._iterable_worker, daemon=True)
            t.start()
            self._threads = [t]
        else:
            self._index_queue: "queue.Queue" = queue.Queue()
            self._order = []
            for i, idxs in enumerate(self.batch_sampler_iter):
                self._index_queue.put((i, idxs))
                self._order.append(i)
            self._n_batches = len(self._order)
            self._results = {}
            self._results_lock = threading.Lock()
            self._next_out = 0
            for _ in range(n_workers):
                self._index_queue.put(_SENTINEL)
            for _ in range(n_workers):
                t = threading.Thread(target=self._map_worker, daemon=True)
                t.start()
                self._threads.append(t)

    def _fetch(self, idxs):
        ds = self.loader.dataset
        batch = [ds[i] for i in idxs]
        return self.loader.collate_fn(batch)

    def _map_worker(self):
        while not self._done.is_set():
            item = self._index_queue.get()
            if item is _SENTINEL:
                return
            i, idxs = item
            try:
                out = self._fetch(idxs)
            except Exception as e:  # propagate
                self._err = e
                self._done.set()
                return
            with self._results_lock:
                self._results[i] = out

    def _iterable_worker(self):
        try:
            batch = []
            for sample in self.loader.dataset:
                batch.append(sample)
                if len(batch) == self.loader.batch_size:
                    self._queue.put(self.loader.collate_fn(batch))
                    batch = []
            if batch and not self.loader.drop_last:
                self._queue.put(self.loader.collate_fn(batch))
            self._queue.put(_SENTINEL)
        except Exception as e:
            self._err = e
            self._done.set()
            self._queue.put(_SENTINEL)

    def __iter__(self):
        return self

    def __next__(self):
        if isinstance(self.loader.dataset, IterableDataset):
            out = self._queue.get()
            if out is _SENTINEL:
                if self._err:
                    raise self._err
                raise StopIteration
            return self._postprocess(out)
        if self._next_out >= self._n_batches:
            raise StopIteration
        want = self._order[self._next_out]
        import time

        while True:
            if self._err:
                raise self._err
            with self._results_lock:
                if want in self._results:
                    out = self._results.pop(want)
                    break
            time.sleep(0.0005)
        self._next_out += 1
        return self._postprocess(out)

    def _postprocess(self, np_batch):
        out = _to_tensor_tree(np_batch)
        if isinstance(out, tuple):
            out = list(out)
        return out

    def __del__(self):
        self._done.set()


class DataLoader:
    def __init__(self, dataset: Dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None, num_workers=0,
                 use_buffer_reader=True, prefetch_factor=2, use_shared_memory=True,
                 timeout=0, worker_init_fn=None, persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.drop_last = drop_last
        self.batch_size = batch_size
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        self._pool = None
        if isinstance(dataset, IterableDataset):
            self.batch_sampler = None
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)

    def __iter__(self):
        # path 1: contiguous arrays + default collate → native batch gather
        if (self.batch_sampler is not None
                and self.collate_fn is default_collate_fn):
            cols = _dataset_arrays(self.dataset)
            if cols is not None:
                return _NativeArrayIter(self, cols)
        # path 2: process workers with shared-memory transport
        if (self.num_workers > 0 and self.use_shared_memory
                and self.batch_sampler is not None
                and hasattr(mp, "get_context")):
            try:
                if self.persistent_workers:
                    if self._pool is None or not self._pool.alive:
                        self._pool = _WorkerPool(self)
                    return _ProcessIter(self, self._pool)
                return _ProcessIter(self, _WorkerPool(self))
            except Exception:
                pass  # fork/shm unavailable → thread fallback
        # path 3: thread workers
        return _LoaderIter(self)

    def __len__(self):
        if self.batch_sampler is not None:
            return len(self.batch_sampler)
        raise TypeError("IterableDataset DataLoader has no len()")
