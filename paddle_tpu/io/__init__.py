"""paddle_tpu.io — datasets and DataLoader.

Ref parity: python/paddle/fluid/dataloader/ (Dataset/BatchSampler/
DistributedBatchSampler) + fluid/reader.py DataLoader +
fluid/dataloader/dataloader_iter.py:97,248 (single-/multi-process
iterators) + dataloader/worker.py (worker loop). `num_workers>0` forks a
real worker pool: samples are collated to numpy inside the workers
(GIL-free of the parent), returned through an mp queue in batch order, and
converted to Tensors in the parent. `use_buffer_reader` double-buffers the
next batch onto the device (jax.device_put is async) while the previous
one computes. TensorDataset batches take the C++ datafeed fast path
(paddle_tpu.native.gather_rows).
"""

from __future__ import annotations

import itertools
import math
import multiprocessing as mp
import queue
import threading
import traceback

import numpy as np

from .. import observe
from ..core.tensor import Tensor
from ..framework import monitor, random as _random


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset does not support indexing")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        # store host numpy copies: samples must be fork-safe (loader
        # workers) and free of device-array references
        self.tensors = [np.asarray(t.numpy()) if isinstance(t, Tensor)
                        else np.asarray(t) for t in tensors]

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            sample = d[idx]
            if isinstance(sample, tuple):
                out.extend(sample)
            else:
                out.append(sample)
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        return itertools.chain(*self.datasets)


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if sum(lengths) != total:
        raise ValueError("sum of input lengths must equal dataset length")
    perm = np.random.permutation(total)
    out, offset = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[offset:offset + n].tolist()))
        offset += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    """ref: python/paddle/fluid/dataloader/batch_sampler.py."""

    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        if sampler is None:
            sampler = RandomSampler(dataset) if shuffle else \
                SequenceSampler(dataset)
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards the dataset across data-parallel ranks (per-host input
    sharding on TPU; ref python/paddle/fluid/dataloader/batch_sampler.py
    DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            from ..distributed import get_rank, get_world_size

            num_replicas = num_replicas or get_world_size()
            rank = rank if rank is not None else get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices = np.concatenate(
            [indices, indices[: self.total_size - n]])
        indices = indices[self.local_rank::self.nranks]
        batch = []
        for idx in indices.tolist():
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def _numpy_collate(batch):
    """Worker-side collate: numpy only (Tensors would drag a jax backend
    into every worker process)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._value) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [_numpy_collate(list(fields)) for fields in transposed]
    if isinstance(sample, dict):
        return {k: _numpy_collate([d[k] for d in batch]) for k in sample}
    return batch


def _to_tensor_tree(item):
    if isinstance(item, np.ndarray):
        return Tensor(item)
    if isinstance(item, (list, tuple)):
        return [_to_tensor_tree(v) for v in item]
    if isinstance(item, dict):
        return {k: _to_tensor_tree(v) for k, v in item.items()}
    return item


def default_collate_fn(batch):
    return _to_tensor_tree(_numpy_collate(batch))


class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info: WorkerInfo | None = None


def get_worker_info():
    """Inside a loader worker: (id, num_workers, dataset); None in the
    main process (ref fluid/dataloader/worker.py get_worker_info)."""
    return _worker_info


class _ExcInfo:
    def __init__(self, exc):
        self.type_name = type(exc).__name__
        self.tb = traceback.format_exc()


def _reject_tensors(obj, where):
    """Recursive: device arrays must never be touched inside a forked
    worker (forking an initialised XLA runtime is unsafe)."""
    if isinstance(obj, Tensor):
        raise RuntimeError(
            f"{where} produced a paddle Tensor inside a loader worker; "
            "return numpy when num_workers > 0 — touching device arrays "
            "in a forked child of an initialised XLA runtime is unsafe")
    if isinstance(obj, (list, tuple)):
        for v in obj:
            _reject_tensors(v, where)
    elif isinstance(obj, dict):
        for v in obj.values():
            _reject_tensors(v, where)


def _worker_loop(dataset, index_queue, result_queue, collate_fn, init_fn,
                 worker_id, num_workers, base_seed):
    """ref fluid/dataloader/worker.py:_worker_loop — pull index lists,
    collate to numpy, push (batch_id, data)."""
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset,
                              base_seed + worker_id)
    np.random.seed(base_seed + worker_id)
    if init_fn is not None:
        init_fn(worker_id)
    while True:
        job = index_queue.get()
        if job is None:
            return
        batch_id, idxs = job
        try:
            samples = [dataset[i] for i in idxs]
            for s in samples:
                _reject_tensors(s, "dataset __getitem__")
            data = collate_fn(samples)
            _reject_tensors(data, "collate_fn")
            result_queue.put((batch_id, ("ok", data)))
        except Exception as e:  # noqa: BLE001 — forwarded to parent
            result_queue.put((batch_id, ("err", _ExcInfo(e))))


class _MultiprocessIter:
    """Fork-based worker pool with ordered batch reassembly
    (ref fluid/dataloader/dataloader_iter.py:248
    _DataLoaderIterMultiProcess)."""

    def __init__(self, loader):
        self.loader = loader
        self.num_workers = loader.num_workers
        self.timeout = loader.timeout or None  # 0/None => wait, watch pool
        ctx = mp.get_context("fork")
        self.index_queues = []
        self.workers = []
        # fresh base seed per iterator/epoch: identical reseeding every
        # epoch would repeat augmentations byte-for-byte
        epoch = loader._epoch_count
        loader._epoch_count += 1
        base_seed = (int(_random.default_generator.initial_seed())
                     * 1000003 + epoch * 7919) & 0x7FFFFFFF
        collate = loader._worker_collate_fn
        self._next_send = 0
        self._next_recv = 0
        self._reorder: dict[int, object] = {}
        self._batches = iter(loader._index_batches())
        self._exhausted = False
        self._window = max(2, loader.prefetch_factor * self.num_workers)
        self._shutdown_done = False
        monitor.stat_add("input_epochs")
        # the whole start of an epoch's pool: its queues, the forks, and
        # the first index lists (a queue's first put starts its feeder
        # thread, slow in a process that has just forked). Spans close
        # in this (the parent) process only, and hold no lock while
        # open: nothing is held across the fork
        with observe.span("input.spawn", cat="input"):
            self.result_queue = ctx.Queue()
            for w in range(self.num_workers):
                iq = ctx.Queue()
                p = ctx.Process(
                    target=_worker_loop,
                    args=(loader.dataset, iq, self.result_queue, collate,
                          loader.worker_init_fn, w, self.num_workers,
                          base_seed),
                    daemon=True)
                p.start()
                self.index_queues.append(iq)
                self.workers.append(p)
            for _ in range(self._window):
                self._dispatch_one()

    def _dispatch_one(self):
        if self._exhausted:
            return
        try:
            idxs = next(self._batches)
        except StopIteration:
            self._exhausted = True
            return
        wid = self._next_send % self.num_workers
        self.index_queues[wid].put((self._next_send, idxs))
        self._next_send += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self._next_recv >= self._next_send and self._exhausted:
            self._shutdown()
            raise StopIteration
        # an iterator's first take waits for workers that have only just
        # been forked: it belongs to the epoch's turnover, and
        # `input.wait` is a steady-state take only. `ready` = how far
        # the workers are ahead of the consumer: the batches they have
        # put and this side has not yielded (the queue's count is a
        # semaphore's value: nothing is read for it)
        ready = len(self._reorder) + self.result_queue.qsize()
        name = "input.wait" if self._next_recv else "input.first_batch"
        with observe.span(name, cat="input", ready=ready):
            self._take_next()
        monitor.stat_add("input_batches")
        if ready == 0:
            monitor.stat_add("input_batches_waited")
        status, data = self._reorder.pop(self._next_recv)
        self._next_recv += 1
        self._dispatch_one()
        if status == "err":
            self._shutdown()
            raise RuntimeError(
                f"DataLoader worker raised {data.type_name}:\n{data.tb}")
        with observe.span("input.convert", cat="input"):
            return _to_tensor_tree(data)

    def _take_next(self):
        """Block on the workers' queue until the next batch in order
        has arrived."""
        waited = 0.0
        while self._next_recv not in self._reorder:
            try:
                batch_id, payload = self.result_queue.get(timeout=5.0)
            except queue.Empty:
                waited += 5.0
                dead = [i for i, p in enumerate(self.workers)
                        if not p.is_alive()]
                if dead:
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader workers died: ranks {dead}")
                if self.timeout and waited >= self.timeout:
                    self._shutdown()
                    raise RuntimeError(
                        f"DataLoader timed out after {self.timeout}s")
                continue  # timeout unset (block indefinitely) or not yet
            self._reorder[batch_id] = payload

    def _shutdown(self):
        if self._shutdown_done:
            return
        self._shutdown_done = True
        # runs inside the `next()` that returns an epoch's LAST batch
        # when `_device_prefetch` reads one batch ahead
        with observe.span("input.close", cat="input"):
            for iq in self.index_queues:
                try:
                    iq.put(None)
                except (OSError, ValueError):
                    pass
            for p in self.workers:
                p.join(timeout=5)
                if p.is_alive():
                    p.terminate()
            self.result_queue.close()

    def __del__(self):
        try:
            self._shutdown()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def _device_prefetch(iterator):
    """Double-buffered prefetch-to-device: the transfer of batch N+1 is
    dispatched (device_put is async) while batch N computes
    (ref reader.py use_buffer_reader / double-buffer queues)."""
    import jax

    def put(batch):
        if isinstance(batch, Tensor):
            return Tensor(jax.device_put(batch._value))
        if isinstance(batch, (list, tuple)):
            return [put(b) for b in batch]
        if isinstance(batch, dict):
            return {k: put(v) for k, v in batch.items()}
        return batch

    prev = None
    for batch in iterator:
        with observe.span("input.convert", cat="input"):
            cur = put(batch)
        if prev is not None:
            yield prev
        prev = cur
    if prev is not None:
        yield prev


class DataLoader:
    """ref: python/paddle/fluid/reader.py:146 DataLoader."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        # worker-side collate must stay numpy; a user collate_fn runs
        # verbatim in the worker and np leaves become Tensors in the parent
        self._worker_collate_fn = collate_fn or _numpy_collate
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_buffer_reader = use_buffer_reader
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self._epoch_count = 0
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        elif not self._iterable_mode:
            self.batch_size = batch_size
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last) if batch_size is not None else None
        else:
            self.batch_size = batch_size
            self.batch_sampler = None
        self.drop_last = drop_last

    def __len__(self):
        if self._iterable_mode:
            raise RuntimeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def _index_batches(self):
        """Index lists consumed by the worker pool (map-style, batched)."""
        yield from self.batch_sampler

    def _native_tensor_batch(self, idxs):
        """C++ datafeed fast path: one parallel gather per component
        instead of per-sample indexing + stack."""
        from .. import native

        return [Tensor(native.gather_rows(a, idxs))
                for a in self._native_arrays]

    def _can_use_native(self):
        from .. import native

        cached = getattr(self, "_native_ok", None)
        if cached is not None:
            return cached
        ok = (isinstance(self.dataset, TensorDataset)
              and self.collate_fn is default_collate_fn
              and native.available())
        if ok:
            arrays = []
            for t in self.dataset.tensors:
                a = t.numpy() if isinstance(t, Tensor) else np.asarray(t)
                arrays.append(np.ascontiguousarray(a))
            self._native_arrays = arrays
        self._native_ok = ok
        return ok

    def _iter_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        elif self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield self.dataset[i]
        elif self._can_use_native():
            for idxs in self.batch_sampler:
                yield self._native_tensor_batch(idxs)
        else:
            for idxs in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idxs])

    def __iter__(self):
        workers = bool(self.num_workers and self.num_workers > 0)
        if workers and self._iterable_mode:
            # iterable datasets keep the thread prefetcher (each fork would
            # otherwise re-iterate the same stream)
            it = self._prefetch_iter()
        elif workers and self.batch_sampler is not None \
                and not self._can_use_native():
            # batch_size=None (raw-sample mode) and pre-loaded
            # TensorDatasets gain nothing from forking
            it = iter(_MultiprocessIter(self))
        else:
            it = self._iter_batches()
        if self.use_buffer_reader:
            return _device_prefetch(it)
        return it

    def _prefetch_iter(self):
        """Thread-based prefetch pipeline (keeps the accelerator fed while
        the next host batch is assembled). Producer exceptions re-raise in
        the consumer (a dataset error must not look like end-of-epoch)."""
        q: "queue.Queue" = queue.Queue(
            maxsize=max(2, self.prefetch_factor * self.num_workers))
        sentinel = object()
        error = []

        def producer():
            try:
                for b in self._iter_batches():
                    q.put(b)
            except BaseException as e:  # noqa: BLE001 — forwarded below
                error.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                if error:
                    raise error[0]
                return
            yield item


