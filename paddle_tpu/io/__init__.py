"""``paddle.io`` — Dataset/DataLoader (``python/paddle/io/`` parity).

The reference's multiprocess worker + shared-memory tensor transport
(``dataloader_iter.py`` + ``mmap_allocator.cc``): num_workers>0 with
use_shared_memory=True forks worker processes that push collated batches
through the native shm ring (``native/shm_channel.cc`` via
``paddle_tpu.native.ShmChannel``) — decode happens off the trainer
process exactly as in the reference. With use_shared_memory=False (or if
the native lib is unavailable) a threaded prefetcher is used instead:
XLA releases the GIL during device compute, so threads still overlap
host decode with the device step.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import queue
import time
import threading
import traceback
import uuid
from typing import Iterable, List, Optional

import numpy as np

from ..framework.core import Tensor, _wrap_out

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
    "ChainDataset", "ConcatDataset", "Subset", "random_split",
    "BatchSampler", "Sampler", "SequenceSampler", "RandomSampler",
    "SubsetRandomSampler", "WeightedRandomSampler",
    "DistributedBatchSampler", "DataLoader",
    "get_worker_info", "default_collate_fn",
]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset is not indexable")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, tuple) else (item,))
        return tuple(out)

    def __len__(self):
        return min(len(ds) for ds in self.datasets)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for ds in self.datasets:
            yield from ds


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets]).tolist()

    def __len__(self):
        return self.cum[-1] if self.cum else 0

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        ds_idx = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if ds_idx == 0 else self.cum[ds_idx - 1]
        return self.datasets[ds_idx][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    lengths = list(lengths)
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        counts = [int(np.floor(n * l)) for l in lengths]
        rem = n - sum(counts)
        for i in range(rem):
            counts[i % len(counts)] += 1
        lengths = counts
    idx = np.random.permutation(sum(lengths)).tolist()
    out, offset = [], 0
    for l in lengths:
        out.append(Subset(dataset, idx[offset:offset + l]))
        offset += l
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
            return iter(np.random.randint(0, n,
                                          size=self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class SubsetRandomSampler(Sampler):
    """``paddle.io.SubsetRandomSampler``: random order over a fixed
    index subset."""

    def __init__(self, indices, generator=None):
        super().__init__(None)
        self.indices = list(indices)

    def __iter__(self):
        return iter(np.random.permutation(
            np.asarray(self.indices)).tolist())

    def __len__(self):
        return len(self.indices)


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
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
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = int(batch_size)
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

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
    """Shards sample indices across dp ranks
    (``python/paddle/io/dataloader/batch_sampler.py`` parity)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from .. import distributed as dist
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.nranks = num_replicas if num_replicas is not None \
            else dist.get_world_size()
        self.local_rank = rank if rank is not None else dist.get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(
            np.ceil(len(dataset) / self.nranks)) if not drop_last else \
            len(dataset) // self.nranks
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        # pad to make divisible
        if not self.drop_last:
            indices += indices[:self.total_size - len(indices)]
        else:
            indices = indices[:self.total_size]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


class _WorkerInfo:
    def __init__(self, id=0, num_workers=1, dataset=None):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None


def get_worker_info():
    return _worker_info


def _tree_to_numpy(obj):
    """Tensor-tree → picklable numpy-tree for shm worker transport."""
    if isinstance(obj, Tensor):
        return ("__pt_tensor__", np.asarray(obj.numpy()))
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_numpy(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_numpy(v) for k, v in obj.items()}
    return obj


def _tree_from_numpy(obj):
    if (isinstance(obj, tuple) and len(obj) == 2
            and obj[0] == "__pt_tensor__"):
        return _wrap_out(obj[1])
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_from_numpy(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_from_numpy(v) for k, v in obj.items()}
    return obj


def default_collate_fn(batch):
    """Stack samples into batch tensors (paddle default_collate parity)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return _wrap_out(np.stack([s.numpy() for s in batch]))
    if isinstance(sample, np.ndarray):
        return _wrap_out(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return _wrap_out(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return _wrap_out(np.asarray(batch, np.float32))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(default_collate_fn(list(s)) for s in transposed)
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch])
                for k in sample}
    return batch


@contextlib.contextmanager
def _worker_environ():
    """Environment a worker process starts with: JAX held to the CPU.
    A worker re-imports this package (and may build Tensors while
    collating); the chip belongs to the trainer that owns the loader,
    and a second process reaching for it fails or hangs. The variable
    must be in place before the child's first import, so it is set
    around ``Process.start()`` — the child snapshots ``os.environ``
    there — and restored (the parent's own JAX read it long ago)."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["JAX_PLATFORMS"]
        else:
            os.environ["JAX_PLATFORMS"] = prev


def _spawn_worker_main(w, n, shm_name, capacity, loader):
    """Entry point of a spawned DataLoader worker: open the parent's shm
    ring and stream this worker's share of batches into it. Runs in a
    fresh interpreter (spawn), so no inherited JAX locks, under
    ``_worker_environ`` (never on the trainer's chip)."""
    from ..native import ShmChannel
    channel = ShmChannel(shm_name, capacity=capacity, create=False)
    code = 0
    try:
        global _worker_info
        _worker_info = _WorkerInfo(w, n, loader.dataset)
        if loader.worker_init_fn is not None:
            loader.worker_init_fn(w)
        if loader.batch_sampler is not None and not loader._iterable_ds:
            # map-style: skip foreign batches BEFORE touching the
            # dataset (no wasted decode)
            def my_batches():
                for b, idxs in enumerate(loader.batch_sampler):
                    if b % n == w:
                        yield loader.collate_fn(
                            [loader.dataset[i] for i in idxs])
            it = my_batches()
        elif loader._iterable_ds:
            # iterable: sharding is the dataset's job via
            # get_worker_info() (torch/paddle semantics); an extra b%n
            # filter here would drop data from datasets that DO shard
            it = loader._raw_iter()
        else:
            it = (item for b, item in enumerate(loader._raw_iter())
                  if b % n == w)
        for item in it:
            channel.put(("ok", _tree_to_numpy(item)),
                        timeout=loader.timeout)
    except BaseException:
        code = 1
        try:
            channel.put(("error", traceback.format_exc()),
                        timeout=loader.timeout)
        except BaseException:
            pass
    finally:
        channel.close_write()
        os._exit(code)  # skip atexit/teardown in the worker


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(2, prefetch_factor)
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self._iterable_ds = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", None)
        elif self._iterable_ds:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        else:
            self.batch_size = batch_size
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last) if batch_size is not None else None

    def __len__(self):
        if self.batch_sampler is not None:
            return len(self.batch_sampler)
        raise TypeError("DataLoader over IterableDataset has no len()")

    def _raw_iter(self):
        if self._iterable_ds:
            if self.batch_size is None:
                for item in self.dataset:
                    yield item
                return
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not getattr(self, "drop_last", False):
                yield self.collate_fn(batch)
            return
        if self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield self.dataset[i]
            return
        for indices in self.batch_sampler:
            batch = [self.dataset[i] for i in indices]
            yield self.collate_fn(batch)

    def _mp_iter(self):
        """Spawned worker processes push collated batches through the
        native shm ring. Worker w owns batches w, w+n, w+2n…, so the
        parent preserves sampler order by round-robin popping.

        spawn (not fork): the parent runs multithreaded JAX, and a
        forked child inheriting its mutexes can deadlock (jax itself
        warns on fork). spawn re-imports in a clean child; the loader
        state (dataset/sampler/collate_fn) rides over by pickle — if it
        is unpicklable, fall back to the threaded prefetcher."""
        import multiprocessing as _mp
        from ..native import ShmChannel
        n = self.num_workers
        uid = uuid.uuid4().hex[:8]
        cap = int(os.environ.get("FLAGS_dataloader_shm_size",
                                 64 * 1024 * 1024))
        names = [f"/ptdl_{os.getpid()}_{uid}_{i}" for i in range(n)]
        channels = [ShmChannel(nm, capacity=cap, create=True)
                    for nm in names]
        # spawn is the safe default (forking a multithreaded JAX parent
        # can deadlock) but requires __main__ guards + picklable state;
        # scripts that relied on fork semantics can flip the flag
        from ..base_flags import get_flag
        method = get_flag("FLAGS_dataloader_start_method", "spawn")
        ctx = _mp.get_context(method)
        procs = []
        try:
            try:
                for w in range(n):
                    p = ctx.Process(
                        target=_spawn_worker_main,
                        args=(w, n, names[w], cap, self), daemon=True)
                    with _worker_environ():
                        p.start()  # pickles args here
                    procs.append(p)
            except Exception as exc:
                import warnings
                warnings.warn(
                    f"DataLoader: could not spawn workers ({exc!r}); "
                    "falling back to threaded prefetching. Make the "
                    "dataset/sampler/collate_fn picklable to enable "
                    "multiprocess loading.")
                for pr in procs:
                    pr.terminate()
                for ch in channels:
                    ch.close_write()
                    ch.close()
                channels = []
                yield from self._threaded_iter()
                return

            def _alive(i):
                return procs[i].is_alive()

            done = [False] * n
            w = 0
            while not all(done):
                if done[w]:
                    w = (w + 1) % n
                    continue
                # poll in 1s slices so a SIGKILLed worker (which never
                # reaches close_write) is detected instead of hanging
                deadline = (time.monotonic() + self.timeout
                            if self.timeout else None)
                while True:
                    try:
                        kind, payload = channels[w].get(timeout=1.0)
                        break
                    except TimeoutError:
                        if not _alive(w):
                            try:  # a final racing message may exist
                                kind, payload = channels[w].get(
                                    timeout=0.05)
                                break
                            except (TimeoutError, EOFError):
                                raise RuntimeError(
                                    f"DataLoader worker {w} (pid "
                                    f"{procs[w].pid}, exitcode "
                                    f"{procs[w].exitcode}) exited "
                                    "unexpectedly")
                        if (deadline is not None
                                and time.monotonic() > deadline):
                            raise TimeoutError(
                                f"DataLoader worker {w} produced no "
                                f"batch within {self.timeout}s")
                    except EOFError:
                        kind = "eof"
                        break
                if kind == "eof":
                    done[w] = True
                    w = (w + 1) % n
                    continue
                if kind == "error":
                    raise RuntimeError(
                        f"DataLoader worker {w} failed:\n{payload}")
                yield _tree_from_numpy(payload)
                w = (w + 1) % n
        finally:
            # unblock workers parked in push BEFORE reaping, then a
            # bounded join so early loop exit leaves no zombies
            for ch in channels:
                ch.close_write()
            for pr in procs:
                pr.join(timeout=5)
                if pr.is_alive():
                    pr.terminate()
                    pr.join(timeout=1)
            for ch in channels:
                ch.close()

    def __iter__(self):
        if self.num_workers == 0:
            yield from self._raw_iter()
            return
        if self.use_shared_memory:
            from .. import native
            if native.is_available():
                yield from self._mp_iter()
                return
        yield from self._threaded_iter()

    def _threaded_iter(self):
        # threaded prefetch: decode-ahead while the device runs
        q: "queue.Queue" = queue.Queue(
            maxsize=self.prefetch_factor * max(1, self.num_workers))
        sentinel = object()
        err_holder = []

        def producer():
            global _worker_info
            # single producer thread IS the whole worker pool here — a
            # worker_info-sharding dataset must see 1 worker, not 1-of-n
            _worker_info = _WorkerInfo(0, 1, self.dataset)
            try:
                for item in self._raw_iter():
                    q.put(item)
            except BaseException as e:  # propagate to consumer
                err_holder.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        if err_holder:
            raise err_holder[0]
