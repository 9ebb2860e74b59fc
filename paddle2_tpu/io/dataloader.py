"""Datasets, samplers, DataLoader (python/paddle/io/ parity).

The reference's multiprocess worker pool over shared memory
(dataloader/dataloader_iter.py:368,448) maps to a thread pool + prefetch
queue here: workers produce numpy batches (GIL released in numpy/IO), the
main thread uploads to HBM — the standard input pipeline shape for TPU
hosts. num_workers>0 enables the pool; 0 is synchronous.
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Any, Callable, Iterable, List, Optional, Sequence, Union

import numpy as np

from ..framework.tensor import Tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
           "ChainDataset", "ConcatDataset", "Subset", "random_split",
           "Sampler", "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
           "BatchSampler", "DistributedBatchSampler", "SubsetRandomSampler",
           "DataLoader", "default_collate_fn", "get_worker_info"]


# ---------------------------------------------------------------------------
# datasets
# ---------------------------------------------------------------------------

class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
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
        return self.cum[-1]

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
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        sizes = [int(np.floor(n * l)) for l in lengths]
        for i in range(n - sum(sizes)):
            sizes[i % len(sizes)] += 1
        lengths = sizes
    if sum(lengths) != len(dataset):
        raise ValueError("sum of lengths != dataset size")
    perm = np.random.permutation(len(dataset)).tolist()
    out, offset = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[offset:offset + l]))
        offset += l
    return out


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------

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


class SubsetRandomSampler(Sampler):
    def __init__(self, indices):
        super().__init__(None)
        self.indices = list(indices)

    def __iter__(self):
        return iter(np.random.permutation(self.indices).tolist())

    def __len__(self):
        return len(self.indices)


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        super().__init__(None)
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
        super().__init__(dataset)
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)
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
    """Rank-sharded batches (python/paddle/io/dataloader/batch_sampler.py
    DistributedBatchSampler parity)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import env as dist_env
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = (num_replicas if num_replicas is not None
                       else dist_env.get_world_size())
        self.local_rank = rank if rank is not None else dist_env.get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        indices = np.arange(len(self.dataset))
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices = np.concatenate(
            [indices, indices[: self.total_size - len(indices)]])
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices.tolist():
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


# ---------------------------------------------------------------------------
# collate + loader
# ---------------------------------------------------------------------------

def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(s._data) for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, np.float32))
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    if isinstance(sample, (tuple, list)):
        return type(sample)(default_collate_fn(list(items))
                            for items in zip(*batch))
    raise TypeError(f"cannot collate {type(sample)}")


class _WorkerInfo:
    def __init__(self, id_, num_workers, dataset):
        self.id = id_
        self.num_workers = num_workers
        self.dataset = dataset


_worker_tls = threading.local()


def get_worker_info():
    return getattr(_worker_tls, "info", None)


class _SyncIter:
    """num_workers=0 path, tracked: exposes the emitted-batch cursor
    (``next_emit``) that DataLoader.state_dict reads for exact resume."""

    def __init__(self, loader, batches):
        self.loader = loader
        self.batches = batches
        self.next_emit = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self.next_emit >= len(self.batches):
            self.loader._note_epoch_end(self)
            raise StopIteration
        batch = self.loader._fetch(self.batches[self.next_emit])
        self.next_emit += 1
        return batch


class _PrefetchIter:
    """Thread-pool prefetcher: ordered batch delivery, bounded queue."""

    def __init__(self, loader, index_iter):
        self.loader = loader
        self.index_iter = enumerate(index_iter)
        self.results: dict = {}
        self.next_emit = 0
        self.next_submit = 0
        self.lock = threading.Lock()
        self.done = False
        self.sem = threading.Semaphore(0)
        self.error = None
        n = loader.num_workers
        self.threads = [threading.Thread(target=self._worker, args=(i,),
                                         daemon=True) for i in range(n)]
        for t in self.threads:
            t.start()

    def _worker(self, wid):
        _worker_tls.info = _WorkerInfo(wid, self.loader.num_workers,
                                       self.loader.dataset)
        while True:
            with self.lock:
                if self.error is not None or self.done:
                    return
                try:
                    i, indices = next(self.index_iter)
                except StopIteration:
                    self.done = True
                    self.sem.release()
                    return
            try:
                batch = self.loader._fetch(indices)
            except BaseException as e:  # propagate to main thread
                with self.lock:
                    self.error = e
                self.sem.release()
                return
            with self.lock:
                self.results[i] = batch
            self.sem.release()

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            with self.lock:
                if self.error is not None:
                    raise self.error
                if self.next_emit in self.results:
                    batch = self.results.pop(self.next_emit)
                    self.next_emit += 1
                    return batch
                if self.done and not self.results and all(
                        not t.is_alive() for t in self.threads):
                    self.loader._note_epoch_end(self)
                    raise StopIteration
            self.sem.acquire(timeout=1.0)


class DataLoader:
    """python/paddle/io/reader.py:262 parity, plus EXACT-RESUME state:
    ``state_dict()`` captures the in-flight epoch (the materialized batch
    index sequence — shuffle already applied — the emitted-batch cursor,
    the sampler epoch, and the numpy RNG state) and
    ``load_state_dict()`` arms the next ``__iter__`` to continue at the
    exact next batch with no replay and no skip. Register the loader
    with ``fault_tolerance.CheckpointManager.register_stateful`` so a
    preempt/rollback resumes the data stream with the model."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, worker_restarts=2):
        self.dataset = dataset
        self.num_workers = max(0, num_workers)
        self.collate_fn = collate_fn or default_collate_fn
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        # restart budget per shm worker before the iterator escalates
        # a crashed worker to the step-level retry loop
        self.worker_restarts = max(0, int(worker_restarts))
        self._iterable_mode = isinstance(dataset, IterableDataset)
        self._epoch = 0
        self._active = None      # (epoch batch list, start, live iterator)
        self._resume = None      # armed by load_state_dict
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        elif self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
            self.batch_size = batch_size

    def _fetch(self, indices):
        samples = [self.dataset[i] for i in indices]
        return self.collate_fn(samples)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def __iter__(self):
        if self._iterable_mode:
            return self._iter_iterable()
        batches, start = self._epoch_plan()
        remaining = batches[start:]
        it = None
        if self.num_workers == 0:
            it = _SyncIter(self, remaining)
        elif self.use_shared_memory and \
                self.collate_fn is default_collate_fn:
            # multiprocess + C++ shm ring: Python decode escapes the GIL
            # (reference dataloader_iter.py:368 design). The ring is
            # built from shm_ring.cpp on first use; a build or shm
            # failure raises here — use_shared_memory=False is the way
            # to ask for the thread prefetcher
            from .shm_loader import ShmProcessIter
            it = ShmProcessIter(self, remaining)
        if it is None:
            it = _PrefetchIter(self, iter(remaining))
        self._active = (batches, start, it)
        return it

    def _epoch_plan(self):
        """Batch index sequence for the epoch about to start, plus the
        cursor to resume from (0 unless load_state_dict armed one)."""
        if self._resume is not None:
            st, self._resume = self._resume, None
            return [list(b) for b in st["batches"]], int(st["cursor"])
        return [list(b) for b in self.batch_sampler], 0

    def _note_epoch_end(self, it):
        if self._active is not None and self._active[2] is it:
            self._active = None
            self._epoch += 1

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not getattr(self, "drop_last", False):
            yield self.collate_fn(batch)

    # -- resumable-pipeline state ---------------------------------------
    def state_dict(self):
        """Snapshot the data stream position. Mid-epoch, the in-flight
        epoch's exact batch sequence (shuffle RNG already applied) and
        the emitted-batch cursor are captured, so a restore yields the
        REMAINING batches only — no duplicates, no gaps; prefetched but
        not-yet-emitted batches are re-decoded, never re-trained. The
        numpy RNG state rides along so every SUBSEQUENT epoch's shuffle
        also replays identically."""
        if self._iterable_mode:
            raise TypeError(
                "IterableDataset pipelines stream without an index "
                "order, so DataLoader.state_dict() cannot capture an "
                "exact cursor; give the dataset itself "
                "state_dict/load_state_dict and register it directly")
        state = {"version": 1, "epoch": self._epoch, "cursor": 0,
                 "batches": None,
                 "sampler_epoch": getattr(self.batch_sampler, "epoch",
                                          None),
                 "np_rng_state": np.random.get_state()}
        if self._active is not None:
            batches, start, it = self._active
            state["cursor"] = start + int(it.next_emit)
            state["batches"] = [list(b) for b in batches]
        elif self._resume is not None:   # saved again before iterating
            state["cursor"] = int(self._resume["cursor"])
            state["batches"] = [list(b) for b in self._resume["batches"]]
        return state

    def load_state_dict(self, state):
        if not isinstance(state, dict) or "epoch" not in state:
            raise ValueError("not a DataLoader state_dict")
        if int(state.get("version", 1)) != 1:
            raise ValueError(
                f"DataLoader state version {state.get('version')} is "
                f"newer than this runtime understands")
        self._epoch = int(state["epoch"])
        if state.get("np_rng_state") is not None:
            np.random.set_state(state["np_rng_state"])
        if state.get("sampler_epoch") is not None and \
                hasattr(self.batch_sampler, "set_epoch"):
            self.batch_sampler.set_epoch(int(state["sampler_epoch"]))
        batches, cursor = state.get("batches"), int(state.get("cursor", 0))
        if batches is not None and cursor < len(batches):
            self._resume = {"batches": batches, "cursor": cursor}
        else:
            self._resume = None
