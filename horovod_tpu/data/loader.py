"""Rank-sharded data loading with uneven-tail (Join) handling.

Analog of the fork's data loader shim (reference horovod/mxnet/dataloader.py
splits batches across ranks) plus the standard Horovod idiom of
``DistributedSampler``-style per-rank sharding; the uneven tail integrates
with Join semantics (elastic/join.py): the last partial global batch is
padded and accompanied by a per-rank ``active`` mask so
``join_allreduce`` divides by the true participant count — the compiled
analog of "rank r joined early" (reference controller.cc:253-264).
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Iterable, Iterator, List, Optional, Tuple

import numpy as np


from .. import core
from ..timeline.timeline import host_span
from ..training import shard_batch
from ..utils import env as env_util

_SENTINEL = object()


def prefetch_to_device(iterator: Iterable, depth: Optional[int] = None
                       ) -> Iterator:
    """Run ``iterator`` ``depth`` items ahead on a background thread so
    the device never waits on host-side batch assembly.

    The producer thread does the host work (index/pad/copy) AND the
    ``device_put`` dispatch — JAX transfers are async, so by the time
    the training loop pops a batch its H2D copy has been in flight for
    a full step.
    ``depth`` defaults to ``HVD_PREFETCH_DEPTH`` (2); 0 degrades to the
    plain synchronous iterator.  Item order is preserved (single
    producer, FIFO queue) and a producer exception re-raises at the
    consumer's next pull instead of killing a daemon thread silently.
    """
    if depth is None:
        depth = env_util.get_int(env_util.HVD_PREFETCH_DEPTH,
                                 env_util.DEFAULT_PREFETCH_DEPTH)
    if depth <= 0:
        yield from iterator
        return
    q: queue.Queue = queue.Queue(maxsize=int(depth))
    err: List[BaseException] = []
    stop = threading.Event()

    def _put(item) -> bool:
        """Bounded put that gives up once the consumer is gone — a
        producer blocked forever on a full queue would leak the thread
        AND pin its staged device-resident batches."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce():
        try:
            for item in iterator:
                if not _put(item):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at the consumer
            err.append(e)
        finally:
            _put(_SENTINEL)

    t = threading.Thread(target=_produce, name="hvd-prefetch", daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _SENTINEL:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        # consumer exited (break / exception / generator close): release
        # the producer and drop any staged batches so nothing stays
        # pinned on device
        stop.set()
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


def pad_tail(cols: List[np.ndarray], valid: int, batch_size: int,
             size: int) -> Tuple[List[np.ndarray], np.ndarray]:
    """THE Join-tail layout (single definition — ShardedLoader and the
    estimator's StoreLoader share it): zero-pad a partial global batch to
    ``batch_size * size`` rows, packing valid rows onto the lowest ranks,
    and return ``(cols, rows_per_rank)`` where ``rows_per_rank > 0`` is
    the active mask."""
    g = batch_size * size
    rows_per_rank = np.full((size,), batch_size, np.int32)
    if valid < g:
        full, rem = divmod(valid, batch_size)
        rows_per_rank = np.array(
            [batch_size] * full + ([rem] if rem else [])
            + [0] * (size - full - (1 if rem else 0)), np.int32,
        )
        pad = g - valid
        cols = [
            np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
            for a in cols
        ]
    return cols, rows_per_rank


class ShardedLoader:
    """Iterate (sharded_batch..., active_mask) over a host dataset.

    Each yield is a *global* batch of ``batch_size * size()`` rows placed
    so dim 0 is split across ranks.  When the data doesn't divide evenly,
    the final batch is zero-padded and ``active`` marks which ranks hold
    at least one real row (per-row validity is in ``valid_counts``).

    ``prefetch`` (default ``HVD_PREFETCH_DEPTH``, 2) keeps that many
    device-resident batches staged ahead of the training loop via
    :func:`prefetch_to_device`; 0 restores the synchronous iterator.
    """

    def __init__(self, *arrays: np.ndarray, batch_size: int,
                 shuffle: bool = False, seed: int = 0,
                 drop_remainder: bool = False,
                 prefetch: Optional[int] = None):
        assert arrays, "need at least one array"
        n = arrays[0].shape[0]
        assert all(a.shape[0] == n for a in arrays)
        self.arrays = [np.asarray(a) for a in arrays]
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.prefetch = prefetch
        self.n = n
        #: passes over the data started so far: the ``epoch`` argument of
        #: this loader's host spans
        self.epoch = 0

    def __len__(self) -> int:
        g = self.batch_size * core.size()
        return self.n // g if self.drop_remainder else -(-self.n // g)

    def __iter__(self) -> Iterator[Tuple]:
        # Host spans (docs/profiling.md), each with the pass and the
        # batch's index in it: hvd_loader_host_batch (index, gather, pad)
        # and hvd_loader_h2d (the device_put) on the hvd-prefetch thread,
        # hvd_loader_wait (the q.get that blocks) on the consumer's.
        self.epoch += 1
        epoch = self.epoch

        def span(kind, index):
            return host_span("loader_" + kind, cat="loader", epoch=epoch,
                             batch=index)

        def produce():
            host = self._iterate_host()
            for index in itertools.count():
                with span("host_batch", index):
                    item = next(host, None)
                if item is None:
                    return
                cols, rows_per_rank = item
                with span("h2d", index):
                    shards = tuple(shard_batch(a) for a in cols)
                    active = shard_batch(rows_per_rank > 0)
                yield (core._require_init().epoch, cols, rows_per_rank,
                       shards, active)

        staged = prefetch_to_device(produce(), self.prefetch)
        for index in itertools.count():
            with span("wait", index):
                item = next(staged, None)
            if item is None:
                return
            mesh_epoch, cols, rpr, shards, active = item
            if mesh_epoch != core._require_init().epoch:
                # staged over a retired mesh: an elastic membership
                # epoch landed while this batch sat in the prefetch
                # queue, so its device placement names devices that may
                # be gone.  Re-place from the retained host columns —
                # one synchronous device_put per epoch flip, not a
                # silent skipped batch.  (A world-SIZE change still
                # needs the caller to restart its epoch iteration: the
                # Join-tail layout is per-size, like the train state
                # rebuild elastic loops already do.)
                shards = tuple(shard_batch(a) for a in cols)
                active = shard_batch(rpr > 0)
            yield (*shards, active)

    def _iterate_host(self) -> Iterator[Tuple[List[np.ndarray], np.ndarray]]:
        """Host-side batch assembly only (index + Join-tail pad) —
        placement happens in the prefetch producer so the H2D copy
        overlaps compute."""
        size = core.size()
        g = self.batch_size * size
        idx = np.arange(self.n)
        if self.shuffle:
            # same permutation on every controller: seeded, not entropy-based
            np.random.default_rng(self.seed).shuffle(idx)
            self.seed += 1
        stop = (self.n // g) * g if self.drop_remainder else self.n
        for start in range(0, stop, g):
            take = idx[start: start + g]
            valid = take.shape[0]
            yield pad_tail(
                [a[take] for a in self.arrays], valid, self.batch_size,
                size,
            )
