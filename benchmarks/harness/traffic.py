"""The one traffic generator.  A traffic file is parameters; this turns
them and a seed into a host dataset and a feed of device batches.

Traffic file keys:

* ``rows_per_chip``: rows of one step on one chip;
* ``dataset_rows_per_chip``: rows of the host dataset per chip (an epoch is
  ``dataset_rows_per_chip / rows_per_chip`` steps);
* ``arrays``: one entry per array of a row, in the order the
  configuration's loss takes them: ``name``, ``shape`` (of one row),
  ``dtype``, and ``low``/``high`` of a uniform integer draw, either a
  number or the name of a key in the configuration's file;
* ``items_per_row`` and ``rate_metric``: what a row counts as (tokens,
  images) and the end-to-end metric that rate is reported under.

The dataset is shuffled each epoch through the program's ``ShardedLoader``
at its default prefetch.  Every seed gives the same sizes; only the values
and the order differ.
"""

from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np


def _bound(value, cfg: dict) -> int:
    return int(cfg[value]) if isinstance(value, str) else int(value)


def dataset(mix: dict, cfg: dict, chips: int, seed: int
            ) -> List[np.ndarray]:
    rows = int(mix["dataset_rows_per_chip"]) * chips
    rng = np.random.default_rng(seed)
    out = []
    for spec in mix["arrays"]:
        shape = (rows, *spec["shape"])
        dtype = np.dtype(spec["dtype"])
        low, high = _bound(spec["low"], cfg), _bound(spec["high"], cfg)
        if dtype == np.uint8 and (low, high) == (0, 256):
            # whole random bytes: several times faster than a bounded draw
            a = np.frombuffer(rng.bytes(int(np.prod(shape))),
                              np.uint8).reshape(shape)
        else:
            a = rng.integers(low, high, size=shape, dtype=dtype)
        out.append(a)
    return out


def batches(mix: dict, arrays: List[np.ndarray], seed: int, annotate
            ) -> Iterator[Tuple]:
    """Endless device batches ``(array, ...)`` in the traffic's ``arrays``
    order.  ``annotate(kind)`` is a context manager that records a host
    span; an epoch's restart is recorded as ``epoch_turnover``."""
    from horovod_tpu.data.loader import ShardedLoader

    loader = ShardedLoader(
        *arrays, batch_size=int(mix["rows_per_chip"]), shuffle=True,
        seed=seed % (2 ** 32), drop_remainder=True)
    it = iter(loader)
    while True:
        try:
            batch = next(it)
        except StopIteration:
            with annotate("epoch_turnover"):
                it = iter(loader)
                batch = next(it)
        yield batch[:-1]  # the last is the Join mask: every row is real
