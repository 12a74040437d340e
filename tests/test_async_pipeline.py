"""Async host pipeline: the trailing loss fetch (training.py
TrailingLossFetcher + HVD_LOSS_FETCH_STEPS) and the device prefetch
loader (data/loader.py prefetch_to_device) — the step-path honesty-sync
fix and the loader overlap."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.data.loader import ShardedLoader, prefetch_to_device
from horovod_tpu.models.mlp import MLP
from horovod_tpu.training import (
    TrailingLossFetcher, init_train_state, make_train_step, shard_batch,
)


# ---------------------------------------------------------------------------
# TrailingLossFetcher
# ---------------------------------------------------------------------------
def test_fetcher_trails_by_cadence():
    f = TrailingLossFetcher(every=3)
    for i in range(1, 13):
        f.push(jnp.asarray(float(i)))
    # retained at steps 3,6,9,12; fetched one cadence behind: step 9
    assert f.step == 9 and f.value == 9.0
    assert f.flush() == 12.0


def test_fetcher_disabled_at_zero():
    f = TrailingLossFetcher(every=0)
    for i in range(5):
        f.push(jnp.asarray(1.0))
    assert f.value is None and f.flush() is None


def _mlp_step(rng, **mk):
    model = MLP(features=(16, 4))
    opt = optax.sgd(0.05)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    step = make_train_step(
        apply_fn=lambda v, a, train=True: model.apply(v, a),
        loss_fn=loss_fn, optimizer=opt, donate=False, **mk)
    state = init_train_state(model, opt, jnp.zeros((2, 8)))
    x = shard_batch(rng.normal(size=(16, 8)).astype(np.float32))
    y = shard_batch(rng.integers(0, 4, size=(16,)).astype(np.int32))
    return step, state, x, y


def test_step_path_fetches_on_cadence_not_per_step(hvd_init, rng,
                                                   monkeypatch):
    """The satellite pin: the hot path must not device_get every step —
    only the trailing cadence fetch (and it is N steps behind, so the
    dispatch pipeline never drains).  The tuners' measuring windows
    keep their own forced syncs (test_profile_guided pins those)."""
    import horovod_tpu.training as training

    step, state, x, y = _mlp_step(rng, loss_fetch_steps=4)
    assert step.loss_fetcher.every == 4
    state, _ = step(state, x, y)        # compile outside the count

    gets = []
    real = jax.device_get
    monkeypatch.setattr(training.jax, "device_get",
                        lambda v: gets.append(1) or real(v))
    for _ in range(12):
        state, _ = step(state, x, y)
    # steps 2..13: retained at 4,8,12 → fetched at 8 (handle from 4)
    # and 12 (handle from 8): exactly 2 trailing fetches, 0 per-step
    assert len(gets) == 2
    assert step.loss_fetcher.value is not None
    assert np.isfinite(step.loss_fetcher.value)
    assert step.loss_fetcher.step == 8


def test_fetcher_exports_train_loss_gauge(hvd_init, rng):
    from horovod_tpu import metrics

    step, state, x, y = _mlp_step(rng, loss_fetch_steps=2)
    for _ in range(5):
        state, _ = step(state, x, y)
    assert metrics.TRAIN_LOSS.get() == pytest.approx(
        step.loss_fetcher.value)


# ---------------------------------------------------------------------------
# prefetch_to_device
# ---------------------------------------------------------------------------
def test_loader_yields_device_resident_batches(hvd_init, rng):
    """The regression pin: every yielded column is already a committed
    jax.Array laid out over the mesh (dim 0 split across ranks) — the
    H2D copy was dispatched by the producer thread, not by the step."""
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = rng.integers(0, 3, size=(32,)).astype(np.int32)
    loader = ShardedLoader(x, y, batch_size=2, prefetch=2)
    batches = list(loader)
    assert len(batches) == len(loader) == 2
    for xs, ys, active in batches:
        for col in (xs, ys, active):
            assert isinstance(col, jax.Array)
            assert len(col.sharding.device_set) == hvd.size()


def test_prefetch_preserves_order_and_tail(hvd_init, rng):
    """Prefetched iteration is element-wise identical to synchronous
    iteration, including the padded Join tail and the active mask."""
    x = np.arange(2 * 19, dtype=np.float32).reshape(19, 2)
    a = list(ShardedLoader(x, batch_size=1, prefetch=0))
    b = list(ShardedLoader(x, batch_size=1, prefetch=3))
    assert len(a) == len(b)
    for (xa, aa), (xb, ab) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(aa), np.asarray(ab))


def test_prefetch_releases_producer_on_early_exit():
    """A consumer that stops early (break / exception / generator
    close) must release the producer thread — a producer blocked
    forever on the full queue would leak the thread and pin its staged
    device-resident batches."""
    import threading

    def endless():
        i = 0
        while True:
            yield i
            i += 1

    before = {t for t in threading.enumerate()
              if t.name == "hvd-prefetch"}
    it = prefetch_to_device(endless(), 2)
    assert next(it) == 0
    it.close()                          # what a `break` triggers at GC
    deadline = time.time() + 5.0
    while time.time() < deadline:
        alive = {t for t in threading.enumerate()
                 if t.name == "hvd-prefetch"} - before
        if not any(t.is_alive() for t in alive):
            break
        time.sleep(0.05)
    assert not any(t.is_alive() for t in alive), alive


def test_prefetch_propagates_producer_exception():
    def bad():
        yield 1
        raise RuntimeError("host pipeline died")

    it = prefetch_to_device(bad(), 2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="host pipeline died"):
        list(it)


def test_prefetch_runs_ahead_of_consumer():
    """Depth-2 prefetch keeps 2 items staged while the consumer holds
    the first — the double-buffering contract, asserted on the
    producer's progress rather than wall time."""
    produced = []

    def source():
        for i in range(6):
            produced.append(i)
            yield i

    it = prefetch_to_device(source(), 2)
    first = next(it)
    assert first == 0
    deadline = time.time() + 5.0
    # producer should stage depth(2) + 1 in-flight beyond the consumed one
    while len(produced) < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert len(produced) >= 3
    assert list(it) == [1, 2, 3, 4, 5]


@pytest.mark.slow
def test_injected_slow_host_no_longer_stalls_consumer():
    """The satellite's injected-slow-host pin: with a 20 ms/batch host
    delay and a 20 ms/batch consumer, depth-2 prefetch overlaps the two
    (≈ max instead of sum).  Generous margin — tier-1 machines are
    noisy."""
    delay, n = 0.02, 10

    def slow_source():
        for i in range(n):
            time.sleep(delay)
            yield i

    def consume(it):
        t0 = time.perf_counter()
        for _ in it:
            time.sleep(delay)
        return time.perf_counter() - t0

    serial = consume(slow_source())
    overlapped = consume(prefetch_to_device(slow_source(), 2))
    assert overlapped < serial * 0.8, (overlapped, serial)


def test_prefetch_replaces_batches_staged_over_retired_mesh(hvd_init, rng):
    """An elastic membership epoch landing while batches sit in the
    prefetch queue must not hand the step buffers placed over the
    retired mesh: the loader re-places stale-epoch batches from its
    retained host columns (same values, fresh placement)."""
    from horovod_tpu import core

    x = rng.normal(size=(32, 4)).astype(np.float32)
    loader = ShardedLoader(x, batch_size=2, prefetch=2)
    it = iter(loader)
    first = next(it)
    st = core._require_init()
    st.epoch += 1                       # what core.reinit does
    try:
        rest = list(it)
    finally:
        st.epoch -= 1
    got = [first] + rest
    want = list(ShardedLoader(x, batch_size=2, prefetch=0))
    assert len(got) == len(want)
    for (xa, aa), (xb, ab) in zip(got, want):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
        np.testing.assert_array_equal(np.asarray(aa), np.asarray(ab))
        assert len(xa.sharding.device_set) == hvd.size()


def test_training_consumes_prefetched_loader(hvd_init, rng):
    """End to end: a train loop over a prefetched ShardedLoader (the
    optimized data path) reaches the same losses as the synchronous
    one."""
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(32,)).astype(np.int32)

    def run(prefetch):
        model = MLP(features=(16, 4))
        opt = optax.sgd(0.05)

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        step = make_train_step(
            apply_fn=lambda v, a, train=True: model.apply(v, a),
            loss_fn=loss_fn, optimizer=opt, donate=False)
        state = init_train_state(model, opt, jnp.zeros((2, 8)))
        losses = []
        for epoch in range(2):
            loader = ShardedLoader(x, y, batch_size=4, prefetch=prefetch)
            for xs, ys, _active in loader:
                state, loss = step(state, xs, ys)
                losses.append(float(np.asarray(jax.device_get(loss))))
        return losses

    np.testing.assert_allclose(run(0), run(2), rtol=1e-6)