"""The 56×56-stage experiment: Pallas residual-add kernel vs
XLA's elementwise fusion (VERDICT round-2 item 7 — "run the named
experiment ... or demonstrate it loses and close the question with
numbers").

Two measurements on the real chip, interleaved in one process (the
comparison that survives run-to-run drift):

  (a) standalone: relu(x + y) on the 56×56-stage activation shape
      [128, 56, 56, 256] bf16 — Pallas single pass vs jitted XLA;
  (b) end-to-end: the ResNet-50 train step (batch 128, 10 in-graph
      steps, the bench.py configuration) with residual_join="pallas"
      vs the default — i.e. does hand-placing the join help or does it
      just break XLA's surrounding fusions.

Usage: python scripts/pallas_residual_experiment.py [--batch 128]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models.resnet import ResNet50
from horovod_tpu.ops.elementwise import residual_relu
from horovod_tpu.training import init_train_state, make_train_step


def _sync(out):
    leaf = jax.tree_util.tree_leaves(out)[-1]
    np.asarray(jax.device_get(leaf.sum() if leaf.ndim else leaf))


def timeit(fn, *args, n=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n


def micro(batch: int):
    shape = (batch, 56, 56, 256)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    xla = jax.jit(lambda a, b: jax.nn.relu(a + b))
    pal = jax.jit(lambda a, b: residual_relu(a, b))

    np.testing.assert_allclose(
        np.asarray(pal(x, y), np.float32),
        np.asarray(xla(x, y), np.float32),
    )
    # interleave 3 rounds, take the min (shared chip)
    t_xla, t_pal = [], []
    for _ in range(3):
        t_xla.append(timeit(xla, x, y))
        t_pal.append(timeit(pal, x, y))
    nbytes = 3 * np.prod(shape) * 2  # 2 reads + 1 write, bf16
    print(f"standalone relu(x+y) {shape} bf16:")
    print(f"  xla    {min(t_xla) * 1e3:7.3f} ms  "
          f"({nbytes / min(t_xla) / 1e9:.0f} GB/s effective)")
    print(f"  pallas {min(t_pal) * 1e3:7.3f} ms  "
          f"({nbytes / min(t_pal) / 1e9:.0f} GB/s effective)")
    return min(t_xla), min(t_pal)


def end_to_end(batch: int, in_graph_steps: int = 10):
    results = {}
    rng = np.random.default_rng(42)
    data = jnp.asarray(
        rng.uniform(size=(batch, 224, 224, 3)), jnp.float32)
    target = jnp.asarray(
        rng.integers(0, 1000, size=(batch,)), jnp.int32)

    def build(join):
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         residual_join=join)
        opt = optax.sgd(0.01, momentum=0.9)

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()

        state = init_train_state(
            model, opt, jnp.zeros((2, 224, 224, 3)), has_batch_stats=True,
        )
        step = make_train_step(
            apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
            has_batch_stats=True, in_graph_steps=in_graph_steps,
        )
        return state, step

    steps = {j: build(j) for j in ("xla", "pallas")}
    for j, (state, step) in steps.items():  # compile both first
        state, loss = step(state, data, target)
        _sync(loss)
        steps[j] = (state, step)

    for _ in range(3):  # interleaved rounds
        for j, (state, step) in steps.items():
            t0 = time.perf_counter()
            for _ in range(2):
                state, loss = step(state, data, target)
            _sync(loss)
            dt = (time.perf_counter() - t0) / (2 * in_graph_steps)
            results.setdefault(j, []).append(dt)
            steps[j] = (state, step)

    for j, ts in results.items():
        best = min(ts)
        print(f"end-to-end train step ({j:6s}): {best * 1e3:6.2f} ms/step"
              f"  = {batch / best:7.1f} img/s")
    return {j: min(ts) for j, ts in results.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--skip-micro", action="store_true")
    ap.add_argument("--skip-e2e", action="store_true")
    args = ap.parse_args()
    hvd.init()
    print(f"devices: {jax.devices()}")
    if not args.skip_micro:
        micro(args.batch)
    if not args.skip_e2e:
        end_to_end(args.batch)


if __name__ == "__main__":
    main()
