"""On-chip A/B for the Pallas conv+BN kernels (round-4 VERDICT #1a).

Measures ops/conv_bn.py against XLA's fused equivalents on the real
chip, interleaved in one process (the shared chip fluctuates ~2x between
runs; interleaving + min-of-N is the reliable comparison — same
methodology as scripts/pallas_residual_experiment.py).  Two shapes from
the HBM-bound 56x56 ResNet-50 stage:

* the bottleneck 3x3 at C=64 ([B, 56, 56, 64] -> 64), and
* a C=256 wide variant ([B, 56, 56, 256] -> 256) for lane-width contrast
  (C=64 leaves half the 128-lane MXU idle; C=256 fills it).

Variants: fused conv+BN-apply+ReLU (inference/apply half) and
conv+stats epilogue (training half).  Writes
scripts/out/conv_bn_experiment.json; verdict goes to the root PERF.md.

Usage: python scripts/pallas_conv_bn_experiment.py [--batch 128]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.ops.conv_bn import (
    conv3x3_bn_relu, conv3x3_stats, xla_conv3x3_bn_relu, xla_conv3x3_stats,
)


def _sync(out):
    leaf = jax.tree_util.tree_leaves(out)[-1]
    np.asarray(jax.device_get(leaf.sum() if leaf.ndim else leaf))


def best_ms(fn, *args, n=5, inner=3):
    out = fn(*args)
    _sync(out)  # compile + warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        _sync(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best * 1e3


# ops per timed call, chained in-graph (carry feeds the next iteration):
# per-dispatch host latency would otherwise dominate a sub-ms kernel and
# the A/B would measure dispatch, not the kernels
_K = 16


def _loop_apply(fn):
    @jax.jit
    def looped(x, w, scale, bias):
        return jax.lax.fori_loop(
            0, _K, lambda i, y: fn(y, w, scale, bias), x)

    return looped


def _loop_stats(fn):
    @jax.jit
    def looped(x, w):
        def body(i, carry):
            y, s, sq = carry
            y2, s2, sq2 = fn(y, w)
            return y2, s + s2, sq + sq2

        c = y0, s0, sq0 = (x, jnp.zeros((x.shape[3],), jnp.float32),
                           jnp.zeros((x.shape[3],), jnp.float32))
        return jax.lax.fori_loop(0, _K, body, c)

    return looped


def run_shape(batch: int, c: int) -> list:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 56, 56, c)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 3, c, c)) * 0.05, jnp.bfloat16)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, size=(c,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(c,)), jnp.float32)

    flops = 2 * batch * 56 * 56 * 9 * c * c  # conv MACs x2, per op

    # correctness on-chip before timing anything
    got = np.asarray(jax.jit(conv3x3_bn_relu)(x, w, scale, bias),
                     np.float32)
    want = np.asarray(jax.jit(xla_conv3x3_bn_relu)(x, w, scale, bias),
                      np.float32)
    np.testing.assert_allclose(got, want, rtol=0.1, atol=0.15)

    rows = []
    # interleave A/B inside each variant
    for name, a_fn, a_args, b_fn, b_args in [
        ("conv+bn_apply+relu",
         _loop_apply(xla_conv3x3_bn_relu), (x, w, scale, bias),
         _loop_apply(conv3x3_bn_relu), (x, w, scale, bias)),
        ("conv+stats_epilogue",
         _loop_stats(xla_conv3x3_stats), (x, w),
         _loop_stats(conv3x3_stats), (x, w)),
    ]:
        # symmetric A/B/A/B interleave: both sides get two windows, min
        # of each — the shared chip drifts ~2x between windows and an
        # asymmetric schedule (A B A) biases whichever side got two
        a1 = best_ms(a_fn, *a_args)
        b1 = best_ms(b_fn, *b_args)
        a2 = best_ms(a_fn, *a_args)
        b2 = best_ms(b_fn, *b_args)
        xla_best = min(a1, a2) / _K
        pl_best = min(b1, b2) / _K
        rows.append({
            "shape": f"[{batch},56,56,{c}]x{c}",
            "variant": name,
            "xla_ms": xla_best,
            "pallas_ms": pl_best,
            "xla_tflops": flops / xla_best / 1e9,
            "pallas_tflops": flops / pl_best / 1e9,
            "pallas_vs_xla": xla_best / pl_best,
        })
        print(f"{rows[-1]['shape']} {name}: XLA {xla_best:.2f} ms "
              f"({rows[-1]['xla_tflops']:.1f} TF), Pallas {pl_best:.2f} ms "
              f"({rows[-1]['pallas_tflops']:.1f} TF)  -> "
              f"{rows[-1]['pallas_vs_xla']:.2f}x", flush=True)
    return rows


def run_end_to_end(batch: int = 128, k_steps: int = 10) -> list:
    """Interleaved ResNet-50 train-step A/B: conv_bn='xla' vs 'pallas'
    (the fused 3x3+BN+ReLU in every stride-1 bottleneck).  Same harness
    as bench.py (K in-graph steps via lax.scan)."""
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.resnet import ResNet50
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    rng = np.random.default_rng(42)
    x = shard_batch(
        rng.uniform(size=(batch, 224, 224, 3)).astype(np.float32))
    y = shard_batch(rng.integers(0, 1000, size=(batch,)).astype(np.int32))

    def build(conv_bn):
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         conv_bn=conv_bn)
        opt = optax.sgd(0.01, momentum=0.9)

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        step = make_train_step(
            apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
            has_batch_stats=True, in_graph_steps=k_steps,
        )
        state = init_train_state(model, opt, jnp.zeros((2, 224, 224, 3)),
                                 has_batch_stats=True)
        return step, state

    def time_steps(step, state, n=4):
        # the step donates its state: thread it and hand it BACK so the
        # next timing window does not execute on donated buffers
        state, loss = step(state, x, y)  # compile + warm
        _sync(loss)
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            state, loss = step(state, x, y)
            _sync(loss)
            best = min(best, (time.perf_counter() - t0) / k_steps)
        return best * 1e3, state

    xla_step, xla_state = build("xla")
    pl_step, pl_state = build("pallas")
    # interleave: A B A B (shared-chip drift hits both sides)
    a1, xla_state = time_steps(xla_step, xla_state)
    b1, pl_state = time_steps(pl_step, pl_state)
    a2, xla_state = time_steps(xla_step, xla_state)
    b2, pl_state = time_steps(pl_step, pl_state)
    xla_ms, pl_ms = min(a1, a2), min(b1, b2)
    row = {
        "variant": "resnet50_train_step_e2e",
        "batch": batch,
        "xla_ms": xla_ms,
        "pallas_ms": pl_ms,
        "xla_img_s": batch / xla_ms * 1e3,
        "pallas_img_s": batch / pl_ms * 1e3,
        "pallas_vs_xla": xla_ms / pl_ms,
    }
    print(f"e2e resnet50 b{batch}: XLA {xla_ms:.1f} ms/step "
          f"({row['xla_img_s']:.0f} img/s), Pallas conv_bn {pl_ms:.1f} ms "
          f"({row['pallas_img_s']:.0f} img/s)  -> "
          f"{row['pallas_vs_xla']:.2f}x", flush=True)
    return [row]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--phase", choices=["standalone", "e2e"],
                    default="standalone",
                    help="run phases in separate processes: the "
                         "standalone shape buffers + two resident "
                         "ResNet-50 train states overflow HBM together")
    args = ap.parse_args()
    hvd.init()

    if args.phase == "standalone":
        rows = run_shape(args.batch, 64) + run_shape(args.batch, 256)
    else:
        rows = run_end_to_end(args.batch)

    dest = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
    os.makedirs(dest, exist_ok=True)
    path = os.path.join(dest, "conv_bn_experiment.json")
    merged = {"batch": args.batch, "rows": [],
              "method": "interleaved A/B/A/B min windows on the real "
                        "chip, device_get sync"}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
        # stamp the CURRENT run's batch/method: stale top-level fields
        # would misattribute rows measured at a different --batch
        merged["batch"] = args.batch
        merged["method"] = ("interleaved A/B/A/B min windows on the real "
                            "chip, device_get sync")
    kept = [r for r in merged.get("rows", [])
            if not any(r.get("variant") == n.get("variant")
                       and r.get("shape") == n.get("shape")
                       for n in rows)]
    merged["rows"] = kept + rows
    with open(path, "w") as f:
        json.dump(merged, f, indent=2)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
