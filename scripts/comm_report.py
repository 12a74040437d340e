"""Collective-traffic + scaling-model report for the headline benchmark.

The stand-in for BASELINE.json's allreduce-scaling metric (reference
docs/benchmarks.rst:12-13) on a single-chip bench host: compiles the
ResNet-50 train step on a virtual 8-device mesh and prints the per-step
collective bytes and the modeled 8→64-chip efficiency curve.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python scripts/comm_report.py [--model ResNet50] [--fp16-allreduce]
"""

from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="ResNet50")
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--fp16-allreduce", action="store_true")
    parser.add_argument("--hierarchical", action="store_true")
    parser.add_argument("--step-ms", type=float, default=None,
                        help="measured single-chip step time (from "
                             "bench.py) to base the scaling model on")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import MODELS
    from horovod_tpu.timeline.comm_report import collective_report
    from horovod_tpu.utils.flops import DEVICE_PEAKS
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    hvd.init(devices=jax.devices("cpu")[:8])

    model = MODELS[args.model](num_classes=1000, dtype=jnp.bfloat16)
    opt = optax.sgd(0.01, momentum=0.9)
    from horovod_tpu.models import BATCH_STATS_FREE

    bn = args.model not in BATCH_STATS_FREE

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    step = make_train_step(
        apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
        has_batch_stats=bn, hierarchical=args.hierarchical,
        compression=hvd.Compression.fp16 if args.fp16_allreduce
        else hvd.Compression.none,
        donate=False,
    )
    # the step builder wraps the compiled fn in a host-side tracer shim;
    # lower the underlying spmd program
    rng = np.random.default_rng(0)
    x = shard_batch(rng.uniform(
        size=(args.batch_size * hvd.size(), args.image_size,
              args.image_size, 3)).astype(np.float32))
    y = shard_batch(rng.integers(
        0, 1000, size=(args.batch_size * hvd.size(),)).astype(np.int32))
    state = init_train_state(
        model, opt, jnp.zeros((2, args.image_size, args.image_size, 3)),
        has_batch_stats=bn,
    )

    report = collective_report(
        lambda s, a, b: step(s, a, b), state, x, y,
        measured_step_seconds=args.step_ms / 1e3 if args.step_ms else None,
        # the chip being modelled, not the CPU mesh this compiles on
        peak_flops=DEVICE_PEAKS["TPU v5 lite"].flops,
    )
    print(json.dumps(report, indent=2))
    return report


if __name__ == "__main__":
    main()
