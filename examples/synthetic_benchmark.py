"""Synthetic benchmark — the framework's headline benchmark harness.

Feature-for-feature port of the reference harness CLI (reference
examples/tensorflow2_synthetic_benchmark.py: --model/--batch-size/
--fp16-allreduce/--num-warmup-batches/--num-batches-per-iter/--num-iters),
re-done TPU-native: the model is flax ResNet, the step is a compiled SPMD
program over the mesh, gradients ride fused psum over ICI.

Run:  python examples/synthetic_benchmark.py --batch-size 32
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import MODELS
from horovod_tpu.training import (
    TrainState, init_train_state, make_train_step, shard_batch,
)


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description="horovod_tpu Synthetic Benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    parser.add_argument("--fp16-allreduce", action="store_true", default=False,
                        help="use bf16 compression during allreduce")
    parser.add_argument("--compression", type=str, default=None,
                        choices=["none", "bf16", "fp16", "int8", "fp8",
                                 "fp8_e5m2"],
                        help="gradient wire format (quantized formats "
                             "carry the error-feedback residual; "
                             "default: the HVD_COMPRESSION env knob)")
    parser.add_argument("--model", type=str, default="ResNet50",
                        help="model to benchmark")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="input batch size per rank")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--num-warmup-batches", type=int, default=10,
                        help="number of warm-up batches not benchmarked")
    parser.add_argument("--num-batches-per-iter", type=int, default=10,
                        help="number of batches per benchmark iteration")
    parser.add_argument("--num-in-graph-steps", type=int, default=1,
                        help="optimizer steps compiled into one program "
                             "(lax.scan); amortizes host dispatch")
    parser.add_argument("--num-iters", type=int, default=10,
                        help="number of benchmark iterations")
    parser.add_argument("--adasum", action="store_true", default=False,
                        help="use Adasum reduction")
    parser.add_argument("--hierarchical", action="store_true", default=False,
                        help="use two-level (ICI/DCN-style) allreduce")
    parser.add_argument("--platform", type=str, default=None,
                        help="jax platform override (tpu/cpu)")
    parser.add_argument("--autotune", action="store_true", default=False,
                        help="live-tune fusion threshold / hierarchical "
                             "allreduce while benchmarking (reference "
                             "horovodrun --autotune)")
    parser.add_argument("--autotune-log-file", type=str, default=None,
                        help="CSV trace of autotune samples")
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32"],
                        help="model compute dtype (params stay float32)")
    parser.add_argument("--loss-fetch-steps", type=int, default=None,
                        help="trailing async loss-fetch cadence "
                             "(default: the HVD_LOSS_FETCH_STEPS knob)")
    return parser.parse_args(argv)


def log(s, nl=True):
    if hvd.rank() != 0:
        return
    print(s, end="\n" if nl else "", flush=True)


def run(args) -> dict:
    hvd.init(platform=args.platform)

    model = MODELS[args.model](
        num_classes=args.num_classes, dtype=jnp.dtype(args.dtype)
    )
    opt = optax.sgd(0.01, momentum=0.9)

    global_batch = args.batch_size * hvd.size()
    rng = np.random.default_rng(42)
    data = rng.uniform(
        size=(global_batch, args.image_size, args.image_size, 3)
    ).astype(np.float32)
    target = rng.integers(0, args.num_classes, size=(global_batch,)).astype(
        np.int32
    )

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    if args.compression:
        from horovod_tpu.ops.compression import Compression as _C
        from horovod_tpu.utils import env as _env

        compression = _C.lookup(
            args.compression,
            error_feedback=_env.get_bool(
                _env.HVD_COMPRESSION_ERROR_FEEDBACK, True))
    elif args.fp16_allreduce:
        compression = hvd.Compression.fp16
    else:
        compression = None   # make_train_step resolves HVD_COMPRESSION

    step = make_train_step(
        apply_fn=model.apply,
        loss_fn=loss_fn,
        optimizer=opt,
        op=hvd.Adasum if args.adasum else hvd.Average,
        compression=compression,
        has_batch_stats=True,
        hierarchical=args.hierarchical,
        autotune=args.autotune or None,
        autotune_log_file=args.autotune_log_file,
        in_graph_steps=args.num_in_graph_steps,
        loss_fetch_steps=args.loss_fetch_steps,
    )

    from horovod_tpu.ops.compression import ErrorFeedback as _EF
    from horovod_tpu.ops.compression import from_env as _comp_from_env

    eff = compression if compression is not None else _comp_from_env()
    state = init_train_state(
        model, opt, jnp.zeros((2, args.image_size, args.image_size, 3)),
        has_batch_stats=True,
        compression=eff if isinstance(eff, _EF) else None,
    )
    x = shard_batch(data)
    y = shard_batch(target)

    log(f"Model: {args.model}")
    log(f"Batch size: {args.batch_size} (global {global_batch})")
    log(f"Number of chips: {hvd.size()}")

    # Sync by fetching the chained loss: the scalar depends on the whole
    # sequential step chain, so the timed region ends when the device does.
    # Every fetched value is kept, so a caller can see the loss move.
    log("Running warmup...")
    t0 = time.perf_counter()
    for _ in range(max(args.num_warmup_batches, 1)):
        state, loss = step(state, x, y)
    losses = [float(np.asarray(jax.device_get(loss)))]
    warmup_sec = time.perf_counter() - t0

    log("Running benchmark...")
    imgs_per_call = (args.batch_size * hvd.size()
                     * max(args.num_in_graph_steps, 1))
    img_secs = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state, loss = step(state, x, y)
        losses.append(float(np.asarray(jax.device_get(loss))))
        dt = time.perf_counter() - t0
        img_sec = imgs_per_call * args.num_batches_per_iter / dt
        log(f"Iter: Img/sec total: {img_sec:.1f}")
        img_secs.append(img_sec)

    pm = getattr(step, "parameter_manager", None)
    if pm is not None:
        log(f"Autotune: frozen={pm.frozen} "
            f"threshold={pm.current.fusion_threshold_bytes} "
            f"hierarchical={pm.current.hierarchical_allreduce}")

    img_sec_mean = float(np.mean(img_secs))
    img_sec_conf = float(1.96 * np.std(img_secs))
    log(f"Img/sec per chip: {img_sec_mean / hvd.size():.1f}")
    log(f"Total img/sec on {hvd.size()} chip(s): "
        f"{img_sec_mean:.1f} +-{img_sec_conf:.1f}")
    return {
        "img_sec_total": img_sec_mean,
        "img_sec_per_chip": img_sec_mean / hvd.size(),
        "conf": img_sec_conf,
        "size": hvd.size(),
        "final_loss": losses[-1],
        # one value per sync point: after warm-up, then after each iter
        "losses": losses,
        # warm-up wall time: the compile plus the warm-up steps
        "warmup_sec": warmup_sec,
    }


if __name__ == "__main__":
    run(parse_args())
