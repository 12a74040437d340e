"""Measured transformer MFU on the chip.

Sweeps GPT-2-small train-step configs over (batch, seq) and records the
MEASURED MFU: FLOPs are taken from the compiled program's own
cost_analysis (XLA's issued-work count for exactly the executable being
timed — not the 6ND analytic estimate), time from wall clock around a
device_get of the loss.  MFU is reported against the device's published
bf16 peak (utils/flops.DEVICE_PEAKS, keyed by device kind; an unknown
device is an error).

Methodology matches the reference benchmark loop (reference
examples/tensorflow2_synthetic_benchmark.py:72-97: warmup, timed iters
over a synthetic batch) with the K-step lax.scan harness bench.py uses.

Writes scripts/out/gpt_mfu_sweep.json.

Usage: python scripts/gpt_mfu_sweep.py [--quick]
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
import optax

import horovod_tpu as hvd
from horovod_tpu.models.gpt import gpt2_small, next_token_loss
from horovod_tpu.training import init_train_state, make_train_step, shard_batch
from horovod_tpu.utils.flops import require_peak_flops


def _sync(x):
    np.asarray(jax.device_get(x))


def run_config(batch: int, seq: int, *, k_steps: int = 5, iters: int = 3,
               inner: int = 3) -> dict:
    model = gpt2_small(dtype=jnp.bfloat16, max_len=max(seq, 1024))
    opt = optax.adam(1e-4)
    step = make_train_step(
        apply_fn=lambda v, x, train=True: model.apply(v, x),
        loss_fn=next_token_loss, optimizer=opt,
        in_graph_steps=k_steps,
    )
    state = init_train_state(model, opt, jnp.zeros((2, seq), jnp.int32))
    rng = np.random.default_rng(0)
    ids = shard_batch(
        rng.integers(0, 1000, size=(batch, seq)).astype(np.int32)
    )

    # Issued-FLOPs per step from a SINGLE-step lowering: XLA's
    # cost_analysis counts a lax.scan body once regardless of trip
    # count, so the K-step executable reports one step's flops anyway —
    # lowering K=1 makes the accounting explicit instead of relying on
    # that quirk.  (Pallas custom calls are opaque to cost_analysis, so
    # flash-attention FLOPs — ~4% of a GPT-2 step at s1024 — are NOT
    # counted: the MFU below is slightly conservative.)
    step1 = make_train_step(
        apply_fn=lambda v, x, train=True: model.apply(v, x),
        loss_fn=next_token_loss, optimizer=opt, in_graph_steps=1,
    )
    lowered = jax.jit(lambda s, a, b: step1(s, a, b)).lower(state, ids, ids)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops_per_step = float(cost.get("flops", 0.0))

    state, loss = step(state, ids, ids)  # warmup/compile
    _sync(loss)
    best_call = float("inf")  # seconds per K-step program call
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(inner):
            state, loss = step(state, ids, ids)
        _sync(loss)
        best_call = min(best_call, (time.perf_counter() - t0) / inner)

    sec_per_step = best_call / k_steps
    tokens_per_step = batch * seq
    tflops = flops_per_step / sec_per_step / 1e12
    return {
        "batch": batch,
        "seq": seq,
        "k_steps": k_steps,
        "ms_per_step": sec_per_step * 1e3,
        "tokens_per_sec": tokens_per_step / sec_per_step,
        "seq_per_sec": batch / sec_per_step,
        "issued_gflops_per_step": flops_per_step / 1e9,
        "tflops_issued": tflops,
        "mfu": tflops * 1e12 / require_peak_flops(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--configs", default=None,
                    help="comma list of BxS, e.g. 8x1024,16x1024")
    ap.add_argument("--k", type=int, default=5,
                    help="in-graph steps per timed call (the bench.py "
                         "amortization knob; per-call overhead is ~2%% "
                         "of a 571 ms call at K=5)")
    args = ap.parse_args()

    hvd.init()
    peak_tflops = require_peak_flops() / 1e12
    if args.configs:
        configs = [tuple(map(int, c.split("x")))
                   for c in args.configs.split(",")]
    elif args.quick:
        configs = [(8, 1024), (16, 1024)]
    else:
        # b48 is the single-chip HBM limit at s1024 (b64 OOMs the
        # 5-step program)
        configs = [(4, 512), (8, 512), (8, 1024), (16, 1024),
                   (32, 1024), (48, 1024), (8, 2048), (16, 2048)]

    dest = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
    os.makedirs(dest, exist_ok=True)
    path = os.path.join(dest, "gpt_mfu_sweep.json")
    # read the mergeable prior rows BEFORE burning device time: a
    # corrupt artifact (e.g. a killed non-atomic write) must not crash
    # the script after the sweep, and rows measured against a different
    # peak must not mix into this run's ratios
    existing = []
    try:
        with open(path) as f:
            existing = [
                r for r in json.load(f).get("configs", [])
                if r.get("peak_tflops") == peak_tflops
            ]
    except (OSError, ValueError):
        existing = []

    rows = []
    for batch, seq in configs:
        r = run_config(batch, seq, k_steps=args.k)
        r["peak_tflops"] = peak_tflops
        rows.append(r)
        print(
            f"b{batch} s{seq}: {r['ms_per_step']:.1f} ms/step  "
            f"{r['tokens_per_sec']:.0f} tok/s  "
            f"{r['tflops_issued']:.1f} TFLOPS issued  "
            f"MFU {r['mfu']:.1%} of the {peak_tflops:.0f} TFLOPS peak",
            flush=True,
        )

    # merge into the existing artifact by (batch, seq): a partial
    # --configs run must not clobber the rest of the sweep
    keyed = {(r["batch"], r["seq"]): r for r in existing}
    keyed.update({(r["batch"], r["seq"]): r for r in rows})
    rows = sorted(keyed.values(), key=lambda r: (r["seq"], r["batch"]))
    best = max(rows, key=lambda r: r["mfu"])
    out = {
        "model": "gpt2_small (124M, bf16, causal flash attention)",
        "peak_tflops": peak_tflops,
        "device_kind": jax.devices()[0].device_kind,
        "method": "flops = compiled-executable cost_analysis (issued "
                  "work); time = wall clock around K in-graph steps with "
                  "device_get sync; min over iters",
        "configs": rows,
        "best": best,
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=2)
    os.replace(tmp, path)  # atomic: a killed run can't truncate the artifact
    print(f"best: b{best['batch']} s{best['seq']} -> "
          f"{best['mfu']:.1%} of peak")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
