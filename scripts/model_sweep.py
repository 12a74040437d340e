"""On-chip batch sweep for the reference's published model table.

The reference's scaling table is Inception V3 / ResNet / VGG-16
(reference README.rst:75-77, docs/benchmarks.rst:12-13).  This script
sweeps them over batch size — img/s/chip and MFU against the device's
published bf16 peak (utils/flops.DEVICE_PEAKS; an unknown device is an
error) — in ONE process with every config interleaved round-robin and
min-of-rounds taken.

Methodology per config = the bench.py harness: k in-graph steps via
lax.scan, wall-clock around the call, ended by a device_get of the
loss.  Per-step FLOPs come from a k=1 lowering's cost_analysis (a scan
body is counted ONCE regardless of trip count) and from the analytic
3x-forward count.

Writes scripts/out/model_sweep.json.

Usage: python scripts/model_sweep.py [--rounds 3] [--k 10] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# analytic forward GFLOPs per image (3x train).  Keyed by model at the
# table's default resolution; "Model@image" entries override for other
# resolutions (ViT FLOPs scale superlinearly with the patch-grid size)
FWD_GFLOPS = {"ResNet50": 4.09, "VGG16": 15.5, "InceptionV3": 5.73,
              "ResNet18": 1.82, "ResNet101": 7.8, "ViT-B16": 17.58, "ViT-L16": 61.6,
              "ViT-B16@384": 55.4}


def fwd_gflops(name: str, image: int) -> float:
    return FWD_GFLOPS.get(f"{name}@{image}", FWD_GFLOPS[name])

CONFIGS = [
    # (model, image, batch) — ResNet50 b128 anchors against the headline
    ("ResNet50", 224, 128),
    ("VGG16", 224, 16),
    ("VGG16", 224, 32),
    ("VGG16", 224, 64),
    ("VGG16", 224, 128),
    ("InceptionV3", 299, 32),
    ("InceptionV3", 299, 64),
    ("InceptionV3", 299, 128),
]
QUICK = [("ResNet50", 224, 128), ("VGG16", 224, 32),
         ("InceptionV3", 299, 64)]
# the attention image family (--set vit): ResNet-50 b128 anchors the
# window against the published-table sweep above
VIT = [("ResNet50", 224, 128), ("ViT-B16", 224, 64),
       ("ViT-B16", 224, 128), ("ViT-B16", 224, 256)]
# plumbing smoke on CPU (wrong-MFU numbers by design; never published;
# ResNet-18 only — ResNet-50/VGG compiles take >20 min on a 1-core host)
SMOKE = [("ResNet18", 64, 4), ("ResNet18", 64, 8)]


def build(model_name: str, image: int, batch: int, k: int,
          shared_states: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import MODELS
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    model = MODELS[model_name](num_classes=1000, dtype=jnp.bfloat16)
    opt = optax.sgd(0.01, momentum=0.9)
    from horovod_tpu.models import BATCH_STATS_FREE

    bn = model_name not in BATCH_STATS_FREE

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    def make(steps):
        return make_train_step(
            apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
            has_batch_stats=bn, in_graph_steps=steps,
        )

    rng = np.random.default_rng(0)
    x = shard_batch(rng.uniform(
        size=(batch * hvd.size(), image, image, 3)).astype(np.float32))
    y = shard_batch(rng.integers(
        0, 1000, size=(batch * hvd.size(),)).astype(np.int32))
    # ONE train state per MODEL, threaded through every batch config
    # (steps donate their state; per-config states would hold ~4x VGG's
    # 1.1 GB and can exhaust HBM)
    skey = (model_name, image)   # ViT params depend on image (pos_embed)
    if skey not in shared_states:
        shared_states[skey] = init_train_state(
            model, opt, jnp.zeros((2, image, image, 3)),
            has_batch_stats=bn)
    state = shared_states[skey]

    step = make(k)
    # XLA-issued FLOPs from a k=1 lowering (scan body counted once).
    # One compile per MODEL — per-step FLOPs scale linearly with batch,
    # so later batch configs scale the first measurement instead of
    # paying another ~30 s chip compile each.
    key = f"__flops_{model_name}_{image}"
    if key not in shared_states:
        one = make(1)
        try:
            compiled = jax.jit(lambda s, a, b: one(s, a, b)).lower(
                state, x, y).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0] if cost else {}
            shared_states[key] = (
                float((cost or {}).get("flops", 0.0)), batch)
        except Exception:  # noqa: BLE001 — cost analysis is advisory
            shared_states[key] = (0.0, batch)
    base_flops, base_batch = shared_states[key]
    xla_flops = base_flops * batch / base_batch
    return step, x, y, xla_flops


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--k", type=int, default=10,
                        help="in-graph steps per timed call")
    parser.add_argument("--quick", action="store_true")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny CPU plumbing check; output not valid")
    parser.add_argument("--set", dest="config_set", default="table",
                        choices=("table", "vit"),
                        help="'table' = the reference's published models; "
                             "'vit' = ViT-B16 sweep with a ResNet anchor")
    parser.add_argument("--configs", default=None,
                        help="ad-hoc override: 'Model:image:batch,...' "
                             "(e.g. 'VGG16:224:256,ViT-L16:224:32'); "
                             "writes model_sweep_custom.json")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    assert args.smoke or jax.devices()[0].platform != "cpu", \
        "model_sweep measures the real chip (--smoke for CPU plumbing)"
    from horovod_tpu.utils.flops import peak_flops, require_peak_flops

    # --smoke runs on the CPU mesh, which has no peak: its mfu is null
    peak = peak_flops() if args.smoke else require_peak_flops()

    if args.configs and args.smoke:
        parser.error("--smoke and --configs are mutually exclusive: "
                     "smoke numbers must never merge into a published "
                     "artifact")
    if args.configs:
        configs = [(m, int(i), int(b)) for m, i, b in
                   (c.split(":") for c in args.configs.split(","))]
        unknown = [m for m, _, _ in configs if m not in FWD_GFLOPS]
        if unknown:
            parser.error(f"no FWD_GFLOPS entry for {unknown}; add the "
                         "analytic count before burning chip time")
    elif args.smoke:
        configs = SMOKE
    elif args.config_set == "vit":
        configs = VIT[:2] if args.quick else VIT
    else:
        configs = QUICK if args.quick else CONFIGS

    # resolve the artifact path and read the prior sessions' rows NOW,
    # before any chip time is spent — a corrupt artifact must fail fast,
    # not after a multi-hour sweep
    path = os.path.join(
        os.path.dirname(__file__), "out",
        "model_sweep_custom.json" if args.configs
        else "model_sweep_smoke.json" if args.smoke
        else f"model_sweep_{args.config_set}.json"
        if args.config_set != "table" else "model_sweep.json")
    prior = {}
    try:
        with open(path) as f:
            prior = json.load(f)
    except FileNotFoundError:
        pass
    except (OSError, ValueError) as e:
        parser.error(f"existing artifact {path} is unreadable ({e}); "
                     "move it aside before sweeping")

    built = {}
    states = {}
    for name, image, batch in configs:
        print(f"compile {name} b{batch}@{image}...", flush=True)
        built[(name, image, batch)] = build(name, image, batch, args.k,
                                            states)
        # warmup: one call, synced; thread the donated state back
        step, x, y, _ = built[(name, image, batch)]
        states[(name, image)], loss = step(states[(name, image)], x, y)
        np.asarray(jax.device_get(loss))

    best_ms = {c: float("inf") for c in configs}
    for r in range(args.rounds):
        for c in configs:
            step, x, y, xla_flops = built[c]
            t0 = time.perf_counter()
            states[c[:2]], loss = step(states[c[:2]], x, y)
            np.asarray(jax.device_get(loss))
            dt = time.perf_counter() - t0
            ms = dt / args.k * 1e3
            best_ms[c] = min(best_ms[c], ms)
            print(f"round {r} {c[0]} b{c[2]}: {ms:.2f} ms/step", flush=True)

    out = {}
    for (name, image, batch), (*_, xla_flops) in built.items():
        ms = best_ms[(name, image, batch)]
        img_s = batch / (ms / 1e3)
        analytic = fwd_gflops(name, image) * 3e9 * batch
        entry = {
            "batch": batch, "image": image,
            "peak_tflops": peak / 1e12 if peak else None,
            "ms_per_step": round(ms, 2),
            "img_sec_per_chip": round(img_s, 1),
            "analytic_flops_per_step": analytic,
            "xla_flops_per_step": xla_flops,
            "mfu": round(analytic / (ms / 1e3) / peak, 4) if peak else None,
        }
        out.setdefault(name, []).append(entry)
        print(f"== {name} b{batch}: {ms:.2f} ms, {img_s:.0f} img/s, "
              f"MFU {entry['mfu']}", flush=True)

    os.makedirs(os.path.dirname(path), exist_ok=True)
    # merge-on-write: successive sessions accumulate per-(model,batch)
    # rows instead of clobbering earlier measurements (the gpt_mfu_sweep
    # convention: prior artifact was pre-loaded before the sweep ran,
    # and rows measured against another peak are dropped)
    merged = {
        name: [e for e in entries
               if peak and e.get("peak_tflops") == peak / 1e12]
        for name, entries in prior.items()
    }
    for name, entries in out.items():
        have = {(e["batch"], e["image"]): i
                for i, e in enumerate(merged.get(name, []))}
        for e in entries:
            k = (e["batch"], e["image"])
            if k in have:
                merged[name][have[k]] = e
            else:
                merged.setdefault(name, []).append(e)
        merged[name].sort(key=lambda e: (e["image"], e["batch"]))
    merged = {k: v for k, v in merged.items() if v}
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
    print("wrote", path)
    return merged


if __name__ == "__main__":
    main()
