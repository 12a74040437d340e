"""Generate the analytic scaling story (docs/SCALING.md's numbers).

For each model in the reference's published scaling table (Inception V3,
ResNet, VGG-16 — reference README.rst:75-77, docs/benchmarks.rst:12-13),
plus ViT-B16 (beyond the reference's table, same methodology),
compile the FULL hierarchical-DP training step on the 8-device virtual
mesh, read the collective traffic out of the optimized HLO
(timeline/comm_report.py), and model the 8→64-chip v5e scaling-efficiency
curve from measured single-chip step times.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        JAX_PLATFORMS=cpu python scripts/scaling_story.py
Writes scripts/out/scaling_story.json.

The built-in step times (ms/step at the listed batch) were taken on an
earlier chip path, before PR 1, and have not been re-measured on the
current code (root PERF.md lists them as hypotheses): pass
--step-ms model=ms from a fresh chip run.  A model with no step time is
an error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

# ms per optimizer step on ONE v5e chip, from chip sessions that predate
# PR 1 (the driver-path bench for ResNet-50, interleaved min-of-rounds
# sweeps for the rest); not re-measured on the current code.
MEASURED_STEP_MS = {
    "ResNet50": {"batch": 128, "ms": 47.7,
                 "source": "driver r5 2683.55 img/s (bench.py k=100)"},
    "ResNet101": {"batch": 128, "ms": 79.01,
                  "source": "r5 interleaved sweep 1620 img/s"},
    "VGG16": {"batch": 256, "ms": 181.47,
              "source": "r5 interleaved sweep 1411 img/s (b256 best)"},
    "InceptionV3": {"batch": 256, "ms": 138.43,
                    "source": "r5 interleaved sweep 1849 img/s (b256 best)"},
    "ViT-B16": {"batch": 64, "ms": 80.36,
                "source": "r5 interleaved sweep 796 img/s"},
}


def one_model(name: str, batch: int, image: int, step_ms, fused: bool):
    from scripts.comm_report import main as comm_main

    argv = ["--model", name, "--batch-size", str(batch),
            "--image-size", str(image)]
    if not fused:
        argv.append("--hierarchical")
    if step_ms:
        argv += ["--step-ms", str(step_ms)]
    return comm_main(argv)


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--models", nargs="*",
                        default=["ResNet50", "ResNet101", "VGG16",
                                 "InceptionV3", "ViT-B16"])
    parser.add_argument("--step-ms", nargs="*", default=[],
                        metavar="MODEL=MS",
                        help="override measured step ms, e.g. ResNet50=48.4")
    args = parser.parse_args(argv)

    overrides = dict(kv.split("=") for kv in args.step_ms)
    out = {}
    for name in args.models:
        image = 299 if name == "InceptionV3" else 224
        meas = MEASURED_STEP_MS.get(name)
        batch = meas["batch"] if meas else 128
        if name in overrides:
            step_ms = float(overrides[name])
            source = "cli override"
        elif meas:
            step_ms, source = meas["ms"], meas["source"]
        else:
            parser.error(f"no step time for {name}: pass "
                         f"--step-ms {name}=MS from a chip run")
        entry = {"batch": batch, "image": image,
                 "step_ms": round(step_ms, 2), "step_ms_source": source}
        for mode, fused in (("fused", True), ("per_tensor", False)):
            rep = one_model(name, batch, image, step_ms, fused)
            entry[mode] = {
                "collectives": rep["collectives"],
                "total_collective_bytes": rep["total_collective_bytes"],
                "modeled_comm_seconds": rep["modeled_comm_seconds"],
                "scaling_model": rep["scaling_model"],
            }
        out[name] = entry
        print(f"== {name}: fused eff@64="
              f"{entry['fused']['scaling_model'][64]}, per-tensor "
              f"eff@64={entry['per_tensor']['scaling_model'][64]}")

    os.makedirs(os.path.join(os.path.dirname(__file__), "out"),
                exist_ok=True)
    path = os.path.join(os.path.dirname(__file__), "out",
                        "scaling_story.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)
    return out


if __name__ == "__main__":
    main()
