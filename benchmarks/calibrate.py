"""Read, in one process on the chip, the numbers ``correct`` compares: for
a cell's program over many seeds, and for its control — the reference in
float8, put in the program's place — over a few.  The limits in the
configurations' modules are set from these two readings (PERF.md section
2); the benchmark's own runs never run this.

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--control-only]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def readings(spec, workload: str, seeds, control_seeds, *, devices=None,
             dump_dir=None) -> dict:
    """``{"program": {seed: numbers}, "control": {seed: numbers}}``.  With
    ``dump_dir`` every seed's losses and per-leaf norms (program,
    reference, control) are also written there as JSON, to try another
    number on without another chip call."""
    import jax
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks import run as bench_run
    from benchmarks.harness import check, loop, traffic
    from benchmarks.references import common

    cell = spec.cell(workload)
    devices = devices or jax.devices()[:cell.chips]
    adapter, cfg, mix = cell.adapter, cell.cfg, cell.mix
    rows = int(mix["rows_per_chip"])
    hvd.init(devices=devices)
    prog = adapter.program(cfg, mix)
    ref = adapter.reference(cfg, mix)
    step = make_train_step(
        apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
        optimizer=prog["optimizer"],
        has_batch_stats=prog["has_batch_stats"])
    out = {"program": {}, "control": {}}
    for seed in sorted(set(seeds) | set(control_seeds)):
        t = time.perf_counter()
        # one state a seed, as ``run.py`` builds it: the step is given its
        # state's buffers, and nothing of a seed is kept for the next
        state, weights = bench_run.seeded_state(
            init_train_state(prog["model"], prog["optimizer"],
                             prog["sample"],
                             has_batch_stats=prog["has_batch_stats"]),
            ref, seed)
        arrays = traffic.dataset(mix, cfg, len(devices), seed)
        feed = traffic.batches(mix, arrays, seed, loop.annotate)
        run = bench_run.RunRecord(cell, len(devices),
                                  devices[0].device_kind, None)
        program, _, first = bench_run.first_steps(
            step, state, feed, prog, weights, run, len(devices))
        del program["state"], state, weights
        host = [tuple(np.asarray(a) for a in b) for b in first]
        del feed, first, arrays

        reference = common.follow(ref, seed, host, rows)
        dump = {"program": program, "reference": reference}
        if seed in seeds:
            out["program"][seed] = check.first_steps_numbers(program,
                                                             reference)
            print(f"calibrate: seed {seed} program "
                  f"{json.dumps(out['program'][seed])} losses "
                  f"{program['losses']} reference {reference['losses']}",
                  flush=True)
        if seed in control_seeds:
            dump["control"] = common.follow(ref, seed, host, rows, "fp8")
            out["control"][seed] = check.first_steps_numbers(
                dump["control"], reference)
            print(f"calibrate: seed {seed} control "
                  f"{json.dumps(out['control'][seed])}", flush=True)
        if dump_dir:
            os.makedirs(dump_dir, exist_ok=True)
            with open(os.path.join(dump_dir, f"{workload}_{seed}.json"),
                      "w") as fh:
                json.dump(dump, fh)
        print(f"calibrate: seed {seed} took {time.perf_counter() - t:.1f} s",
              flush=True)
    hvd.shutdown()
    return out


def summary(out: dict) -> dict:
    names = next(iter(out["program"].values())).keys()
    return {n: {"program_largest": max(v[n] for v in
                                       out["program"].values()),
                "control_smallest": min((v[n] for v in
                                         out["control"].values()),
                                        default=None)}
            for n in names}


def control_readings(spec, workload: str, seeds) -> dict:
    """The control's numbers alone, which need one chip whatever the cell:
    reference and control follow the first rows of the seeded dataset, in
    the cell's one-chip blocks, with no program in the process."""
    from benchmarks import run as bench_run
    from benchmarks.harness import check, traffic
    from benchmarks.references import common

    cell = spec.cell(workload)
    adapter, cfg, mix = cell.adapter, cell.cfg, cell.mix
    rows = int(mix["rows_per_chip"])
    steps = bench_run.CHECK_STEPS
    ref = adapter.reference(cfg, mix)
    out = {}
    for seed in seeds:
        arrays = traffic.dataset(mix, cfg, cell.chips, seed)
        g = rows * cell.chips
        host = [tuple(a[i * g:(i + 1) * g] for a in arrays)
                for i in range(steps)]

        out[seed] = check.first_steps_numbers(
            common.follow(ref, seed, host, rows, "fp8"),
            common.follow(ref, seed, host, rows))
        print(f"calibrate: seed {seed} control {json.dumps(out[seed])}",
              flush=True)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", default="")
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--dump-dir", default=None)
    parser.add_argument("--control-only", action="store_true",
                        help="read the control alone, on one chip")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    from benchmarks.harness.spec import Spec

    if args.control_only:
        control_readings(Spec(REPO_ROOT), args.workload, control)
        return 0
    out = readings(Spec(REPO_ROOT), args.workload, seeds, control,
                   dump_dir=args.dump_dir)
    print("calibrate: summary " + json.dumps(summary(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
