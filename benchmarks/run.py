"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process, the program's public entry points (``hvd.init``,
``init_train_state``, ``ShardedLoader``/``shard_batch``,
``make_train_step``), the benchmark's own loop.  The last line of standard
output is the result object; everything else is on earlier lines.  Without
a TPU, or with fewer chips than the cell asks for, it exits 2 and prints
no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

#: steps the set-up drives through the window's own call and feed, and the
#: reference follows
CHECK_STEPS = 3
#: seconds of a traced run's window (a trace of the whole run would be
#: hundreds of megabytes)
TRACE_SECONDS = 4.0


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclass
class RunRecord:
    """What a per-layer metric's ``read(run)`` may look at."""
    cell: object
    chips: int
    device_kind: str
    peak: object
    steps: int = 0
    window_s: float = 0.0
    init_s: float = 0.0
    compile_s: float = 0.0
    wait_s: List[float] = field(default_factory=list)
    dispatch_s: List[float] = field(default_factory=list)
    reduced: Optional[object] = None      # harness.trace.Reduced
    module_memory: Dict[str, int] = field(default_factory=dict)

    def per_step_ms(self, seconds: float) -> float:
        return seconds / self.steps * 1e3


def _memory_peak(devices) -> int:
    """Peak bytes on the fullest chip.  The allocator's
    ``peak_bytes_in_use`` leaves out the running program's temporaries
    (1.7 GB against the compiler's 7.6 GB for the 1k step: PERF.md, PR 21),
    which it holds as ``bytes_reserved`` while the program is loaded; so,
    read right after the window: the larger of that peak and of what is in
    use plus what is reserved."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        say(f"memory: {d} " + " ".join(
            f"{k}={stats[k]}" for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "peak_bytes_reserved") if k in stats))
        peaks.append(max(
            int(stats.get("peak_bytes_in_use", 0)),
            int(stats.get("bytes_in_use", 0))
            + int(stats.get("bytes_reserved", 0))))
    return max(peaks)


def seeded_state(state, ref: dict, seed: int):
    """``state`` with the benchmark's weights of ``seed`` as its parameters
    — not the program's: the reference starts from the same ones and takes
    nothing the program made — and those weights, placed as the state is.
    The state holds a copy (the step is given its state's buffers) and the
    caller keeps the weights through the checked steps, for
    ``first_steps`` to take each leaf's change against: 4 bytes a
    parameter beside the state.  Making them again once the steps are done,
    a few leaves at a time, frees that and cost ``setup_s`` 5 s a run on
    the chip (PERF.md section 6, PR 40), so the copy stays."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import check

    replicated = jax.tree_util.tree_leaves(state.params)[0].sharding
    weights = jax.device_put(ref["init"](seed), replicated)
    return state._replace(params=check.replace_leaves(
        state.params, jax.tree_util.tree_map(jnp.copy, weights))), weights


def first_steps(step, state, feed, prog: dict, weights: dict, run,
                chips: int):
    """Drive ``CHECK_STEPS`` steps through the window's own call and feed
    and read off what ``correct`` compares: each loss, the first gradient's
    norm and sketch leaf by leaf as the optimizer received it (from its
    state after one step) and the norm of each leaf's change over the
    steps.  Returns
    ``(numbers + state, placement, batches)``."""
    import jax
    import numpy as np

    from benchmarks.references import common

    placement, first, program = {}, [], {"losses": []}
    for i in range(CHECK_STEPS):
        batch = next(feed)
        first.append(batch)
        if i == 0:
            shards = batch[0].addressable_shards
            placement["batch_shards_missing"] = float(
                chips - len({s.device for s in shards}))
            placement["state_leaves_not_replicated"] = float(sum(
                not (leaf.sharding.is_fully_replicated
                     and len(leaf.addressable_shards) == chips)
                for leaf in jax.tree_util.tree_leaves(state)))
        t = time.perf_counter()
        state, loss = step(state, *prog["xy"](batch))
        program["losses"].append(float(loss))
        if i == 0:
            run.compile_s = time.perf_counter() - t
            tree, scale = prog["first_gradient"](state.opt_state)
            leaves = common.flatten(tree)
            program["grad_norms"] = {
                k: scale * float(v)
                for k, v in common.leaf_norms(leaves).items()}
            program["grad_sketches"] = {
                k: [scale * float(x) for x in np.asarray(v)]
                for k, v in common.leaf_sketches(leaves).items()}
    moved = common.leaf_diff_norms(common.flatten(state.params), weights)
    program["update_norms"] = {k: float(v) for k, v in moved.items()}
    program["state"] = state
    return program, placement, first


def run_cell(spec, workload: str, seed: int, seconds: float, trace: bool,
             *, devices=None, peaks=None, scratch: Optional[str] = None,
             t0: Optional[float] = None, break_step=None) -> dict:
    """Everything after the look for a chip.  ``devices`` defaults to the
    first ``chips`` TPU devices; ``peaks`` to the published table.
    ``break_step`` wraps the compiled step (tests break the timed path
    with it).  Returns the result object."""
    import jax
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness import check, loop, traffic
    from benchmarks.harness import peaks as peaks_mod
    from benchmarks.harness import trace as trace_mod
    from benchmarks.harness import xplane
    from benchmarks.references import common

    t0 = time.perf_counter() if t0 is None else t0
    cell = spec.cell(workload)
    if devices is None:
        devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    peak = (peaks or peaks_mod.PEAKS).get(kind) or peaks_mod.peak_for(kind)
    run = RunRecord(cell, len(devices), kind, peak)
    adapter, cfg, mix = cell.adapter, cell.cfg, cell.mix

    # -- set-up ------------------------------------------------------------
    t = time.perf_counter()
    hvd.init(devices=devices)
    prog = adapter.program(cfg, mix)
    state = init_train_state(prog["model"], prog["optimizer"],
                             prog["sample"],
                             has_batch_stats=prog["has_batch_stats"])
    ref = adapter.reference(cfg, mix)
    state, weights = seeded_state(state, ref, seed)
    step = make_train_step(
        apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
        optimizer=prog["optimizer"],
        has_batch_stats=prog["has_batch_stats"])
    if break_step is not None:
        step = break_step(step)
    run.init_s = time.perf_counter() - t

    arrays = traffic.dataset(mix, cfg, len(devices), seed)
    feed = traffic.batches(mix, arrays, seed, loop.annotate)
    say(f"setup: init {run.init_s:.2f} s, dataset "
        f"{sum(a.nbytes for a in arrays) / 1e6:.1f} MB")

    program, placement, first = first_steps(
        step, state, feed, prog, weights, run, len(devices))
    state = program.pop("state")
    del weights
    say(f"setup: first step (trace, lower, compile or cache load, run) "
        f"{run.compile_s:.2f} s; first losses {program['losses']}")

    trace_dir = None
    if trace:
        trace_dir = os.path.join(scratch or REPO_ROOT, ".bench_trace",
                                 workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        t = time.perf_counter()
        compiled = jax.jit(step).lower(
            state, *prog["xy"](first[0])).compile()
        mem = compiled.memory_analysis()
        run.module_memory = {
            "argument": int(mem.argument_size_in_bytes),
            "temp": int(mem.temp_size_in_bytes),
            "output": int(mem.output_size_in_bytes),
            "alias": int(mem.alias_size_in_bytes)}
        del compiled
        say(f"setup: module compiled again for its memory account in "
            f"{time.perf_counter() - t:.2f} s (traced runs only): "
            f"{run.module_memory}")
        seconds = min(seconds, TRACE_SECONDS)

    setup_s = time.perf_counter() - t0

    # -- the window --------------------------------------------------------
    state, w = loop.drive(step, state, feed, prog["xy"], seconds, trace_dir)
    memory_peak = _memory_peak(devices)
    run.steps, run.window_s = w.steps, w.seconds
    run.wait_s, run.dispatch_s = w.wait_s, w.dispatch_s
    rows = int(mix["rows_per_chip"])
    rate = w.steps * rows * int(mix["items_per_row"]) / w.seconds
    say(f"window: {w.steps} steps in {w.seconds:.4f} s, "
        f"{w.seconds / w.steps * 1e3:.3f} ms a step")
    # a window of one step has no interval, and no cell of one step a tail
    intervals = loop.step_intervals_ms(w.completions) or [math.nan]
    p50, p95, p99 = np.percentile(intervals, [50.0, 95.0, 99.0])
    say(f"window: {len(intervals)} intervals between consecutive steps' "
        f"completions: p50 {p50:.3f}, p95 {p95:.3f}, p99 {p99:.3f}, "
        f"longest {max(intervals):.3f} ms")

    metrics: Dict[str, float] = {}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        run.reduced = trace_mod.reduce(xplane.read(xplane.find(trace_dir)))
        if scratch is None:  # a caller that names the place keeps the file
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.reduced.busy_s()
        device["window_s"] = run.reduced.window_s
        breakdown = {
            "device_ops": [[n, s] for n, s in run.reduced.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in run.reduced.idle_gaps(10)]}
        for name, module in cell.per_layer.items():
            value = module.read(run)
            if value is not None:
                metrics[name] = float(value)
    else:
        values = {
            mix["rate_metric"]: rate,
            "mfu": 100.0 * rate * adapter.flops_per_item(cfg, mix)
            / peak.flops,
            "setup_s": setup_s,
            "step_ms_p95": float(p95),
        }
        metrics = {name: values[name] for name in cell.end_to_end}

    # -- correct, outside the window and outside set-up ---------------------
    host_batches = [tuple(np.asarray(a) for a in b) for b in first]
    parameters = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    del state, step, feed, first, arrays
    hvd.shutdown()
    t = time.perf_counter()
    reference = common.follow(ref, seed, host_batches, rows)
    say(f"check: reference followed {len(host_batches)} steps in "
        f"{time.perf_counter() - t:.2f} s; losses {reference['losses']}")
    # a line of the log, not a metric: what the process peaked at once the
    # check has run too (``memory_peak_bytes`` was read before it)
    after = _memory_peak(devices)
    say(f"check: peak after the check {after} bytes on the fullest chip, "
        f"{after / parameters:.2f} a parameter of {parameters}")
    numbers = check.first_steps_numbers(program, reference)
    numbers.update(check.window_numbers(w.losses))
    numbers.update(placement)
    correct, lines = check.verdict(numbers, adapter.limits(cfg, mix))
    for line in lines:
        say(line)

    failed = sum(not math.isfinite(x) for x in w.losses)
    result = {
        "correct": bool(correct), "attempted": w.steps, "failed": failed,
        "metrics": {name: {"value": value, "unit": spec.unit(name)}
                    for name, value in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from benchmarks.harness.spec import Spec

    spec = Spec(REPO_ROOT)
    cell = spec.cell(args.workload)

    import jax

    devices = jax.devices()
    say(f"device: platform {devices[0].platform}, device_kind "
        f"{devices[0].device_kind}, count {len(devices)}; cell "
        f"{cell.name} wants {cell.chips}")
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} device(s) of platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices=devices[:cell.chips],
                      t0=_T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
