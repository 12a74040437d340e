"""A tiny copy of the benchmark for the CPU tests: the real harness,
adapters, readers and generator, with toy configurations, traffic and a
per-layer metric *added as new files and entries only* — which is also the
proof that a later PR can add a cell, a configuration and a metric without
editing a file that is there."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GPT_TINY = {
    "source": "test preset", "n_layer": 2, "n_embd": 64, "n_head": 4,
    "n_inner": 128, "vocab_size": 512, "n_positions": 128,
    "layer_norm_epsilon": 1e-06, "initializer_range": 0.02,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 0.0001,
}
RESNET_TINY = {
    "source": "test preset", "stage_sizes": [1, 1, 1, 1], "num_filters": 8,
    "num_classes": 10, "image_size": 32, "compute_dtype": "float32",
    "param_dtype": "float32", "optimizer": "sgd_momentum",
    "learning_rate": 0.01, "momentum": 0.9,
}
SEQ_TINY = {
    "rows_per_chip": 2, "dataset_rows_per_chip": 8,
    "arrays": [{"name": "ids", "shape": [128], "dtype": "int32", "low": 0,
                "high": "vocab_size"}],
    "items_per_row": 128, "rate_metric": "tokens_per_s_chip",
}
IMG_TINY = {
    "rows_per_chip": 16, "dataset_rows_per_chip": 32,
    "arrays": [{"name": "images", "shape": [3072], "dtype": "uint8",
                "low": 0, "high": 256},
               {"name": "labels", "shape": [], "dtype": "int32", "low": 0,
                "high": "num_classes"}],
    "items_per_row": 1, "rate_metric": "images_per_s_chip",
}
#: a per-layer metric of its own file: steps the window completed
STEPS_READER = '''"""Steps the window completed: a count."""


def read(run):
    return run.steps
'''


def make(tmp: str) -> str:
    """Build the tiny benchmark under ``tmp`` and return its root."""
    root = os.path.join(tmp, "repo")
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)

    def write(rel, data):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(data if isinstance(data, str)
                     else json.dumps(data, indent=1))

    # new files only: two configurations (sizes + a module that re-exports
    # the published configuration's adapter), three traffic mixes (a pair
    # of configuration and traffic is one cell's alone, so the four-chip
    # cell has a file of its own), one reader
    write("configs/gpt2_tiny.json", GPT_TINY)
    write("configs/gpt2_tiny.py",
          "from benchmarks.configs.gpt2_small import *  # noqa: F401,F403\n")
    write("configs/resnet_tiny.json", RESNET_TINY)
    write("configs/resnet_tiny.py",
          "from benchmarks.configs.resnet50 import *  # noqa: F401,F403\n")
    write("traffic/seq128-b2.json", SEQ_TINY)
    write("traffic/seq128-b2x4.json", SEQ_TINY)
    write("traffic/img32-b16.json", IMG_TINY)
    write("layer_metrics/steps_done.py", STEPS_READER)
    # new entries only
    bench["configs"] += [
        {"name": "gpt2_tiny", "source": "test preset",
         "file": "benchmarks/configs/gpt2_tiny.json", "reduced": [],
         "why": "toy"},
        {"name": "resnet_tiny", "source": "test preset",
         "file": "benchmarks/configs/resnet_tiny.json", "reduced": [],
         "why": "toy"}]
    bench["workloads"] += [
        {"name": "tiny-gpt", "config": "gpt2_tiny", "traffic": "seq128-b2",
         "chips": 1, "why": "toy"},
        {"name": "tiny-gpt-dp4", "config": "gpt2_tiny",
         "traffic": "seq128-b2x4", "chips": 4, "why": "toy"},
        {"name": "tiny-resnet", "config": "resnet_tiny",
         "traffic": "img32-b16", "chips": 1, "why": "toy"}]
    tiny_gpt = ["tiny-gpt", "tiny-gpt-dp4"]
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s_chip":
            m["workloads"] += tiny_gpt
        if m["name"] == "images_per_s_chip":
            m["workloads"] += ["tiny-resnet"]
        if m["name"] == "step_ms_p95":
            m["workloads"] += tiny_gpt
    for m in bench["per_layer"]:
        if m["name"] in ("flash_ms", "flash_roofline"):
            m["workloads"] += tiny_gpt
    bench["per_layer"].append(
        {"name": "steps_done", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "harness", "moves": "mfu",
         "workloads": tiny_gpt + ["tiny-resnet"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)
    return root


def small_trace_space(chips=2):
    """Times in ms.  Per chip and step (period 10 ms, three steps from
    t = 1): a forward fusion 0-4, a Mosaic kernel 4-5, an all-reduce-start
    op 5-5.1 whose transfer is in flight 5-8 on the asynchronous line, a
    backward fusion 5.1-7 that hides part of it, an all-reduce-done op
    7-8 that waits for the rest, an update fusion 8-8.3, a synchronous
    all-reduce that ``lax.psum`` named ``psum.7`` 8.3-8.5.  Then the core
    is idle until the next step at +10: 1.5 ms, the host in next_batch for
    the first gap, in epoch_turnover (inside next_batch) for the second."""
    from benchmarks.harness import xplane

    space = xplane._schema()()

    def plane(name):
        p = space.planes.add()
        p.name = name
        return p

    def meta(p, ident, name, **stats):
        entry = p.event_metadata[ident]
        entry.id = ident
        entry.name = name.encode()
        for sname, value in stats.items():
            sid = {"tf_op": 1, "hlo_category": 2}[sname]  # 2: not kept
            s = entry.stats.add()
            s.metadata_id = sid
            s.str_value = value.encode()

    def line(p, name, events):
        ln = p.lines.add()
        ln.name = name
        for ident, start_ms, dur_ms in events:
            e = ln.events.add()
            e.metadata_id = ident
            e.offset_ps = int(round(start_ms * 1e9))
            e.duration_ps = int(round(dur_ms * 1e9))

    for chip in range(chips):
        p = plane(f"/device:TPU:{chip}")
        for sid, sname in ((1, "tf_op"), (2, "hlo_category")):
            p.stat_metadata[sid].id = sid
            p.stat_metadata[sid].name = sname
        meta(p, 1, "%fusion.1 = bf16[8,8] fusion(%p)",
             tf_op="jit(step)/jvp(hvd_forward)/M/dot_general:",
             hlo_category="convolution fusion")
        meta(p, 2, '%attn.2 = f32[8] custom-call(%q), '
             'custom_call_target="tpu_custom_call"',
             tf_op="jit(step)/jvp(hvd_forward)/M/pallas_call:",
             hlo_category="custom-call")
        meta(p, 3, "%all-reduce-start.3 = (f32[1000], f32[24]) "
             "all-reduce-start(%g)", hlo_category="all-reduce-start")
        meta(p, 4, "%fusion.4 = bf16[8,8] fusion(%p)",
             tf_op="jit(step)/transpose(jvp(hvd_forward))/M/dot_general:",
             hlo_category="convolution fusion")
        meta(p, 5, "%all-reduce-done.5 = (f32[1000], f32[24]) "
             "all-reduce-done(%s)", hlo_category="all-reduce-done")
        meta(p, 7, "%psum.7 = f32[500]{0} all-reduce(f32[500]{0} %x), "
             "replica_groups={{0,1}}", tf_op="jit(step)/hvd_grad_allreduce/"
             "psum:", hlo_category="all-reduce")
        meta(p, 6, "%fusion.6 = f32[8] fusion(%p)",
             tf_op="jit(step)/hvd_optimizer_update/add:",
             hlo_category="loop fusion")
        ops, flight = [], []
        for step in range(3):
            t = 1 + 10 * step
            ops += [(1, t, 4), (2, t + 4, 1), (3, t + 5, 0.1),
                    (4, t + 5.1, 1.9), (5, t + 7, 1), (6, t + 8, 0.3),
                    (7, t + 8.3, 0.2)]
            flight.append((3, t + 5, 3))
        line(p, "XLA Ops", ops)
        line(p, "Async XLA Ops", flight)
        line(p, "TC Overlay", [(1, 0, 100)])  # a line the reader skips
    host = plane("/host:CPU")
    for ident, name in ((1, "bench_window"), (2, "bench_next_batch"),
                        (3, "bench_epoch_turnover"), (4, "bench_dispatch"),
                        (5, "bench_loss_fetch"), (6, "$python noise")):
        meta(host, ident, name)
    line(host, "python", [
        (1, 0, 31), (4, 0.2, 0.6), (2, 9.6, 1.2), (4, 10.8, 0.4),
        (2, 19.4, 1.7), (3, 19.6, 1.4), (4, 21.1, 0.3), (5, 22, 8),
        (6, 3, 1)])
    return space


def write_small_trace(directory: str) -> str:
    """The small recorded trace, where ``jax.profiler`` would have put it."""
    d = os.path.join(directory, "plugins", "profile", "run")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as fh:
        fh.write(small_trace_space().SerializeToString())
    return directory
