"""The harness end to end on the CPU at a tiny preset: a run prints a
well-formed result, a broken timed path comes out not correct, a cell, a
configuration and a per-layer metric are added with new files and entries
only, and a name with no file is an error that names it."""

import filecmp
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import benchmark_tiny
from benchmarks import run as bench_run
from benchmarks.harness import loop, xplane
from benchmarks.harness.peaks import Peak
from benchmarks.harness.spec import Spec, SpecError

#: a CPU has no published peak; the table takes no default, a test passes
#: its own
PEAKS = {"cpu": Peak(1e12, 1e11, 2 ** 34)}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return benchmark_tiny.make(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture()
def world():
    """``run_cell`` starts and stops the framework itself; leave none."""
    import horovod_tpu as hvd

    hvd.shutdown()
    yield
    hvd.shutdown()


def _run(root, workload, chips, *, trace=False, seed=2 ** 31 + 17, **kw):
    return bench_run.run_cell(
        Spec(root), workload, seed, 0.4, trace,
        devices=jax.devices("cpu")[:chips], peaks=PEAKS, **kw)


def _well_formed(result, cell, chips):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert "memory_peak_bytes" in result["device"]
    for entry in result["metrics"].values():
        assert set(entry) == {"value", "unit"}
        assert isinstance(entry["value"], float)
    json.loads(json.dumps(result))  # one JSON object, nothing exotic in it


@pytest.mark.parametrize("workload,chips,metrics", [
    ("tiny-gpt", 1, {"tokens_per_s_chip", "mfu", "step_ms_p95", "setup_s"}),
    # four virtual devices: the data-parallel path, all-reduce and all
    ("tiny-gpt-dp4", 4, {"tokens_per_s_chip", "mfu", "step_ms_p95",
                         "setup_s"}),
    ("tiny-resnet", 1, {"images_per_s_chip", "mfu", "setup_s"}),
])
def test_cell_runs_end_to_end(tiny_root, world, workload, chips, metrics,
                              capsys):
    result = _run(tiny_root, workload, chips)
    _well_formed(result, workload, chips)
    assert result["correct"] is True
    assert set(result["metrics"]) == metrics
    assert all(v["value"] > 0 for v in result["metrics"].values())
    out = capsys.readouterr().out
    # every compared number is printed beside its limit
    for name in ("loss_gap", "grad_norm_gap", "update_norm_gap",
                 "final_loss", "nonfinite_losses"):
        assert f"check: {name} = " in out and "limit" in out


def _state_unchanged(step):
    def broken(state, x, y):
        # the real step donates its state: give it a copy, return the old
        _, loss = step(jax.tree_util.tree_map(jnp.copy, state), x, y)
        return state, loss
    return broken


def _half_the_batch(step):
    def broken(state, x, y):
        half = x.shape[0] // 2
        again = lambda a: jnp.concatenate([a[:half], a[:half]])  # noqa: E731
        return step(state, again(x), again(y))
    return broken


def _halved_conv_gradient(step, leaf="BottleneckBlock_1/Conv_1/kernel",
                          lr=benchmark_tiny.RESNET_TINY["learning_rate"]):
    """A 3x3 convolution inside a residual branch whose weight gradient
    comes out at half its size: what the optimizer receives for that
    kernel (its momentum trace) and the update made from it are altered
    where the step produces them."""
    from jax.tree_util import tree_map, tree_map_with_path

    def here(path):
        return "/".join(str(p.key) for p in path) == leaf

    def broken(state, x, y):
        before = tree_map(jnp.copy, state.params)  # the step donates them
        new, loss = step(state, x, y)
        momentum = new.opt_state[0]
        trace = tree_map_with_path(
            lambda p, t: 0.5 * t if here(p) else t, momentum.trace)
        params = tree_map_with_path(
            lambda p, b, n, t: b - lr * t if here(p) else n,
            before, new.params, trace)
        return new._replace(
            params=params,
            opt_state=(momentum._replace(trace=trace),
                       *new.opt_state[1:])), loss
    return broken


@pytest.mark.parametrize("workload,break_step,number", [
    ("tiny-gpt", _state_unchanged, "update_norm_gap"),
    ("tiny-gpt", _half_the_batch, "loss_gap"),
    # the seeded weights leave every residual branch open, so the backward
    # pass of the convolutions inside them is held to account
    ("tiny-resnet", _halved_conv_gradient, "grad_norm_gap"),
])
def test_broken_timed_path_is_not_correct(tiny_root, world, workload,
                                          break_step, number, capsys):
    result = _run(tiny_root, workload, 1, break_step=break_step)
    _well_formed(result, workload, 1)
    assert result["correct"] is False
    assert any(line.startswith(f"check: {number} = ") and "OVER" in line
               for line in capsys.readouterr().out.splitlines())


def test_traced_run_reports_per_layer_metrics(tiny_root, world, tmp_path,
                                              monkeypatch):
    """A CPU writes no device plane, so the small recorded trace stands in
    for the file the profiler wrote; everything else is the traced run."""
    recorded = xplane.read(xplane.find(
        benchmark_tiny.write_small_trace(str(tmp_path / "recorded"))))
    monkeypatch.setattr(xplane, "read", lambda path: recorded)
    result = _run(tiny_root, "tiny-gpt", 1, trace=True,
                  scratch=str(tmp_path))
    _well_formed(result, "tiny-gpt", 1)
    m = result["metrics"]
    # every reader that found something, and the one the tiny benchmark
    # added as a file of its own; nothing of the four-chip or conv cells
    assert set(m) == {"init_s", "compile_s", "input_wait_ms", "dispatch_ms",
                      "fwd_bwd_ms", "flash_ms", "flash_roofline",
                      "device_idle_pct", "hbm_gb", "steps_done"}
    assert m["steps_done"]["value"] == result["attempted"]
    assert m["steps_done"]["unit"] == "steps"
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert {n for n, _ in result["breakdown"]["idle_gaps"]} <= {
        "next_batch", "dispatch", "loss_fetch", "epoch_turnover",
        "between_ops", "other"}


def test_tiny_benchmark_only_adds_files_and_entries(tiny_root):
    """The cell, the configurations, the traffic and the per-layer metric
    above came as new files and new entries: nothing the benchmark had was
    edited."""
    real = os.path.join(benchmark_tiny.REPO, "benchmarks")
    added = set()
    for sub in ("", "configs", "traffic", "layer_metrics", "harness",
                "references"):
        cmp = filecmp.dircmp(os.path.join(real, sub),
                             os.path.join(tiny_root, "benchmarks", sub),
                             ignore=["__pycache__"])
        assert not cmp.diff_files and not cmp.left_only, (sub, cmp.diff_files)
        added |= {os.path.join(sub, f) for f in cmp.right_only}
    assert added == {
        "configs/gpt2_tiny.json", "configs/gpt2_tiny.py",
        "configs/resnet_tiny.json", "configs/resnet_tiny.py",
        "traffic/seq128-b2.json", "traffic/seq128-b2x4.json",
        "traffic/img32-b16.json", "layer_metrics/steps_done.py"}
    with open(os.path.join(benchmark_tiny.REPO, "BENCHMARK.json")) as fh:
        real_spec = json.load(fh)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as fh:
        tiny_spec = json.load(fh)
    for key in ("configs", "workloads"):
        assert tiny_spec[key][:len(real_spec[key])] == real_spec[key]
    # a metric's entry changes only in the cells it lists
    for mine, theirs in zip(tiny_spec["per_layer"], real_spec["per_layer"]):
        assert {k: v for k, v in mine.items() if k != "workloads"} \
            == {k: v for k, v in theirs.items() if k != "workloads"}


@pytest.mark.parametrize("remove,workload,names", [
    ("traffic/seq128-b2.json", "tiny-gpt", "traffic 'seq128-b2'"),
    ("layer_metrics/steps_done.py", "tiny-gpt",
     "per-layer metric 'steps_done'"),
    ("configs/resnet_tiny.py", "tiny-resnet",
     "configuration 'resnet_tiny'"),
    ("configs/resnet_tiny.json", "tiny-resnet",
     "configuration 'resnet_tiny'"),
])
def test_a_name_without_its_file_is_an_error_that_names_it(
        tmp_path, remove, workload, names):
    root = benchmark_tiny.make(str(tmp_path))
    os.remove(os.path.join(root, "benchmarks", remove))
    with pytest.raises(SpecError) as err:
        Spec(root).cell(workload)
    assert names in str(err.value) and remove in str(err.value)


def test_unknown_workload_lists_the_known_ones(tiny_root):
    with pytest.raises(SpecError, match="'nope' is not in BENCHMARK.json; "
                                        "known: gpt2s-1k, resnet50-b256"):
        Spec(tiny_root).cell("nope")


def _real_cells():
    with open(os.path.join(benchmark_tiny.REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)["workloads"]


@pytest.mark.parametrize("entry", _real_cells(), ids=lambda w: w["name"])
def test_a_cell_of_the_real_benchmark_finds_its_files(entry):
    """The cells are ``BENCHMARK.json``'s own, whatever their number: a
    later PR's cell is a case here without an edit."""
    spec = Spec(benchmark_tiny.REPO)
    cell = spec.cell(entry["name"])
    assert (cell.config, cell.traffic, cell.chips) == (
        entry["config"], entry["traffic"], entry["chips"])
    assert "setup_s" in cell.end_to_end and "mfu" in cell.end_to_end
    # the rate the cell reports is the one its traffic is counted in
    rates = [m for m in cell.end_to_end if m.endswith("_per_s_chip")]
    assert rates == [cell.mix["rate_metric"]]
    assert cell.per_layer and all(
        hasattr(m, "read") for m in cell.per_layer.values())
    assert cell.adapter.flops_per_item(cell.cfg, cell.mix) > 0
    limits = cell.adapter.limits(cell.cfg, cell.mix)
    assert {"loss_gap", "grad_norm_gap", "grad_sketch_gap",
            "update_norm_gap", "final_loss", "nonfinite_losses",
            "batch_shards_missing",
            "state_leaves_not_replicated"} <= set(limits)
    assert len(entry["why"]) <= 200


def test_a_traffic_file_is_one_cells_only():
    """A pair of configuration and traffic is one cell's only (the driver
    refused the file that named a traffic twice: PERF.md section 4)."""
    cells = _real_cells()
    assert len({w["traffic"] for w in cells}) == len(cells)
    assert len({w["name"] for w in cells}) == len(cells)


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(benchmark_tiny.REPO, "benchmarks",
                                      "run.py"),
         "--workload", "gpt2s-1k", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=benchmark_tiny.REPO)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert '"metrics"' not in proc.stdout
    # the earlier line still names what JAX found
    assert "device: platform cpu, device_kind cpu, count" in proc.stdout
    assert "needs 1 TPU chip(s)" in proc.stderr


def test_step_intervals_are_between_consecutive_completions():
    completions = [0.0, 0.1, 0.2, 0.4, 0.5]
    assert loop.step_intervals_ms(completions) == pytest.approx(
        [100.0, 100.0, 200.0, 100.0])
