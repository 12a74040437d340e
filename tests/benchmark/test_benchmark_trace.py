"""The reduction from a profiler trace to numbers, on a small recorded
trace: ``small_trace()`` writes an ``.xplane.pb`` with the schema the
reader parses — two chips, three steps, an asynchronous all-reduce that
compute hides in part, host spans of the loop — and every number is
checked against a hand count."""

import math
import os

import pytest

import benchmark_tiny
from benchmarks.harness import trace, xplane

MS = 1e-3


@pytest.fixture()
def small_trace(tmp_path):
    return benchmark_tiny.write_small_trace(str(tmp_path))


def test_reader_keeps_ops_spans_and_metadata(small_trace):
    raw = xplane.read(xplane.find(small_trace))
    assert sorted(raw.planes) == ["/device:TPU:0", "/device:TPU:1",
                                  "/host:CPU"]
    chip = raw.planes["/device:TPU:0"]
    assert sorted(chip) == ["Async XLA Ops", "XLA Ops"]
    first = chip["XLA Ops"][0]
    assert first.name.startswith("%fusion.1 = ")
    assert first.meta["tf_op"].endswith("hvd_forward)/M/dot_general:")
    assert math.isclose(first.start_s, 1 * MS) \
        and math.isclose(first.dur_s, 4 * MS)


def test_find_wants_exactly_one_file(tmp_path):
    with pytest.raises(RuntimeError, match="expected one .xplane.pb"):
        xplane.find(str(tmp_path))


def test_busy_union_and_idle_share(small_trace):
    r = trace.reduce(xplane.read(xplane.find(small_trace)))
    assert math.isclose(r.window_s, 31 * MS)
    # 8.5 ms of every 10 busy, three steps, both chips alike
    assert math.isclose(r.busy_s(), 3 * 8.5 * MS)
    assert math.isclose(r.idle_share(), 1 - 25.5 / 31)


def test_per_scope_and_kernel_seconds(small_trace):
    r = trace.reduce(xplane.read(xplane.find(small_trace)))
    fwd_bwd = r.op_seconds(lambda op: "hvd_forward" in op.tf_op)
    assert math.isclose(fwd_bwd, 3 * (4 + 1 + 1.9) * MS)
    assert math.isclose(r.op_seconds(trace.is_mosaic_kernel), 3 * 1 * MS)


def test_exposed_allreduce_is_what_compute_does_not_hide(small_trace):
    r = trace.reduce(xplane.read(xplane.find(small_trace)))
    flight, exposed = r.allreduce_seconds()
    # in flight 5-8 of each step, of which the backward fusion hides 5.1-7,
    # and 8.3-8.5 for the one lax.psum named, which nothing hides
    assert math.isclose(flight, 3 * (3 + 0.2) * MS)
    assert math.isclose(exposed, 3 * (0.1 + 1.0 + 0.2) * MS)


def test_gaps_go_to_what_the_host_was_doing(small_trace):
    r = trace.reduce(xplane.read(xplane.find(small_trace)))
    gaps = dict(r.idle_gaps())
    # before the first op the host was dispatching (0-1 ms); gap 9.5-11 sits
    # in next_batch (1.2 ms of it) ; gap 19.5-21 in epoch_turnover; the
    # tail 29.5-31 in loss_fetch
    assert math.isclose(gaps["dispatch"], 1.0 * MS)
    assert math.isclose(gaps["next_batch"], 1.5 * MS)
    assert math.isclose(gaps["epoch_turnover"], 1.5 * MS)
    assert math.isclose(gaps["loss_fetch"], 1.5 * MS)
    assert math.isclose(sum(gaps.values()), (31 - 25.5) * MS)


def test_top_ops_are_named_by_family_and_scope(small_trace):
    r = trace.reduce(xplane.read(xplane.find(small_trace)))
    top = dict(r.top_ops())
    assert math.isclose(top["fusion_hvd_forward/dot_general"],
                        3 * (4 + 1.9) * MS)
    assert math.isclose(top["attn_hvd_forward/pallas_call"], 3 * MS)
    assert "all-reduce-done" in top


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 1), (4, 6)], [(0, 5)]) == [(5, 6)]
    assert trace.clip([(0, 5), (8, 12)], (4, 10)) == [(4, 5), (8, 10)]


def test_a_trace_without_device_ops_is_refused(tmp_path):
    space = benchmark_tiny.small_trace_space(chips=0)
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(space.SerializeToString())
    with pytest.raises(RuntimeError, match="no /device:TPU plane"):
        trace.reduce(xplane.read(xplane.find(str(tmp_path))))


def test_allreduce_bytes_come_from_the_ops_own_text(small_trace):
    import importlib.util

    path = os.path.join(os.path.dirname(xplane.__file__), "..",
                        "layer_metrics", "allreduce_mb.py")
    spec = importlib.util.spec_from_file_location("allreduce_mb", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.result_bytes("%all-reduce-start.3 = (f32[1000], f32[24]) "
                            "all-reduce-start(%g)") == 4096

    class Run:
        reduced = trace.reduce(xplane.read(xplane.find(small_trace)))
        steps = 3

    # the start/done pair counts once, the psum-named all-reduce too
    assert math.isclose(mod.read(Run), (4096 + 2000) / 1e6)
