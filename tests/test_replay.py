"""Replay engine (timeline/replay/): clock handshake, stitcher,
critical path, what-if simulation, CLI smoke, and the GET /replay route.

The pinned numbers come from the hand-computed fixture
(horovod_tpu/timeline/replay/fixture.py): a 2-rank step whose schedule
fits on a napkin — rank 1 computes 300 us while rank 0 waits, a 50 us
allreduce, then tails of 100/50 us -> 450 us makespan, 250 us if the
straggler were as fast as rank 0."""

import importlib.util as _ilu
import json
import os

import pytest

from horovod_tpu.run.http_client import (
    get_clock, get_replay, put_replay_summary,
)
from horovod_tpu.run.http_server import RendezvousServer
from horovod_tpu.timeline.replay import (
    analyze, annotated_trace, critical_path, schedule,
)
from horovod_tpu.timeline.replay.clock import estimate_offset
from horovod_tpu.timeline.replay.fixture import (
    EXPECTED, write_fixture_trace,
)
from horovod_tpu.timeline.replay.simulator import CostModel, fused_dag
from horovod_tpu.timeline.replay.stitcher import read_gml, stitch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fixture_dir(tmp_path):
    write_fixture_trace(str(tmp_path))
    return str(tmp_path)


@pytest.fixture()
def server():
    srv = RendezvousServer()
    srv.start()
    yield srv
    srv.stop()


# ---------------------------------------------------------------------------
# clock handshake
# ---------------------------------------------------------------------------
def test_estimate_offset_against_real_server(server):
    est = estimate_offset("127.0.0.1", server.port, samples=4)
    # server and client share one process clock -> offset ~ 0 (network
    # stack noise only); rtt must be positive and sane
    assert abs(est["offset_us"]) < 50_000
    assert 0 < est["rtt_us"] < 5_000_000
    assert est["samples"] == 4


def test_get_clock_is_monotonic(server):
    a = get_clock("127.0.0.1", server.port)
    b = get_clock("127.0.0.1", server.port)
    assert b >= a > 0


def test_timeline_initialize_writes_clock_sidecar(server, tmp_path,
                                                  monkeypatch):
    from horovod_tpu.timeline.timeline import Timeline

    monkeypatch.setenv("HVD_TIMELINE_PYTHON", "1")
    monkeypatch.setenv("HVD_METRICS_KV_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_METRICS_KV_PORT", str(server.port))
    monkeypatch.setenv("HVD_REPLAY_CLOCK_SAMPLES", "2")
    tl = Timeline()
    tl.initialize(str(tmp_path))
    tl.shutdown()
    sidecar = tmp_path / "0" / "clock_sync.json"
    assert sidecar.is_file()
    d = json.loads(sidecar.read_text())
    assert "offset_us" in d and d["rtt_us"] > 0 and d["rank"] == 0


def test_timeline_clock_sync_disabled_by_knob(server, tmp_path,
                                              monkeypatch):
    from horovod_tpu.timeline.timeline import Timeline

    monkeypatch.setenv("HVD_TIMELINE_PYTHON", "1")
    monkeypatch.setenv("HVD_METRICS_KV_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_METRICS_KV_PORT", str(server.port))
    monkeypatch.setenv("HVD_REPLAY_CLOCK_SYNC", "0")
    tl = Timeline()
    tl.initialize(str(tmp_path))
    tl.shutdown()
    assert not (tmp_path / "0" / "clock_sync.json").exists()


# ---------------------------------------------------------------------------
# stitcher
# ---------------------------------------------------------------------------
def test_stitch_fixture_joins_all_artifacts(fixture_dir):
    art, dags = stitch(fixture_dir)
    assert art.ranks == [0, 1]
    assert art.clock_aligned
    assert art.clock_offsets_us == {0: 0.0, 1: 25.0}
    assert len(dags) == 1
    dag = dags[0]
    assert dag.step == 1 and dag.world == 2
    comms = [n for n in dag.nodes if n.kind == "comm"]
    assert len(comms) == 1
    c = comms[0]
    assert c.tensor == "g0" and c.op == "all-reduce"
    assert c.nbytes == EXPECTED["tensor_bytes"]  # joined via shapes
    assert c.ranks == (0, 1)
    assert c.dag_label == "allreduce/g0"         # joined via dag.gml


def test_stitcher_one_compute_node_between_two_collectives(fixture_dir):
    """A rank's compute between two collectives (and after the last) is
    one node: the replay fixture's own --check contract."""
    _art, dags = stitch(fixture_dir)
    labels = [n.label for n in dags[0].nodes if n.kind == "compute"]
    assert labels == ["pre:g0:0", "tail", "pre:g0:0", "tail"]


def test_read_gml_roundtrip(tmp_path):
    from horovod_tpu.timeline.recorder import structure_dag, write_gml

    nodes, edges = structure_dag(["a", "b"])
    path = str(tmp_path / "dag.gml")
    write_gml(nodes, edges, path)
    rnodes, redges = read_gml(path)
    assert [n["label"] for n in rnodes] == [n["label"] for n in nodes]
    assert redges == edges


def test_stitch_applies_clock_offsets(fixture_dir):
    """Rank 1's raw trace is 25 us behind; after alignment both ranks'
    ALLREDUCE spans start at the same aligned instant."""
    art, _ = stitch(fixture_dir)
    starts = {}
    for rank, evs in art.events.items():
        for ev in evs:
            if ev.get("name") == "ALLREDUCE":
                starts[rank] = ev["ts"]
    assert starts[0] == pytest.approx(starts[1])


# ---------------------------------------------------------------------------
# critical path + attribution (acceptance: exact on the fixture)
# ---------------------------------------------------------------------------
def test_fixture_critical_path_exact(fixture_dir):
    res = analyze(fixture_dir)
    s = res.summary["steps"][0]
    assert s["replay_step_us"] == pytest.approx(EXPECTED["makespan_us"])
    assert s["measured_step_us"] == pytest.approx(EXPECTED["makespan_us"])
    assert s["replay_error_pct"] == pytest.approx(0.0)
    got = [(r["kind"], r["rank"], r["dur_us"]) for r in s["critical_path"]]
    want = [(r["kind"], r.get("rank"), r["dur_us"])
            for r in EXPECTED["critical_path"]]
    assert got == want
    # the path's durations account for every us of the makespan
    assert sum(r["dur_us"] for r in s["critical_path"]) == pytest.approx(
        s["replay_step_us"])


def test_fixture_attribution_pinned(fixture_dir):
    res = analyze(fixture_dir)
    attr = res.summary["steps"][0]["attribution"]
    for rank, want in EXPECTED["attribution"].items():
        got = attr["per_rank"][rank]
        for k, v in want.items():
            assert got[k] == pytest.approx(v), (rank, k)
    # per-tensor view: rank 0 waited 200 us on g0, rank 1 (straggler) 0
    t = attr["per_tensor"]["comm:g0:0"]
    assert t["per_rank_wait_us"] == {"0": 200.0, "1": 0.0}
    assert t["spread_us"] == pytest.approx(200.0)
    assert t["straggler_rank"] == 1


# ---------------------------------------------------------------------------
# what-if simulation (acceptance: remove-straggler within 5%)
# ---------------------------------------------------------------------------
def test_what_if_remove_straggler_within_5pct(fixture_dir):
    res = analyze(fixture_dir)
    wi = res.summary["steps"][0]["what_if"]
    assert wi["straggler_rank"] == EXPECTED["straggler_rank"]
    by_name = {s["scenario"]: s for s in wi["scenarios"]}
    got = by_name[f"remove_straggler_rank_{EXPECTED['straggler_rank']}"]
    want = EXPECTED["remove_straggler_us"]
    assert abs(got["predicted_step_us"] - want) / want <= 0.05
    # on the fixture the scenario is exactly computable: 100+50+100
    assert got["predicted_step_us"] == pytest.approx(250.0)


def test_what_if_bandwidth_scales_beta_only(fixture_dir):
    """2 ranks, allreduce: alpha = 2 hops x 1 us = 2 us; measured 50 us
    -> beta 48 us; x2 bandwidth -> 2 + 24 = 26 us comm, 426 us step."""
    res = analyze(fixture_dir)
    by_name = {s["scenario"]: s
               for s in res.summary["steps"][0]["what_if"]["scenarios"]}
    assert by_name["ici_bandwidth_x2"]["predicted_step_us"] == \
        pytest.approx(426.0)
    assert by_name["ici_bandwidth_x4"]["predicted_step_us"] == \
        pytest.approx(414.0)


def test_what_if_overlap_comm(fixture_dir):
    """Overlapped, rank 0's tail no longer waits for the collective:
    step end = comm end (350) on both ranks."""
    res = analyze(fixture_dir)
    by_name = {s["scenario"]: s
               for s in res.summary["steps"][0]["what_if"]["scenarios"]}
    assert by_name["overlap_comm"]["predicted_step_us"] == \
        pytest.approx(350.0)


def test_what_if_ranked_by_speedup(fixture_dir):
    res = analyze(fixture_dir)
    wi = res.summary["steps"][0]["what_if"]["scenarios"]
    preds = [s["predicted_step_us"] for s in wi]
    assert preds == sorted(preds)
    recs = res.summary["recommendations"]
    assert recs[0]["scenario"] == "remove_straggler_rank_1"


def _two_tensor_trace(tmp_path):
    """Two back-to-back 4 MiB allreduces per rank, no skew: fusion has
    something to re-batch."""
    for rank in (0, 1):
        d = tmp_path / str(rank)
        d.mkdir(parents=True, exist_ok=True)
        evs = [{"name": "STEP", "cat": "step_1", "ph": "X", "ts": 0.0,
                "dur": 400.0, "pid": rank, "tid": "step"}]
        for i, t in enumerate(("g0", "g1")):
            base = 100.0 + i * 100.0
            evs += [
                {"name": "NEGOTIATE_ALLREDUCE", "cat": t, "ph": "B",
                 "ts": base, "pid": rank, "tid": t},
                {"name": "NEGOTIATE_ALLREDUCE", "cat": t, "ph": "E",
                 "ts": base, "pid": rank, "tid": t},
                {"name": "ALLREDUCE", "cat": t, "ph": "X", "ts": base,
                 "dur": 50.0, "pid": rank, "tid": t},
            ]
        (d / "comm.json").write_text(json.dumps(evs))
        (d / "tensor_shapes.json").write_text(
            json.dumps({"g0": [1024, 1024], "g1": [1024, 1024]}))
    return str(tmp_path)


def test_fuse_all_rebatches_to_one_alpha(tmp_path):
    d = _two_tensor_trace(tmp_path)
    art, dags = stitch(d)
    dag = dags[0]
    cm = CostModel(world=2)
    fdag = fused_dag(dag, cm)
    assert fdag is not None
    comms = [n for n in fdag.nodes if n.kind == "comm"]
    assert len(comms) == 1
    # one alpha (2 us) + summed calibrated betas (48 us each)
    assert comms[0].dur_us == pytest.approx(2.0 + 48.0 * 2)
    assert comms[0].nbytes == 2 * 1024 * 1024 * 4
    # fused schedule still a DAG and no slower than serial comm
    fsched = schedule(fdag)
    assert fsched.makespan <= schedule(dag).makespan + 1e-6


def test_cost_table_agrees_with_comm_report_model(fixture_dir):
    from horovod_tpu.timeline.comm_report import predict_collective_us

    res = analyze(fixture_dir)
    row = res.summary["steps"][0]["cost_model_table"]["g0"]
    cmdl = res.summary["steps"][0]["what_if"]["cost_model"]
    want = predict_collective_us(
        "all-reduce", row["bytes"], cmdl["world"],
        ici_bytes_per_sec=cmdl["ici_bytes_per_sec"],
        ici_hop_latency=cmdl["hop_latency_us"] * 1e-6)
    assert row["predicted_us"] == pytest.approx(want, abs=1e-3)
    assert row["measured_us"] == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# annotated trace
# ---------------------------------------------------------------------------
def test_annotated_trace_highlights_critical_path(fixture_dir, tmp_path):
    out = tmp_path / "replay_trace.json"
    tr = annotated_trace(fixture_dir, out_path=str(out))
    assert json.loads(out.read_text()) == tr
    cp = [e for e in tr["traceEvents"] if e.get("pid") == 9999
          and e.get("ph") == "X"]
    assert len(cp) == len(EXPECTED["critical_path"])
    assert [e["args"]["kind"] for e in cp] == \
        [r["kind"] for r in EXPECTED["critical_path"]]
    # rank rows still present alongside the critical-path track
    assert {e["pid"] for e in tr["traceEvents"]} >= {0, 1, 9999}


# ---------------------------------------------------------------------------
# CLI + GET /replay (acceptance: server serves what the CLI prints)
# ---------------------------------------------------------------------------
def _load_cli():
    spec = _ilu.spec_from_file_location(
        "hvd_replay", os.path.join(REPO, "scripts", "hvd_replay.py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_check_smoke():
    """The tier-1 smoke the ISSUE pins: --check exits 0 on the fixture."""
    cli = _load_cli()
    with pytest.raises(SystemExit) as e:
        cli.main(["--check"])
    assert e.value.code == 0


def test_cli_json_out_and_text(fixture_dir, tmp_path, capsys):
    cli = _load_cli()
    out = tmp_path / "summary.json"
    summary = cli.main([fixture_dir, "--out", str(out)])
    assert json.loads(out.read_text()) == summary
    text = capsys.readouterr().out
    assert "critical path" in text and "remove_straggler_rank_1" in text
    summary2 = cli.main([fixture_dir, "--json"])
    assert json.loads(capsys.readouterr().out) == summary2


def test_get_replay_serves_cli_summary(fixture_dir, server, capsys):
    cli = _load_cli()
    summary = cli.main([fixture_dir, "--json",
                        "--push", f"127.0.0.1:{server.port}"])
    capsys.readouterr()
    assert get_replay("127.0.0.1", server.port) == summary


def test_get_replay_404_when_unpublished(server):
    assert get_replay("127.0.0.1", server.port) is None


def test_replay_routes_signed(fixture_dir):
    """A secret-bearing server rejects unsigned /replay + /clock but
    serves signed requests — same contract as /metrics."""
    import urllib.error

    secret = b"s3cr3t"
    srv = RendezvousServer(secret=secret)
    srv.start()
    try:
        put_replay_summary("127.0.0.1", srv.port, {"ok": 1},
                           secret=secret)
        assert get_replay("127.0.0.1", srv.port, secret=secret) == {"ok": 1}
        assert get_clock("127.0.0.1", srv.port, secret=secret) > 0
        with pytest.raises(urllib.error.HTTPError):
            get_replay("127.0.0.1", srv.port)
        with pytest.raises(urllib.error.HTTPError):
            get_clock("127.0.0.1", srv.port)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# recorder artifact extension (bytes join source)
# ---------------------------------------------------------------------------
def test_register_gradients_dumps_shapes_and_dtypes(tmp_path):
    import numpy as np

    from horovod_tpu.timeline.recorder import Recorder

    rec = Recorder(str(tmp_path), rank=0)
    rec.register_gradients({"w": np.zeros((4, 2), np.float32),
                            "b": np.zeros((2,), np.float32)})
    d = tmp_path / "0"
    shapes = json.loads((d / "tensor_shapes.json").read_text())
    dtypes = json.loads((d / "tensor_dtypes.json").read_text())
    assert shapes["gradients/w"] == [4, 2]
    assert dtypes["gradients/b"] == "float32"
    names = json.loads((d / "gradient_name_list.json").read_text())
    assert set(names) == {"gradients/w", "gradients/b"}
