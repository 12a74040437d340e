"""Digital-twin projection plane (timeline/replay/projection.py).

The pinned numbers come from the hand-computed 2-rank fixture
(fixture.PROJECTION_EXPECTED): identity must bit-match the 450 us
replay baseline, the 2->4 projection lands on 478 us exactly
(alpha 2 -> 6, beta_cal 48 x 1.5 = 72), and the 6-rank local-2/cross-3
two-level projection is the predict_collective_us arithmetic exactly
(576.398 us).  The live 1->8 CPU-mesh drive pins the twin's
projected-vs-measured error inside a band (docs/projection.md
"Accuracy caveats" explains the single-engine-host bias).
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.run.http_client import get_projection, put_projection_summary
from horovod_tpu.run.http_server import RendezvousServer
from horovod_tpu.timeline.comm_report import (
    TopologySpec, model_scaling, predict_collective_us,
)
from horovod_tpu.timeline.replay import analyze
from horovod_tpu.timeline.replay.fixture import (
    EXPECTED, PROJECTION_EXPECTED, write_fixture_trace,
)
from horovod_tpu.timeline.replay.projection import (
    SYNTH_TENSOR, base_spec_from_env, export_projection_gauges,
    live_validation, parse_project_spec, project_analysis, project_dag,
    project_serving_p99, serving_slo_headroom, slowest_source_rank,
    validate,
)
from horovod_tpu.timeline.replay.simulator import CostModel, what_if

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MiB = 1024 * 1024


@pytest.fixture()
def fixture_dir(tmp_path):
    write_fixture_trace(str(tmp_path))
    return str(tmp_path)


@pytest.fixture()
def fixture_result(fixture_dir):
    return analyze(fixture_dir, plan_search=False)


@pytest.fixture()
def base_spec():
    # explicit, env-independent base: default alpha-beta, planner-choice
    # two_level policy (what base_spec_from_env builds on a clean env)
    return TopologySpec(world=2, two_level="auto")


@pytest.fixture()
def server():
    srv = RendezvousServer()
    srv.start()
    yield srv
    srv.stop()


def _synth_trace(root, *, steps=3, step_us=800.0, size=1,
                 shapes=None):
    """A comm-less single-rank trace (SPMD capture shape: STEP envelopes
    only) plus the Recorder manifest the synthesized collective prices."""
    shapes = shapes if shapes is not None else {"g0": [512, 512]}
    d = os.path.join(root, "0")
    os.makedirs(d, exist_ok=True)
    events = [{"name": "STEP", "cat": f"step_{i}", "ph": "X",
               "ts": step_us * i, "dur": step_us, "pid": 0, "tid": "step"}
              for i in range(steps)]
    for fname, payload in (
            ("comm.json", events),
            ("tensor_shapes.json", shapes),
            ("tensor_dtypes.json", {k: "float32" for k in shapes}),
            ("gradient_name_list.json", sorted(shapes)),
            ("metadata.json", {"rank": 0, "size": size})):
        with open(os.path.join(d, fname), "w") as f:
            json.dump(payload, f)
    return root


# ---------------------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------------------
def test_parse_factor_and_absolute_world(base_spec):
    (name, spec), = parse_project_spec("4x", 2, base_spec)
    assert (name, spec.world) == ("4x", 8)
    (name, spec), = parse_project_spec("16", 2, base_spec)
    assert (name, spec.world) == ("8x", 16)
    (name, spec), = parse_project_spec("world=6", 2, base_spec)
    assert (name, spec.world) == ("3x", 6)


def test_parse_doubling_range(base_spec):
    rows = parse_project_spec("2x..16x", 2, base_spec)
    assert [(n, s.world) for n, s in rows] == [
        ("2x", 4), ("4x", 8), ("8x", 16), ("16x", 32)]


def test_parse_kv_overrides(base_spec):
    (name, spec), = parse_project_spec(
        "world=64,local=8,ici_gbps=100,hop_us=2,dcn_gbps=50,"
        "dcn_hop_us=5,compression=int8,two_level=on", 2, base_spec)
    assert spec.world == 64 and spec.local_size == 8
    assert spec.cross_size == 8
    assert spec.ici_bytes_per_sec == 100e9
    assert spec.ici_hop_latency_us == 2.0
    assert spec.dcn_bytes_per_sec == 50e9
    assert spec.dcn_hop_latency_us == 5.0
    assert spec.compression == "int8" and spec.two_level == "on"


def test_parse_identity_row_and_errors(base_spec):
    (name, spec), = parse_project_spec("", 2, base_spec)
    assert name == "identity" and spec.world == 2
    with pytest.raises(ValueError):
        parse_project_spec("bogus", 2, base_spec)
    with pytest.raises(ValueError):
        parse_project_spec("frobnitz=3", 2, base_spec)
    with pytest.raises(ValueError):
        parse_project_spec("two_level=sometimes", 2, base_spec)


# ---------------------------------------------------------------------------
# hand-computed projections (PROJECTION_EXPECTED)
# ---------------------------------------------------------------------------
def test_identity_projection_bit_matches_baseline(fixture_result, base_spec):
    cm = CostModel.from_topology(base_spec)
    summary = project_analysis(
        fixture_result, parse_project_spec("1x", 2, base_spec),
        mode="distribution", cost_model=cm)
    row = summary["projections"][0]
    assert row["name"] == "identity"
    assert row["projected_step_us"] == \
        summary["source"]["baseline_replay_us"] == \
        PROJECTION_EXPECTED["identity_us"]
    assert row["scaling_efficiency"] == 1.0
    assert not row["synthesized_comm"]


def test_projection_2_to_4_exact(fixture_result, base_spec):
    cm = CostModel.from_topology(base_spec)
    summary = project_analysis(
        fixture_result, parse_project_spec("2x", 2, base_spec),
        mode="distribution", cost_model=cm)
    row = summary["projections"][0]
    assert row["world"] == 4
    assert row["projected_step_us"] == PROJECTION_EXPECTED["world4_us"]
    assert row["scaling_efficiency"] == \
        PROJECTION_EXPECTED["world4_efficiency"]
    assert row["wire_formats"] == {"comm:g0:0": "flat"}


def test_projection_2_to_4_dag_structure(fixture_result, base_spec):
    """The re-materialized DAG itself: 4 chains (0/2 clone rank 0,
    1/3 clone rank 1), ONE shared comm node re-priced to 78 us with a
    readiness edge per target rank."""
    dag = fixture_result.dags[0]
    cm = CostModel.from_topology(base_spec)
    (_, spec), = parse_project_spec("2x", 2, base_spec)
    pdag, info = project_dag(dag, cm, spec, mode="distribution")
    assert sorted(pdag.chains) == [0, 1, 2, 3]
    comms = [n for n in pdag.nodes if n.kind == "comm"]
    assert len(comms) == 1
    assert comms[0].dur_us == PROJECTION_EXPECTED["world4_comm_us"]
    assert comms[0].ranks == (0, 1, 2, 3)
    assert set(pdag.ready_pred[comms[0].nid]) == {0, 1, 2, 3}
    # clones carry their source chains: ranks 1/3 lead with the 300 us
    # straggler segment, ranks 0/2 with the 100 us one
    lead = {t: pdag.nodes[chain[0]].dur_us
            for t, chain in pdag.chains.items()}
    assert lead == {0: 100.0, 1: 300.0, 2: 100.0, 3: 300.0}


def test_projection_two_level_six_ranks_exact(fixture_result, base_spec):
    """world=6,local=2 two-level: pure model arithmetic — the projected
    collective equals predict_collective_us' two-level shape and the
    makespan is 300 + comm + 100 exactly."""
    cm = CostModel.from_topology(base_spec)
    specs = parse_project_spec("world=6,local=2,two_level=on", 2, base_spec)
    summary = project_analysis(fixture_result, specs,
                               mode="distribution", cost_model=cm)
    row = summary["projections"][0]
    want_comm = predict_collective_us(
        "all-reduce", EXPECTED["tensor_bytes"], 6,
        two_level=True, local_size=2)
    assert round(300.0 + want_comm + 100.0, 3) == \
        PROJECTION_EXPECTED["world6_local2_us"]
    assert row["projected_step_us"] == PROJECTION_EXPECTED["world6_local2_us"]
    assert row["wire_formats"] == {"comm:g0:0": "two_level"}


def test_slowest_mode_clamps_every_rank(fixture_result, base_spec):
    """slowest mode: every target rank gets rank 1's chain (300 us
    compute, 50 us tail) — makespan 300 + 78 + 50 = 428 us."""
    dag = fixture_result.dags[0]
    assert slowest_source_rank(dag) == 1
    cm = CostModel.from_topology(base_spec)
    (_, spec), = parse_project_spec("2x", 2, base_spec)
    pdag, _ = project_dag(dag, cm, spec, mode="slowest")
    from horovod_tpu.timeline.replay import schedule

    assert round(schedule(pdag).makespan, 3) == 428.0


def test_project_mode_env_default(fixture_result, base_spec, monkeypatch):
    monkeypatch.setenv("HVD_PROJECT_MODE", "slowest")
    cm = CostModel.from_topology(base_spec)
    summary = project_analysis(
        fixture_result, parse_project_spec("2x", 2, base_spec),
        cost_model=cm)
    assert summary["mode"] == "slowest"
    assert summary["projections"][0]["projected_step_us"] == 428.0


# ---------------------------------------------------------------------------
# single-sourced topology math
# ---------------------------------------------------------------------------
def test_model_scaling_routes_through_topology_spec():
    """The SCALING.md tables and a projection price through the same
    TopologySpec arithmetic: model_scaling's per-size comm seconds equal
    the spec's predict_us sum, for flat AND two-level+compressed."""
    cols = {"all-reduce": {"count": 3, "bytes": 100 * MiB},
            "all-gather": {"count": 2, "bytes": 10 * MiB}}
    for kwargs, spec_kw in (
            ({}, {}),
            ({"compression": "int8"}, {}),
            ({"two_level": True, "local_size": 8},
             {"local_size": 8, "two_level": "on"})):
        comm, _ = model_scaling(cols, None, sizes=(16,), **kwargs)
        spec = TopologySpec(world=16, flat_fabric="ici", **spec_kw)
        want = sum(
            spec.predict_us(op, d["bytes"], calls=d["count"],
                            compression=kwargs.get("compression")
                            if op == "all-reduce" else None) * 1e-6
            for op, d in cols.items())
        assert comm[16] == round(want, 6), (kwargs, comm)


def test_wire_choice_policies():
    spec = TopologySpec(world=8, local_size=2, two_level="auto")
    flat = dataclasses.replace(spec, two_level="off")
    on = dataclasses.replace(spec, two_level="on")
    w_auto, us_auto = spec.wire_choice("all-reduce", 64 * MiB)
    _, us_flat = flat.wire_choice("all-reduce", 64 * MiB)
    _, us_on = on.wire_choice("all-reduce", 64 * MiB)
    assert us_auto == min(us_flat, us_on)
    assert w_auto == ("two_level" if us_on < us_flat else "flat")
    # non-all-reduce ops never take the two-level shape
    w, _ = on.wire_choice("all-gather", 64 * MiB)
    assert w == "flat"
    # a spanning spec prices the flat ring at the DCN link
    assert flat.spans_dcn()
    assert us_flat > TopologySpec(world=8).wire_choice(
        "all-reduce", 64 * MiB)[1]


def _four_rank_dag():
    """A hand-built flat 4-rank step: per rank [compute 100][comm 50
    (4 MiB)][tail 50] — small enough to price by hand, big enough for a
    2x2 tier decomposition."""
    from horovod_tpu.timeline.replay.stitcher import Node, StepDAG

    nodes, chains, ready = [], {}, {}
    comm = Node(0, "comm", 50.0, tensor="g0", op="all-reduce",
                nbytes=4 * MiB, label="comm:g0:0", dtype="float32",
                ranks=(0, 1, 2, 3))
    for r in range(4):
        head = Node(len(nodes), "compute", 100.0, rank=r, label="pre")
        nodes.append(head)
    comm.nid = len(nodes)
    nodes.append(comm)
    ready[comm.nid] = {r: r for r in range(4)}
    for r in range(4):
        tail = Node(len(nodes), "compute", 50.0, rank=r, label="tail")
        nodes.append(tail)
        chains[r] = [r, comm.nid, tail.nid]
    return StepDAG(step=0, t0_us=0.0, nodes=nodes, chains=chains,
                   ready_pred=ready,
                   rank_base_us={r: 0.0 for r in range(4)},
                   measured_span_us={r: 200.0 for r in range(4)}, world=4)


def test_what_if_two_level_gate_is_topology_spec_driven():
    """A trace captured on a FLAT world (local_size=1 cost model)
    evaluates the two_level_comm scenario when a hierarchical TARGET
    spec is passed — the scenario is no longer silently gated on the
    currently running hierarchy."""
    dag = _four_rank_dag()
    flat_cm = CostModel(world=4)
    names = lambda wi: {s["scenario"] for s in wi["scenarios"]}  # noqa: E731
    without = what_if(dag, flat_cm, plan_search=False)
    assert "two_level_comm" not in names(without)
    target = TopologySpec(world=64, local_size=2)  # world is overridden
    with_spec = what_if(dag, flat_cm, plan_search=False, topology=target)
    assert "two_level_comm" in names(with_spec)
    assert with_spec["cost_model"]["local_size"] == 2
    # priced for the TRACE's world (4 ranks) decomposed 2x2
    row = next(s for s in with_spec["scenarios"]
               if s["scenario"] == "two_level_comm")
    want = predict_collective_us("all-reduce", 4 * MiB, 4,
                                 two_level=True, local_size=2)
    assert row["predicted_step_us"] == round(100.0 + want + 50.0, 3)


# ---------------------------------------------------------------------------
# synthesized collectives (comm-less SPMD/1-rank traces)
# ---------------------------------------------------------------------------
def test_synthesized_comm_priced_by_spec(tmp_path, base_spec):
    root = _synth_trace(str(tmp_path))
    res = analyze(root, plan_search=False)
    base = dataclasses.replace(base_spec, world=1)
    cm = CostModel.from_topology(base)
    specs = parse_project_spec("8x", 1, base)
    summary = project_analysis(res, specs, mode="distribution",
                               cost_model=cm)
    row = summary["projections"][0]
    nbytes = 512 * 512 * 4
    want = base.with_world(8).predict_us("all-reduce", nbytes)
    assert row["synthesized_comm"] and row["synth_bytes"] == nbytes
    assert row["projected_step_us"] == round(800.0 + want, 3)
    assert f"comm:{SYNTH_TENSOR}" in row["wire_formats"]
    # the spec's wire policy applies to SYNTHESIZED collectives too —
    # a compressed capacity projection must not silently price the
    # comm-less-trace path uncompressed
    (c_name, c_spec), = parse_project_spec("8x,compression=int8", 1, base)
    c_row = project_analysis(res, [(c_name, c_spec)], mode="distribution",
                             cost_model=cm)["projections"][0]
    c_want = base.with_world(8).predict_us("all-reduce", nbytes,
                                           compression="int8")
    assert c_row["projected_step_us"] == round(800.0 + c_want, 3)
    assert c_row["wire_formats"][f"comm:{SYNTH_TENSOR}"] == "flat+int8"


def test_spmd_mesh_trace_bills_marginal_comm_only(tmp_path, base_spec):
    """Projecting a multi-rank SPMD trace (metadata size=8, collectives
    embedded in its compute spans) to a bigger world synthesizes only
    the INCREMENT over the source world's flat price — not a second
    full collective on top of the embedded one."""
    root = _synth_trace(str(tmp_path), size=8)
    res = analyze(root, plan_search=False)
    base = dataclasses.replace(base_spec, world=8)
    summary = project_analysis(
        res, parse_project_spec("2x", 8, base), mode="distribution",
        cost_model=CostModel.from_topology(base.with_world(1)))
    row = summary["projections"][0]
    assert row["world"] == 16 and row["synthesized_comm"]
    nbytes = 512 * 512 * 4
    full = base.with_world(16).predict_us("all-reduce", nbytes)
    embedded = base.with_world(8).predict_us("all-reduce", nbytes)
    assert row["projected_step_us"] == round(800.0 + full - embedded, 3)


def test_identity_of_spmd_mesh_trace_stays_baseline(tmp_path, base_spec):
    """A single-process SPMD trace (one rank dir STANDING for an 8-dev
    mesh via metadata size) projected onto its own job size must not
    synthesize a collective — its in-graph collectives already live
    inside the measured compute spans, and the identity anchor holds."""
    root = _synth_trace(str(tmp_path), size=8)
    res = analyze(root, plan_search=False)
    base = dataclasses.replace(base_spec, world=8)
    summary = project_analysis(
        res, parse_project_spec("", 8, base), mode="distribution",
        cost_model=CostModel.from_topology(base.with_world(1)))
    row = summary["projections"][0]
    assert row["name"] == "identity" and row["world"] == 8
    assert not row["synthesized_comm"]
    assert row["projected_step_us"] == 800.0
    assert summary["source"]["size"] == 8


def test_identity_trusts_measurement_under_declared_hierarchy():
    """At an unchanged world the measured collective duration wins over
    any re-derivation — an env-declared local_size (auto two-level,
    DCN flat fabric) must not re-price the world the trace actually
    ran on.  two_level='on' explicitly opts back into model pricing."""
    from horovod_tpu.timeline.replay.projection import project_comm_dur

    dag = _four_rank_dag()
    comm = next(n for n in dag.nodes if n.kind == "comm")
    cm = CostModel(world=4)
    hier = TopologySpec(world=4, local_size=2, two_level="auto")
    wire, dur = project_comm_dur(comm, cm, hier)
    assert (wire, dur) == ("measured", 50.0)
    forced = dataclasses.replace(hier, two_level="on")
    wire, dur = project_comm_dur(comm, cm, forced)
    assert wire == "two_level"
    assert dur == predict_collective_us("all-reduce", 4 * MiB, 4,
                                        two_level=True, local_size=2)


def test_same_world_link_overrides_are_priced(fixture_result, base_spec):
    """Explicit α–β overrides at an UNCHANGED world re-price ('my world
    on 10x slower links'): the identity short-circuit only fires when
    the spec's link parameters equal the source cost model's.
    Hand math: α = 2 hops x 5 = 10 µs; β_cal = 48 µs x (186/18.6) =
    480 µs → comm 490, makespan 300 + 490 + 100 = 890."""
    cm = CostModel.from_topology(base_spec)
    specs = parse_project_spec("ici_gbps=18.6,hop_us=5", 2, base_spec)
    summary = project_analysis(fixture_result, specs,
                               mode="distribution", cost_model=cm)
    row = summary["projections"][0]
    assert row["world"] == 2
    assert row["wire_formats"] == {"comm:g0:0": "flat"}
    assert row["projected_step_us"] == 890.0


def test_identity_of_commless_trace_stays_baseline(tmp_path, base_spec):
    root = _synth_trace(str(tmp_path))
    res = analyze(root, plan_search=False)
    base = dataclasses.replace(base_spec, world=1)
    summary = project_analysis(
        res, parse_project_spec("1x", 1, base), mode="distribution",
        cost_model=CostModel.from_topology(base))
    row = summary["projections"][0]
    assert not row["synthesized_comm"]
    assert row["projected_step_us"] == 800.0


# ---------------------------------------------------------------------------
# projected-vs-measured accuracy
# ---------------------------------------------------------------------------
def test_validate_between_trace_dirs(tmp_path, base_spec):
    """validate(): project the 1-rank trace onto the measured dir's
    world (metadata size wins over the single rank dir) and report the
    tracked err_pct."""
    src = _synth_trace(str(tmp_path / "src"), step_us=800.0, size=1)
    tgt = _synth_trace(str(tmp_path / "tgt"), step_us=900.0, size=8)
    rec = validate(src, tgt)
    assert rec["source_world"] == 1 and rec["target_world"] == 8
    assert rec["measured_step_us"] == 900.0
    nbytes = 512 * 512 * 4
    want = 800.0 + base_spec_from_env(8).predict_us("all-reduce", nbytes)
    assert rec["projected_step_us"] == round(want, 3)
    assert rec["err_pct"] == round(
        (rec["projected_step_us"] - 900.0) / 900.0 * 100.0, 2)


def test_live_projection_accuracy_band(tmp_path):
    """The live drive: project a really-measured 1-device trace onto the
    really-measured 8-device CPU mesh and serve the record on a signed
    GET /projection.  The error is a ratio of two wall-clock runs on a
    host other workers load, so it is held to being a finite number,
    not to a band; the engine's accuracy is pinned by the deterministic
    cases above (identity bit-match, 2 to 4 exact, two-level exact)."""
    import math

    out = live_validation(root=str(tmp_path))
    assert out["source_world"] == 1 and out["target_world"] == 8
    assert out["projected_step_us"] > 0 and out["measured_step_us"] > 0
    assert math.isfinite(out["err_pct"]), out
    secret = b"live-twin"
    srv = RendezvousServer(secret=secret)
    srv.start()
    try:
        put_projection_summary("127.0.0.1", srv.port,
                               {"validation": out}, secret=secret)
        served = get_projection("127.0.0.1", srv.port, secret=secret)
        assert served["validation"]["err_pct"] == out["err_pct"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# CLI + GET /projection + gauges
# ---------------------------------------------------------------------------
def test_cli_project_json_and_out(fixture_dir, tmp_path, capsys):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from scripts.hvd_replay import main

    out_path = str(tmp_path / "summary.json")
    summary = main([fixture_dir, "--project", "2x..8x",
                    "--no-plan-search", "--out", out_path, "--json"])
    capsys.readouterr()
    proj = summary["projection"]
    assert [r["world"] for r in proj["projections"]] == [4, 8, 16]
    assert proj["projections"][0]["projected_step_us"] == \
        PROJECTION_EXPECTED["world4_us"]
    on_disk = json.loads(open(out_path).read())
    assert on_disk["projection"]["source"]["world"] == 2


def test_cli_project_validate_and_push(tmp_path, server, capsys):
    """--project-validate pins the accuracy record into the summary and
    --push serves the projection on the signed GET /projection."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from scripts.hvd_replay import main

    src = _synth_trace(str(tmp_path / "src"), step_us=800.0, size=1)
    tgt = _synth_trace(str(tmp_path / "tgt"), step_us=900.0, size=8)
    summary = main([src, "--project", "8x", "--no-plan-search",
                    "--project-validate", tgt,
                    "--push", f"127.0.0.1:{server.port}"])
    capsys.readouterr()
    served = get_projection("127.0.0.1", server.port)
    assert served == summary["projection"]
    assert served["validation"]["err_pct"] is not None
    assert server.projection_report() == served


def test_cli_validate_alone_implies_projection(tmp_path, capsys):
    """--project-validate without --project still runs the accuracy
    pin (an implied default projection) instead of silently skipping
    the check the user asked for."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from scripts.hvd_replay import main

    src = _synth_trace(str(tmp_path / "src"), step_us=800.0, size=1)
    tgt = _synth_trace(str(tmp_path / "tgt"), step_us=900.0, size=8)
    summary = main([src, "--no-plan-search", "--project-validate", tgt])
    capsys.readouterr()
    assert summary["projection"]["validation"]["err_pct"] is not None


def test_projection_route_signed_and_404(server):
    secret = b"twin-secret"
    srv = RendezvousServer(secret=secret)
    srv.start()
    try:
        assert get_projection("127.0.0.1", srv.port, secret=secret) is None
        put_projection_summary("127.0.0.1", srv.port, {"projections": []},
                               secret=secret)
        assert get_projection("127.0.0.1", srv.port,
                              secret=secret) == {"projections": []}
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            import urllib.request

            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/projection", timeout=5)
        assert ei.value.code == 401
    finally:
        srv.stop()


def test_projection_gauges_exported(monkeypatch):
    from horovod_tpu import metrics

    monkeypatch.setattr(metrics.registry, "enabled", True)
    summary = {"projections": [
        {"world": 8, "projected_step_us": 478.0,
         "scaling_efficiency": 0.9414}],
        "validation": {"err_pct": -12.5}}
    export_projection_gauges(summary)
    fam = metrics.registry.snapshot()["metrics"]
    step = fam["hvd_projection_step_us"]["samples"]
    assert any(s["labels"] == {"world": "8"} and s["value"] == 478.0
               for s in step)
    eff = fam["hvd_projection_efficiency"]["samples"]
    assert any(s["value"] == 0.9414 for s in eff)
    err = fam["hvd_projection_err_pct"]["samples"]
    assert any(s["value"] == -12.5 for s in err)


def test_project_check_cli_green():
    """`hvd_replay --project --check` — the tier-1 self-test wire."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "hvd_replay.py"),
         "--project", "--check"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr + p.stdout
    assert "bit-matches baseline" in p.stdout


# ---------------------------------------------------------------------------
# serving SLO-headroom hook
# ---------------------------------------------------------------------------
def test_project_serving_p99_math():
    # tail (p99 - p50) scales by R/(R+delta); service floor stays
    assert project_serving_p99(10.0, 50.0, 2, delta=1) == \
        round(10.0 + 40.0 * 2 / 3, 3)
    assert project_serving_p99(10.0, 50.0, 2, delta=-1) == 90.0
    assert project_serving_p99(None, 50.0, 2, delta=1) == \
        round(50.0 * 2 / 3, 3)
    assert project_serving_p99(10.0, None, 2) is None
    assert project_serving_p99(10.0, 50.0, 1, delta=-1) is None
    stats = {"p50_ms": 10.0, "p99_ms": 50.0}
    assert serving_slo_headroom(stats, 2, 100.0, delta=-1) == 10.0
    assert serving_slo_headroom(stats, 2, 80.0, delta=-1) == -10.0
    assert serving_slo_headroom({}, 2, 80.0) is None


class _StubDriver:
    def __init__(self, world):
        self.world = list(world)
        self.spares = []
        self.initial = list(world)
        self.finished = set()
        self.epoch = 0
        self.failed_reason = None
        self.removed = []

    def remove(self, worker, reason, drain=False):
        self.world.remove(worker)
        self.removed.append((worker, drain))
        return True

    def admit_spare(self, reason):
        return None


class _StubBroker:
    def __init__(self, stats):
        self.stats = stats

    def window_stats(self):
        return dict(self.stats)


def test_autoscaler_shrink_held_by_projected_slo_breach(monkeypatch):
    """The predictive guard: idle hysteresis is satisfied, but the twin
    prices p99 at one fewer replica OVER the SLO -> the shrink is held
    and the cooldown it would have started is cancelled."""
    from horovod_tpu.serving.autoscaler import (
        AutoscalePolicy, ServingAutoscaler,
    )

    monkeypatch.delenv("HVD_PROJECT_SLO_GUARD", raising=False)
    # idle queue but a latency tail: p50 5, p99 60 at 2 replicas ->
    # projected p99 at 1 replica = 5 + 55*2 = 115 > SLO 100
    broker = _StubBroker({"queue_depth": 0, "p50_ms": 5.0, "p99_ms": 60.0})
    drv = _StubDriver(["0", "1"])
    scaler = ServingAutoscaler(
        drv, broker, AutoscalePolicy(hysteresis_ticks=1, cooldown_s=0.0,
                                     slo_ms=100.0, queue_low=1.0))
    assert scaler.tick() == "hold"
    assert drv.removed == []
    assert scaler.snapshot()["slo_headroom_ms"]["shrink_ms"] == -15.0
    assert not scaler.policy.in_cooldown()
    # with a comfortable tail the same idle run shrinks
    broker.stats["p99_ms"] = 20.0  # projected @1 = 5 + 15*2 = 35 < 100
    assert scaler.tick() == "shrink"
    assert drv.removed == [("1", True)]


def test_autoscaler_guard_disabled_by_env(monkeypatch):
    from horovod_tpu.serving.autoscaler import (
        AutoscalePolicy, ServingAutoscaler,
    )

    monkeypatch.setenv("HVD_PROJECT_SLO_GUARD", "0")
    broker = _StubBroker({"queue_depth": 0, "p50_ms": 5.0, "p99_ms": 60.0})
    drv = _StubDriver(["0", "1"])
    scaler = ServingAutoscaler(
        drv, broker, AutoscalePolicy(hysteresis_ticks=1, cooldown_s=0.0,
                                     slo_ms=100.0, queue_low=1.0))
    assert scaler.tick() == "shrink"
    assert drv.removed == [("1", True)]


# ---------------------------------------------------------------------------
# bench.py tail leg
# ---------------------------------------------------------------------------
def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_projection_leg_merged_and_skippable(monkeypatch, capsys):
    """projection_err_pct lands in the JSON tail; HVD_BENCH_PROJECTION=0
    skips the child entirely; a failing child degrades to null without
    costing the main number — the autotune/compression-leg contract."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line, rc=0):
            self.returncode = rc
            self.stdout = ("RESULT " + line + "\n") if rc == 0 else ""
            self.stderr = "boom"

    calls = []
    fail_projection = [False]

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-projection" in cmd:
            if fail_projection[0]:
                return FakeProc("", rc=1)
            return FakeProc(json.dumps({"projection_err_pct": -31.4,
                                        "projected_step_us": 2000.0,
                                        "measured_step_us": 2915.0}))
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench, "_compression_delta", lambda v: {})
    monkeypatch.setattr(bench, "_serving_leg", lambda: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_PROJECTION", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["projection_err_pct"] == -31.4
    assert any("--child-projection" in c for c in calls)

    # failure: null, never costs the main number
    fail_projection[0] = True
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["projection_err_pct"] is None
    assert "projection_error" in out

    # skip: no child, no tail fields
    calls.clear()
    monkeypatch.setenv("HVD_BENCH_PROJECTION", "0")
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert "projection_err_pct" not in out
    assert not any("--child-projection" in c for c in calls)
