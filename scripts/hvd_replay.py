"""Replay a merged byteprofile trace: critical path + what-if scenarios.

The dPRO-style closer for the capture stack: stitch
``<trace_dir>/<rank>/comm.json`` + Recorder artifacts into a global
per-step DAG (clock-aligned via each rank's ``clock_sync.json``), report
the critical path and {compute, negotiation, comm, idle} attribution,
and rank what-if scenarios (remove straggler, scale ICI bandwidth,
perfect overlap, fuse-all re-batching) by predicted speedup.

The digital-twin plane (docs/projection.md) rides the same CLI:
``--project <spec>`` re-materializes the stitched DAG onto hypothetical
topologies (``2x..64x`` sweeps, ``world=64,local=8,compression=int8``
specs), ``--project-validate <dir>`` pins projected-vs-measured error
against a trace we actually ran, and ``--push`` serves the projection
summary on the rendezvous server's signed ``GET /projection``.

Run::

    python scripts/hvd_replay.py <trace_dir> \
        [--step N] [--json] [--out summary.json] \
        [--annotated replay_trace.json] \
        [--project SPEC [--project SPEC ...]] \
        [--project-mode distribution|slowest] \
        [--project-validate measured_trace_dir] \
        [--push host:port [--secret HEX]]    # GET /replay + /projection
    python scripts/hvd_replay.py --check             # replay self-test
    python scripts/hvd_replay.py --project --check   # projection self-test
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.timeline.replay import analyze, annotated_trace  # noqa: E402


def run_check() -> int:
    """Self-test on the hand-computed fixture: the critical path must
    match exactly and the remove-straggler prediction within 5% — the
    acceptance bar the engine's unit tests also pin."""
    from horovod_tpu.timeline.replay.fixture import write_fixture_trace

    with tempfile.TemporaryDirectory(prefix="hvd_replay_check_") as d:
        exp = write_fixture_trace(d)
        res = analyze(d)
        s = res.summary["steps"][0]
        errors = []
        if abs(s["replay_step_us"] - exp["makespan_us"]) > 1e-3:
            errors.append(
                f"makespan {s['replay_step_us']} != {exp['makespan_us']}")
        got_cp = [(r["kind"], r["rank"], round(r["dur_us"], 3))
                  for r in s["critical_path"]]
        want_cp = [(r["kind"], r.get("rank"), r["dur_us"])
                   for r in exp["critical_path"]]
        if got_cp != want_cp:
            errors.append(f"critical path {got_cp} != {want_cp}")
        wi = {sc["scenario"]: sc["predicted_step_us"]
              for sc in s["what_if"]["scenarios"]}
        key = f"remove_straggler_rank_{exp['straggler_rank']}"
        want = exp["remove_straggler_us"]
        got = wi.get(key)
        if got is None or abs(got - want) / want > 0.05:
            errors.append(f"{key} predicted {got}, want {want} ±5%")
        if not res.summary["clock_aligned"]:
            errors.append("fixture clock offsets not applied")
        if errors:
            print("hvd_replay --check FAILED:", file=sys.stderr)
            for e in errors:
                print(f"  {e}", file=sys.stderr)
            return 1
        print(f"hvd_replay --check OK: critical path exact, "
              f"{key} = {got:.1f} us (hand-computed {want:.1f})")
        return 0


def run_project_check() -> int:
    """Projection self-test on the same hand-computed fixture
    (fixture.PROJECTION_EXPECTED): the identity projection must
    bit-match the replay baseline, the 2→4 projection must recover the
    hand-computed 478 µs exactly, and the 6-rank local-2/cross-3
    two-level projection must land on the model arithmetic exactly."""
    from horovod_tpu.timeline.comm_report import TopologySpec
    from horovod_tpu.timeline.replay import analyze
    from horovod_tpu.timeline.replay.fixture import (
        PROJECTION_EXPECTED, write_fixture_trace,
    )
    from horovod_tpu.timeline.replay.projection import (
        parse_project_spec, project_analysis,
    )
    from horovod_tpu.timeline.replay.simulator import CostModel

    exp = PROJECTION_EXPECTED
    with tempfile.TemporaryDirectory(prefix="hvd_project_check_") as d:
        write_fixture_trace(d)
        res = analyze(d, plan_search=False)
        base = TopologySpec(world=2, two_level="auto",
                            ici_hop_latency_us=exp["hop_latency_us"])
        specs = (parse_project_spec("1x", 2, base)
                 + parse_project_spec("2x", 2, base)
                 + parse_project_spec("world=6,local=2,two_level=on",
                                      2, base))
        summary = project_analysis(
            res, specs, mode="distribution",
            cost_model=CostModel.from_topology(base))
        rows = {r["world"]: r for r in summary["projections"]}
        errors = []
        base_us = summary["source"]["baseline_replay_us"]
        if rows[2]["projected_step_us"] != base_us:
            errors.append(
                f"identity projection {rows[2]['projected_step_us']} != "
                f"replay baseline {base_us} (must bit-match)")
        if rows[2]["projected_step_us"] != exp["identity_us"]:
            errors.append(f"identity {rows[2]['projected_step_us']} != "
                          f"{exp['identity_us']}")
        if rows[4]["projected_step_us"] != exp["world4_us"]:
            errors.append(f"2x projection {rows[4]['projected_step_us']} "
                          f"!= hand-computed {exp['world4_us']}")
        if rows[4]["scaling_efficiency"] != exp["world4_efficiency"]:
            errors.append(f"2x efficiency {rows[4]['scaling_efficiency']} "
                          f"!= {exp['world4_efficiency']}")
        if rows[6]["projected_step_us"] != exp["world6_local2_us"]:
            errors.append(f"6-rank two-level "
                          f"{rows[6]['projected_step_us']} != "
                          f"{exp['world6_local2_us']}")
        if not any(w.startswith("two_level")
                   for w in rows[6]["wire_formats"].values()):
            errors.append("6-rank projection did not choose two_level: "
                          f"{rows[6]['wire_formats']}")
        if errors:
            print("hvd_replay --project --check FAILED:", file=sys.stderr)
            for e in errors:
                print(f"  {e}", file=sys.stderr)
            return 1
        print(f"hvd_replay --project --check OK: identity bit-matches "
              f"baseline ({exp['identity_us']:.1f} us), 2x = "
              f"{exp['world4_us']:.1f} us exact, 6-rank two-level = "
              f"{exp['world6_local2_us']:.3f} us exact")
        return 0


def _print_text(summary: dict) -> None:
    print(f"replayed {summary['trace_dir']}  "
          f"ranks={summary['ranks']}  "
          f"clock_aligned={summary['clock_aligned']}")
    for s in summary["steps"]:
        print(f"\nstep {s['step']}: measured {s['measured_step_us']:.1f} us,"
              f" replay {s['replay_step_us']:.1f} us"
              f" (error {s['replay_error_pct']}%)")
        print("  critical path:")
        for row in s["critical_path"]:
            who = f"rank {row['rank']}" if row["rank"] is not None else \
                "ranks " + ",".join(str(r) for r in row["ranks"] or ())
            what = row["tensor"] or row["label"] or row["kind"]
            print(f"    {row['start_us']:>10.1f} us  {row['kind']:<8} "
                  f"{who:<10} {what:<24} {row['dur_us']:>9.1f} us")
        print("  attribution (us):")
        for rank, a in sorted(s["attribution"]["per_rank"].items(),
                              key=lambda kv: int(kv[0])):
            print(f"    rank {rank}: compute {a['compute_us']:>10.1f}  "
                  f"comm {a['comm_us']:>9.1f}  "
                  f"negotiation {a['negotiation_us']:>10.1f}  "
                  f"idle {a['idle_us']:>9.1f}")
        print("  what-if (ranked):")
        for sc in s["what_if"]["scenarios"]:
            print(f"    {sc['scenario']:<28} {sc['predicted_step_us']:>10.1f}"
                  f" us  ({sc['speedup_pct']:+.1f}%)")
    if summary["recommendations"]:
        best = summary["recommendations"][0]
        print(f"\nbest lever: {best['scenario']} (step {best['step']}) — "
              f"predicted {best['predicted_step_us']:.1f} us, "
              f"{best['speedup_pct']:+.1f}%")
    if summary.get("projection"):
        _print_projection(summary["projection"])


def _print_projection(proj: dict) -> None:
    src = proj["source"]
    print(f"\nprojection (mode={proj['mode']}): source world "
          f"{src['world']}, baseline {src['baseline_replay_us']:.1f} us")
    print(f"  {'target':<24} {'world':>6} {'step us':>12} "
          f"{'eff':>7}  wire")
    for row in proj["projections"]:
        eff = row.get("scaling_efficiency")
        wires = sorted(set(row.get("wire_formats", {}).values())) or ["-"]
        tag = row["name"] + (" (synth comm)" if row.get("synthesized_comm")
                             else "")
        print(f"  {tag:<24} {row['world']:>6} "
              f"{row['projected_step_us']:>12.1f} "
              f"{eff if eff is not None else '-':>7}  "
              f"{','.join(wires)}")
    val = proj.get("validation")
    if val:
        print(f"  accuracy: projected {val['projected_step_us']:.1f} us vs "
              f"measured {val['measured_step_us']:.1f} us on world "
              f"{val['target_world']} -> err {val['err_pct']}%")


def main(argv=None):
    p = argparse.ArgumentParser(
        description="dPRO-style replay: critical path + what-if over a "
                    "merged trace dir")
    p.add_argument("trace_dir", nargs="?",
                   help="timeline dir (HVD_TIMELINE target)")
    p.add_argument("--step", type=int, default=None,
                   help="replay only this step number")
    p.add_argument("--json", action="store_true",
                   help="machine-readable summary on stdout")
    p.add_argument("--out", default=None,
                   help="also write the summary JSON here")
    p.add_argument("--annotated", default=None,
                   help="write the merged Chrome trace with the critical "
                        "path highlighted (default off; pass a path)")
    p.add_argument("--push", default=None, metavar="HOST:PORT",
                   help="publish the summary to the rendezvous server so "
                        "GET /replay serves it")
    p.add_argument("--secret", default=None,
                   help="hex HMAC secret for --push (HVD_RUN_SECRET "
                        "equivalent)")
    p.add_argument("--check", action="store_true",
                   help="self-test on the built-in hand-computed fixture")
    p.add_argument("--no-plan-search", action="store_true",
                   help="skip the fusion bucket search (the expensive "
                        "what-if on big traces) — straggler/attribution "
                        "reports only")
    p.add_argument("--project", action="append", nargs="?", const="",
                   metavar="SPEC",
                   help="project the trace onto a target topology: '4x', "
                        "'2x..64x', 'world=64,local=8,compression=int8,"
                        "two_level=auto' (repeatable; with --check runs "
                        "the hand-computed projection self-test)")
    p.add_argument("--project-mode", default=None,
                   choices=["distribution", "slowest"],
                   help="compute-chain replication mode (default "
                        "HVD_PROJECT_MODE or 'distribution')")
    p.add_argument("--project-validate", default=None, metavar="DIR",
                   help="measured trace dir to pin projected-vs-measured "
                        "error against (the tracked accuracy observable)")
    args = p.parse_args(argv)

    if args.check:
        if args.project is not None:
            sys.exit(run_project_check())
        sys.exit(run_check())
    if not args.trace_dir:
        p.error("trace_dir is required (or use --check)")
    push_host = push_port = None
    if args.push:
        push_host, _, port_s = args.push.partition(":")
        if not push_host or not port_s.isdigit():
            p.error(f"--push wants HOST:PORT, got {args.push!r}")
        push_port = int(port_s)

    result = analyze(args.trace_dir, step=args.step,
                     plan_search=not args.no_plan_search)
    summary = result.summary
    if args.project is None and args.project_validate:
        # --project-validate alone implies a projection onto the
        # measured world (silently skipping the accuracy pin the user
        # asked for would be worse than either behavior)
        args.project = [""]
    if args.project is not None:
        from horovod_tpu.timeline.replay.projection import (
            export_projection_gauges, parse_project_spec, project_analysis,
            source_world_of, validate,
        )

        sw = source_world_of(result)
        specs = []
        for text in args.project:
            if text:
                specs.extend(parse_project_spec(text, sw))
        if not specs:
            specs = parse_project_spec("2x..8x", sw)
        proj = project_analysis(result, specs, mode=args.project_mode)
        if args.project_validate:
            proj["validation"] = validate(args.trace_dir,
                                          args.project_validate,
                                          mode=args.project_mode,
                                          source_result=result)
        export_projection_gauges(proj)
        summary["projection"] = proj
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    if args.annotated:
        annotated_trace(args.trace_dir, result, out_path=args.annotated)
    if args.push:
        from horovod_tpu.run.http_client import (
            put_projection_summary, put_replay_summary,
        )

        secret = bytes.fromhex(args.secret) if args.secret else None
        put_replay_summary(push_host, push_port, summary, secret=secret)
        print(f"pushed summary -> GET http://{args.push}/replay",
              file=sys.stderr)
        if summary.get("projection"):
            put_projection_summary(push_host, push_port,
                                   summary["projection"], secret=secret)
            print(f"pushed projection -> GET http://{args.push}/projection",
                  file=sys.stderr)

    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        _print_text(summary)
    return summary


if __name__ == "__main__":
    main()
