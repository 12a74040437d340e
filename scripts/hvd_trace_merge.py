"""Merge per-rank communication traces into one Chrome trace + straggler
report.

The fork writes one ``comm.json`` per rank (``<dir>/<rank>/comm.json``,
reference timeline.cc:205-228); this CLI fuses a whole trace dir into a
single viewer-loadable file (pid = rank) and answers the dPRO question
"which rank is late" from the per-tensor negotiation-wait spread.

A flight-recorder dump saved next to the traces (``hvd_events --json >
<dir>/events.json``, or a raw ``GET /events`` report) merges as a
"control plane" row of instant events above the rank rows, so lease
expiries / epoch commits / restarts line up against the device
timeline (docs/observe.md).

Run::

    python scripts/hvd_trace_merge.py <trace_dir> \
        [--out merged_trace.json] [--report straggler.json] \
        [--top 20] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.timeline.merge import straggler_report, write_merged  # noqa: E402


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(
        description="merge <dir>/<rank>/comm.json traces + straggler report"
    )
    p.add_argument("trace_dir", help="timeline dir (HVD_TIMELINE target)")
    p.add_argument("--out", default=None,
                   help="merged Chrome trace path "
                        "(default <trace_dir>/merged_trace.json)")
    p.add_argument("--report", default=None,
                   help="also write the straggler report to this JSON file")
    p.add_argument("--top", type=int, default=20,
                   help="show the N widest-spread tensors")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report on stdout")
    args = p.parse_args(argv)

    out = args.out or os.path.join(args.trace_dir, "merged_trace.json")
    merged = write_merged(args.trace_dir, out)
    report = straggler_report(args.trace_dir, top=args.top)
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)

    if args.json:
        print(json.dumps(report, indent=2))
        return report

    n_ev = len(merged["traceEvents"])
    n_ranks = len(report["ranks"])
    print(f"merged {n_ranks} rank(s), {n_ev} events -> {out}")
    if not report["tensors"]:
        print("no tensor negotiated on >= 2 ranks; no straggler analysis")
    else:
        print(f"{'tensor':<32} {'op':<12} {'spread_us':>10}  straggler")
        for row in report["tensors"]:
            print(f"{row['tensor']:<32} {row['op']:<12} "
                  f"{row['spread_us']:>10.1f}  rank {row['straggler_rank']}")
        print("per-rank blame (straggler = arrived last, waited least):")
        for rank, d in sorted(report["ranks"].items(),
                              key=lambda kv: int(kv[0])):
            print(f"  rank {rank}: straggler for {d['times_straggler']} "
                  f"tensor(s), total negotiate wait "
                  f"{d['total_negotiate_wait_us']:.1f} us")
    # the machine block the watchdog's drift detector consumes
    # (observe.detectors.straggler_from_verdicts)
    verdicts = (report.get("verdicts") or {}).get("ranks") or {}
    if verdicts:
        print("verdicts:")
        for rank, v in sorted(verdicts.items(), key=lambda kv: kv[0]):
            print(f"  rank {rank}: {v['verdict']} "
                  f"(skew {v['skew']:.2f}x, basis {v['basis']})")
    return report


if __name__ == "__main__":
    main()
