"""Driver benchmark: ResNet-50 synthetic throughput on real hardware.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...}.  When no TPU is found,
or the measurement child fails, it prints the reason on stderr and exits
non-zero: a CPU number is never published as the per-chip metric.

Baseline: the reference's published sample throughput for its benchmark
methodology is 1656.82 images/sec on 16 Pascal GPUs (ResNet-101, batch 64,
reference docs/benchmarks.rst:27-41) ≈ 103.55 img/sec/GPU; the in-repo
synthetic benchmark's default model is ResNet-50 (reference
examples/tensorflow2_synthetic_benchmark.py:32-35).  vs_baseline =
our img/sec/chip ÷ 103.55.

Configuration: batch 128, bf16 compute, 100 optimizer steps compiled
into one program via lax.scan.  Both were picked on an earlier chip
path (batch sweep, k ladder); root PERF.md lists them as hypotheses to
re-measure in a cell.

MFU accounting: ResNet-50 training requires 24.30 GFLOPs/image (4.09 G
multiply-adds forward at two operations each, three passes less the
stem's input gradient: utils/flops.RESNET50_TRAIN_FLOPS_PER_IMG; until
PR 24 this read 12.27, multiply-adds counted as operations, and the
headline MFU read half).  The peak comes from
utils/flops.DEVICE_PEAKS, keyed by the device kind; a device that is
not in the table (and no HVD_PEAK_FLOPS) is an error.

One process per chip: this parent never touches JAX (importing
horovod_tpu starts no backend — tests/test_bench.py pins it), so every
measurement child gets the chip to itself, one after another.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, __file__.rsplit("/", 1)[0])

BASELINE_IMG_SEC_PER_DEVICE = 1656.82 / 16  # docs/benchmarks.rst:27-41
# MFU constants live in horovod_tpu/utils/flops.py (single-sourced with
# the comm report; HVD_PEAK_FLOPS overrides the peak) — every leg's mfu
# field routes through _mfu() below


def _mfu(img_sec_per_chip: float) -> float:
    """MFU for a bench leg, computed through utils/flops so the bench
    JSON and the comm report can never disagree.  Runs in the
    measurement child after ``hvd.init()``; a device with no known peak
    raises (utils/flops.require_peak_flops)."""
    from horovod_tpu.utils import flops as _flops

    return round(_flops.image_model_mfu(
        img_sec_per_chip, peak=_flops.require_peak_flops()), 4)


def _require_tpu() -> dict:
    """The device the child runs on, as JAX reports it; raises unless it
    is a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU found: JAX reports platform {dev.platform!r} "
            f"({dev.device_kind}); refusing to publish it as the "
            "per-chip TPU metric")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


RUN_TIMEOUT_S = 560        # compile (~40 s) + 3 measured iters, generous
AUTOTUNE_TIMEOUT_S = 420   # autotuned comparison run (re-jits a few times)
COMPRESSION_TIMEOUT_S = 420  # compressed comparison run (one compile)
SERVE_TIMEOUT_S = 180      # serving fixture: a few MLP compiles + ~1.5 s trace
PROJECTION_TIMEOUT_S = 240  # digital-twin leg: two traced MLP drives (1 + 8 dev)
CONTROL_TIMEOUT_S = 120    # control-plane churn: ~5k loopback HTTP requests
WATCH_TIMEOUT_S = 90       # watchdog leg: pure host-side detector replay
RESTORE_TIMEOUT_S = 120    # peer-restore leg: snapshot/restore fixture
CHAOS_TIMEOUT_S = 240      # chaos leg: 8-scenario in-process campaign


def _measure() -> None:
    """Child-process entry: touch the TPU and print the result line."""
    device = _require_tpu()
    from examples.synthetic_benchmark import parse_args, run

    args = parse_args([
        "--batch-size", "128",
        "--num-in-graph-steps", "100",
        "--num-warmup-batches", "1",
        "--num-batches-per-iter", "1",
        "--num-iters", "3",
    ])
    result = run(args)
    per_chip = result["img_sec_per_chip"]
    print("RESULT " + json.dumps({
        "metric": "resnet50_synthetic_img_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMG_SEC_PER_DEVICE, 3),
        "mfu": _mfu(per_chip),
        "mfu_note": "24.30 GF/img required / peak from utils/flops "
                    "DEVICE_PEAKS by device kind (HVD_PEAK_FLOPS "
                    "overrides)",
        **device,
    }))


def _measure_autotuned() -> None:
    """Child-process entry for the autotuned comparison leg: the same
    synthetic benchmark with the live Bayesian autotuner (warm-started
    from the α–β model, docs/autotune.md) moving the fusion knobs.  A
    shorter run — the point is the autotuned-vs-default delta, not a
    second absolute number — with a small sample budget so the re-jit
    cost stays inside AUTOTUNE_TIMEOUT_S."""
    _require_tpu()
    os.environ.setdefault("HVD_AUTOTUNE_WARMUP_SAMPLES", "0")
    os.environ.setdefault("HVD_AUTOTUNE_STEPS_PER_SAMPLE", "1")
    os.environ.setdefault("HVD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", "4")
    from examples.synthetic_benchmark import parse_args, run

    args = parse_args([
        "--batch-size", "128",
        "--num-in-graph-steps", "100",
        "--num-warmup-batches", "6",   # tuner samples + freeze happen here
        "--num-batches-per-iter", "1",
        "--num-iters", "2",
        "--autotune",
    ])
    result = run(args)
    print("RESULT " + json.dumps(
        {"img_sec_per_chip": round(result["img_sec_per_chip"], 2),
         "mfu": _mfu(result["img_sec_per_chip"])}))


def _measure_compressed() -> None:
    """Child-process entry for the compressed comparison leg: the same
    synthetic benchmark with error-feedback int8 gradient compression
    (docs/compression.md) — the wire-efficiency tier's headline delta.
    Single-chip, so the delta isolates the quantize/dequantize overhead
    (the wire saving needs a multi-chip run to show up); a shorter run,
    same contract as the autotune leg: the point is the delta, not a
    second absolute number."""
    _require_tpu()
    from examples.synthetic_benchmark import parse_args, run

    args = parse_args([
        "--batch-size", "128",
        "--num-in-graph-steps", "100",
        "--num-warmup-batches", "1",
        "--num-batches-per-iter", "1",
        "--num-iters", "2",
        "--compression", "int8",
    ])
    result = run(args)
    print("RESULT " + json.dumps(
        {"img_sec_per_chip": round(result["img_sec_per_chip"], 2),
         "mfu": _mfu(result["img_sec_per_chip"])}))


def _measure_serving() -> None:
    """Child-process entry for the serving leg: the seeded bursty
    open-loop load-generator fixture against a small jitted MLP
    replica set (docs/inference.md) — p50/p99 request latency and
    goodput-under-burst are the serving plane's headline numbers.
    Latency of a tiny MLP is host-dominated, so this leg runs on
    whatever platform the child gets (CPU included): it benchmarks the
    batching/queueing plane, not the chip."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from horovod_tpu.serving.plane import run_bench_fixture

    out = run_bench_fixture()
    print("RESULT " + json.dumps({
        "serve_p50_ms": out["serve_p50_ms"],
        "serve_p99_ms": out["serve_p99_ms"],
        "goodput_under_burst": out["goodput_under_burst"],
        "serve_offered": out["offered"],
        "serve_completed": out["completed"],
    }))


def _measure_projection() -> None:
    """Child-process entry for the digital-twin accuracy leg: drive the
    1-device → 8-device CPU-mesh validation (timeline/replay/projection
    live_validation, docs/projection.md) and report the twin's
    projected-vs-measured step-time error.  Like the serving leg this
    benchmarks a host-side plane, not the chip, so it runs on the CPU
    mesh regardless of TPU availability — the twin's ACCURACY is the
    tracked number, the same way autotune_delta_pct tracks the tuner."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    from horovod_tpu.timeline.replay.projection import live_validation

    out = live_validation()
    print("RESULT " + json.dumps({
        "projection_err_pct": out["err_pct"],
        "projected_step_us": out["projected_step_us"],
        "measured_step_us": out["measured_step_us"],
    }))


def _measure_control() -> None:
    """Child-process entry for the control-plane churn leg: the
    simulated 64-host/512-rank heartbeat/metrics/fingerprint storm of
    scripts/control_plane_bench.py against a real sharded rendezvous
    server (docs/control_plane.md).  Pure host-side machinery — no
    accelerator involved — so it runs anywhere; the tracked numbers are
    the relay-vs-per-rank request reduction and the p99 lease-renewal /
    epoch-commit latencies."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from control_plane_bench import run_bench

    out = run_bench(hosts=64, ranks=512, ticks=3)
    print("RESULT " + json.dumps({
        "control_p99_lease_ms": out["p99_lease_renewal_ms"],
        "control_p99_epoch_ms": out["p99_epoch_commit_ms"],
        "control_abort_ms": out["abort_propagation_ms"],
        "control_request_reduction_x": out["request_reduction_x"],
    }))


def _measure_watch() -> None:
    """Child-process entry for the watchdog leg: a scripted step-time
    regression (2 ranks, 200 quiet steps at ~0.100 s, then rank 0
    degrading to 0.200 s) replayed through a real rendezvous server +
    Watchdog (observe/watchdog.py) — pure host-side machinery, runs
    anywhere.  Tracked numbers: detection latency in steps past the
    regression onset, false positives over the quiet phase, and the
    per-append cost of the always-on ring buffer (the ONLY thing the
    step path pays)."""
    import json as _json
    import time as _time

    os.environ["HVD_WATCH_INTERVAL_SECONDS"] = "999"  # tick() driven by hand
    from horovod_tpu.metrics import timeseries as ts_mod
    from horovod_tpu.observe.watchdog import Watchdog
    from horovod_tpu.run.http_server import RendezvousServer

    # ring-buffer append cost: the step path's entire overhead
    n = 200_000
    t0 = _time.perf_counter()
    for i in range(n):
        ts_mod.record(ts_mod.STEP_SECONDS, 0.1, step=i)
    append_us = (_time.perf_counter() - t0) / n * 1e6
    ts_mod.store.reset()

    server = RendezvousServer()
    server.start()
    try:
        dog = Watchdog(server)        # not started: ticks driven below
        stores = {r: ts_mod.TimeseriesStore(enabled=True)
                  for r in ("0", "1")}
        quiet_steps, onset_extra, chunk = 200, 100, 5

        def _feed(step):
            for rank, st in stores.items():
                dt = 0.100 if step % 2 else 0.101
                if rank == "0" and step > quiet_steps:
                    dt = 0.200             # the scripted regression
                st.record(ts_mod.STEP_SECONDS, dt, step=step)
                server.put("timeseries", rank,
                           _json.dumps(st.snapshot()).encode())

        false_positives = 0
        detect_step = None
        for step in range(1, quiet_steps + onset_extra + 1):
            _feed(step)
            if step % chunk:
                continue
            alerts = dog.tick()
            if step <= quiet_steps:
                false_positives += len(alerts)
            elif detect_step is None and any(
                    a["signal"] in ("step_time_regression",
                                    "straggler_drift") for a in alerts):
                detect_step = step
        print("RESULT " + _json.dumps({
            "watch_detect_steps": (detect_step - quiet_steps)
            if detect_step is not None else None,
            "watch_false_positives": false_positives,
            "watch_armed": dog.arms > 0,
            "watch_append_us": round(append_us, 3),
            "watch_overhead_pct_1ms_step": round(append_us / 1e3 * 100, 4),
        }))
    finally:
        server.stop()


def _watch_leg() -> dict:
    """The watchdog tail fields, from a separately-timed child so a
    hung or failed detector replay can never cost the main number
    (HVD_BENCH_WATCH=0 skips).  Null-on-failure, same contract as
    every other leg."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_WATCH, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-watch", WATCH_TIMEOUT_S)
        if payload is not None:
            return {
                "watch_detect_steps": payload.get("watch_detect_steps"),
                "watch_false_positives":
                    payload.get("watch_false_positives"),
                "watch_armed": payload.get("watch_armed"),
                "watch_append_us": payload.get("watch_append_us"),
                "watch_overhead_pct_1ms_step":
                    payload.get("watch_overhead_pct_1ms_step"),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"watch_detect_steps": None, "watch_false_positives": None,
            "watch_armed": None, "watch_append_us": None,
            "watch_overhead_pct_1ms_step": None, "watch_error": reason}


def _measure_restore() -> None:
    """Child-process entry for the peer-state-plane leg: a 3-worker
    in-process fixture (one rendezvous + three peer shard servers,
    elastic/peerstate.py) snapshotting a ~4 MB state every 5 steps and
    then restoring from peers under churn — pure host-side machinery,
    runs anywhere.  Tracked numbers: the step-path stall of a snapshot
    enqueue in µs (the ONLY checkpoint cost a step pays on the peer
    tier), restore-to-training p99 in ms, and steps lost on a failure
    at the worst point of the snapshot interval."""
    import json as _json
    import time as _time

    import numpy as np

    os.environ["HVD_NUM_PROCESSES"] = "3"   # gen committed = all 3 ranks
    from horovod_tpu.elastic.peerstate import PeerSnapshotManager
    from horovod_tpu.run.http_server import RendezvousServer

    secret = b"bench-restore"
    server = RendezvousServer(secret=secret)
    port = server.start()
    managers = [
        PeerSnapshotManager(replicas_k=2, nshards=4, addr="127.0.0.1",
                            port=port, secret=secret, worker=f"w{r}",
                            rank=r)
        for r in range(3)
    ]
    try:
        for m in managers:
            m.start()
        rng = np.random.default_rng(0)
        state = {"params": rng.standard_normal(500_000),  # ~4 MB
                 "opt": rng.standard_normal(2)}
        # 59 steps with a 5-step cadence: the last committed generation
        # is 55, so the measured steps-lost is the honest worst point of
        # the interval (a crash just before the next snapshot)
        steps, interval = 59, 5
        stalls_us = []
        for step in range(1, steps + 1):
            _time.sleep(0.001)                    # the fake train step
            if step % interval == 0:
                for m in managers:
                    s = m.snapshot(state, step)
                    if m is managers[0]:
                        stalls_us.append(s * 1e6)
        ok = all(m.drain(30.0) for m in managers)
        restores_ms = []
        restored_gen = None
        for _ in range(20):
            # a fresh manager each round: the post-crash relaunch shape
            fresh = PeerSnapshotManager(replicas_k=2, nshards=4,
                                        addr="127.0.0.1", port=port,
                                        secret=secret, worker="w0", rank=0)
            t0 = _time.perf_counter()
            got = fresh.restore()
            restores_ms.append((_time.perf_counter() - t0) * 1e3)
            if got is not None:
                restored_gen = got[1]
        restores_ms.sort()
        got_all = restored_gen is not None and bool(restores_ms)
        p99_i = min(int(len(restores_ms) * 0.99), len(restores_ms) - 1) \
            if restores_ms else 0
        print("RESULT " + _json.dumps({
            "restore_ckpt_stall_us":
                round(sum(stalls_us) / len(stalls_us), 3)
                if stalls_us else None,
            "restore_p99_ms": round(restores_ms[p99_i], 3)
                if got_all else None,
            "restore_p50_ms": round(
                restores_ms[len(restores_ms) // 2], 3)
                if got_all else None,
            # a crash at the worst point loses the steps since the last
            # committed snapshot — the target is one interval
            "restore_steps_lost": (steps - restored_gen)
                if restored_gen is not None else None,
            "restore_snapshot_interval": interval,
            "restore_drained": ok,
        }))
    finally:
        for m in managers:
            m.stop()
        server.stop()


def _restore_leg() -> dict:
    """The peer-state-plane tail fields, from a separately-timed child
    so a hung snapshot fixture can never cost the main number
    (HVD_BENCH_RESTORE=0 skips).  Null-on-failure, same contract as
    every other leg."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_RESTORE, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-restore", RESTORE_TIMEOUT_S)
        if payload is not None:
            return {
                "restore_ckpt_stall_us":
                    payload.get("restore_ckpt_stall_us"),
                "restore_p99_ms": payload.get("restore_p99_ms"),
                "restore_steps_lost": payload.get("restore_steps_lost"),
                "restore_snapshot_interval":
                    payload.get("restore_snapshot_interval"),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"restore_ckpt_stall_us": None, "restore_p99_ms": None,
            "restore_steps_lost": None, "restore_snapshot_interval": None,
            "restore_error": reason}


def _measure_chaos() -> None:
    """Child-process entry for the chaos-campaign leg: a fixed-seed
    8-scenario campaign (elastic/chaos.py) against the in-process
    elastic control plane — crashes, hangs, partitions, preemptions,
    a primary kill, and a relay kill, all invariant-checked.  Tracked
    numbers: MTTR p50/p99 across every recovery (trigger evidence to
    the last survivor resume), the worst steps-lost of any resume, and
    the violation count (which must be 0 for the leg to report)."""
    import json as _json

    import logging as _logging

    _logging.disable(_logging.ERROR)   # scenario churn is all expected
    from horovod_tpu.elastic import chaos

    scenarios = chaos.generate_campaign(1234, count=8)
    campaign = chaos.run_campaign(scenarios, seed=1234)
    mttrs = sorted(r["mttr_ms"] for res in campaign.results
                   for r in res.recoveries if r["mttr_ms"] is not None)
    losses = [lost for res in campaign.results
              for r in res.recoveries for lost in r["steps_lost"]]
    n_viol = sum(len(res.violations) for res in campaign.results)
    ok = campaign.ok and bool(mttrs)
    p99_i = min(int(len(mttrs) * 0.99), len(mttrs) - 1) if mttrs else 0
    print("RESULT " + _json.dumps({
        "chaos_mttr_p50_ms": round(mttrs[len(mttrs) // 2], 1)
            if ok else None,
        "chaos_mttr_p99_ms": round(mttrs[p99_i], 1) if ok else None,
        "chaos_steps_lost_max": max(losses) if ok and losses else None,
        "chaos_scenarios": len(campaign.results),
        "chaos_recoveries": len(mttrs),
        "chaos_violations": n_viol,
    }))


def _chaos_leg() -> dict:
    """The chaos-campaign tail fields, from a separately-timed child so
    a wedged scenario can never cost the main number
    (HVD_BENCH_CHAOS=0 skips).  Null-on-failure, same contract as
    every other leg."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_CHAOS, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-chaos", CHAOS_TIMEOUT_S)
        if payload is not None:
            return {
                "chaos_mttr_p50_ms": payload.get("chaos_mttr_p50_ms"),
                "chaos_mttr_p99_ms": payload.get("chaos_mttr_p99_ms"),
                "chaos_steps_lost_max":
                    payload.get("chaos_steps_lost_max"),
                "chaos_scenarios": payload.get("chaos_scenarios"),
                "chaos_violations": payload.get("chaos_violations"),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"chaos_mttr_p50_ms": None, "chaos_mttr_p99_ms": None,
            "chaos_steps_lost_max": None, "chaos_error": reason}


def _control_leg() -> dict:
    """The control-plane tail fields, from a separately-timed child so
    a hung or failed churn run can never cost the main number
    (HVD_BENCH_CONTROL=0 skips).  ``control_p99_*`` are null on any
    failure — same contract as every other leg."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_CONTROL, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-control", CONTROL_TIMEOUT_S)
        if payload is not None:
            return {
                "control_p99_lease_ms": payload.get("control_p99_lease_ms"),
                "control_p99_epoch_ms": payload.get("control_p99_epoch_ms"),
                "control_abort_ms": payload.get("control_abort_ms"),
                "control_request_reduction_x":
                    payload.get("control_request_reduction_x"),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"control_p99_lease_ms": None, "control_p99_epoch_ms": None,
            "control_abort_ms": None, "control_request_reduction_x": None,
            "control_error": reason}


def _projection_leg() -> dict:
    """The projection-accuracy tail field, from a separately-timed child
    so a hung or failed twin drive can never cost the main number
    (HVD_BENCH_PROJECTION=0 skips).  ``projection_err_pct`` is null on
    any failure — same contract as the autotune/compression legs."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_PROJECTION, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-projection",
                                     PROJECTION_TIMEOUT_S)
        if payload is not None:
            return {"projection_err_pct": payload.get("projection_err_pct")}
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"projection_err_pct": None, "projection_error": reason}


def _serving_leg() -> dict:
    """The serving tail fields, from a separately-timed child so a hung
    or failed serving fixture can never cost the training number
    (HVD_BENCH_SERVE=0 skips).  Null-on-failure, same contract as the
    autotune/compression legs."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_SERVE, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-serve", SERVE_TIMEOUT_S)
        if payload is not None:
            return {
                "serve_p50_ms": payload.get("serve_p50_ms"),
                "serve_p99_ms": payload.get("serve_p99_ms"),
                "goodput_under_burst": payload.get("goodput_under_burst"),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"serve_p50_ms": None, "serve_p99_ms": None,
            "goodput_under_burst": None, "serve_error": reason}


def _compression_delta(default_per_chip: float) -> dict:
    """The compressed-vs-default tail fields, from a separately-timed
    child so a hung or failed compression leg can never cost the main
    number (HVD_BENCH_COMPRESSION=0 skips).  Returns the fields to
    merge into the RESULT payload — ``compression_delta_pct`` is null
    on any failure, same contract as the autotune leg."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_COMPRESSION, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled or default_per_chip <= 0:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-compression",
                                     COMPRESSION_TIMEOUT_S)
        if payload is not None:
            at = float(payload["img_sec_per_chip"])
            return {
                "compressed_img_sec_per_chip": round(at, 2),
                "compressed_mfu": payload.get("mfu"),
                "compression_delta_pct": round(
                    (at - default_per_chip) / default_per_chip * 100.0, 2),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"compression_delta_pct": None, "compressed_mfu": None,
            "compression_error": reason}


def _run_child(flag: str, timeout_s: float):
    """Run this file as a child process with ``flag`` and parse its
    ``RESULT`` line.  Returns ``(payload, None)`` on success or
    ``(None, reason)`` — the one copy of the child protocol that both
    the main measurement and the autotune leg share."""
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return None, f"timeout after {timeout_s:g}s"
    except Exception as e:  # noqa: BLE001 — callers degrade, never crash
        return None, f"{type(e).__name__}: {e}"
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    if p.returncode == 0 and lines:
        try:
            return json.loads(lines[-1][len("RESULT "):]), None
        except ValueError as e:
            return None, f"unparseable result: {e}"
    tail = (p.stderr or p.stdout).strip().splitlines()[-1:]
    return None, f"rc={p.returncode} {' '.join(tail)[:200]}"


def _autotune_delta(default_per_chip: float) -> dict:
    """The autotuned-vs-default tail fields, from a separately-timed
    child so a hung or failed autotune leg can never cost the main
    number.  Returns the fields to merge into the RESULT payload."""
    try:
        from horovod_tpu.utils import env as env_util

        enabled = env_util.get_bool(env_util.HVD_BENCH_AUTOTUNE, True)
    except Exception:  # noqa: BLE001
        enabled = True
    if not enabled or default_per_chip <= 0:
        return {}
    reason = None
    try:
        payload, reason = _run_child("--child-autotune", AUTOTUNE_TIMEOUT_S)
        if payload is not None:
            at = float(payload["img_sec_per_chip"])
            return {
                "autotuned_img_sec_per_chip": round(at, 2),
                "autotuned_mfu": payload.get("mfu"),
                "autotune_delta_pct": round(
                    (at - default_per_chip) / default_per_chip * 100.0, 2),
            }
    except Exception as e:  # noqa: BLE001 — the leg can never cost the main number
        reason = f"{type(e).__name__}: {e}"
    return {"autotune_delta_pct": None, "autotuned_mfu": None,
            "autotune_error": reason}


def main() -> int:
    out, reason = _run_child("--child", RUN_TIMEOUT_S)
    if out is None:
        print(f"bench.py: measurement failed: {reason}", file=sys.stderr)
        return 1
    # autotuned-vs-default tail (HVD_BENCH_AUTOTUNE=0 skips):
    # did the profile-guided/Bayesian loop move the MFU number?
    out.update(_autotune_delta(float(out.get("value", 0.0))))
    # compressed-vs-default tail (HVD_BENCH_COMPRESSION=0 skips):
    # what does error-feedback int8 cost/buy on this chip?
    out.update(_compression_delta(float(out.get("value", 0.0))))
    # serving tail (HVD_BENCH_SERVE=0 skips): p50/p99 request
    # latency + goodput-under-burst of the serving plane fixture
    out.update(_serving_leg())
    # digital-twin tail (HVD_BENCH_PROJECTION=0 skips): the
    # projection engine's accuracy on the world being benched
    out.update(_projection_leg())
    # control-plane tail (HVD_BENCH_CONTROL=0 skips): churn-
    # harness p99 lease/epoch latencies + relay request
    # reduction — the control plane's own tracked numbers
    out.update(_control_leg())
    # watchdog tail (HVD_BENCH_WATCH=0 skips): detection
    # latency + false positives on a scripted regression trace,
    # and the ring-buffer append cost the step path pays
    out.update(_watch_leg())
    # peer-state-plane tail (HVD_BENCH_RESTORE=0 skips):
    # snapshot enqueue stall µs/step, restore-from-peers p99,
    # and steps lost to a worst-point failure
    out.update(_restore_leg())
    # chaos-campaign tail (HVD_BENCH_CHAOS=0 skips): MTTR
    # p50/p99 and worst steps-lost across a fixed-seed
    # composed-fault campaign, invariant-checked
    out.update(_chaos_leg())
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    if "--child-autotune" in sys.argv:
        _measure_autotuned()
    elif "--child-compression" in sys.argv:
        _measure_compressed()
    elif "--child-serve" in sys.argv:
        _measure_serving()
    elif "--child-projection" in sys.argv:
        _measure_projection()
    elif "--child-control" in sys.argv:
        _measure_control()
    elif "--child-watch" in sys.argv:
        _measure_watch()
    elif "--child-restore" in sys.argv:
        _measure_restore()
    elif "--child-chaos" in sys.argv:
        _measure_chaos()
    elif "--child" in sys.argv:
        _measure()
    else:
        sys.exit(main())
