"""Observe plane: the always-on telemetry time-series (ring buffers,
tiered downsampling, the delta flush protocol, ``GET /timeseries``),
the watchdog's detectors on hand-computed fixtures, alert publication
(``GET /alerts``), the auto-arm broadcast, and the e2e slow-rank smoke
(docs/observe.md)."""

import json
import time

import pytest

from horovod_tpu.metrics import timeseries as ts_mod
from horovod_tpu.observe import autoarm, detectors
from horovod_tpu.observe.fixtures import (
    WATCH_EXPECTED, evaluate_fixture, watch_fixture,
)
from horovod_tpu.observe.watchdog import Watchdog


@pytest.fixture()
def fresh_observe(monkeypatch):
    """Clean store + autoarm state, watchdog ticks driven by hand."""
    monkeypatch.setattr(ts_mod, "store",
                        ts_mod.TimeseriesStore(enabled=True))
    autoarm.reset()
    yield
    autoarm.reset()


@pytest.fixture()
def rdv_server():
    from horovod_tpu.run.http_server import RendezvousServer

    server = RendezvousServer(secret=b"observe-secret")
    server.start()
    yield server, server.port, b"observe-secret"
    server.stop()


# -- ring buffer / tiering ---------------------------------------------------
def test_series_append_and_merged_ordering():
    s = ts_mod.Series(cap=8, tiers=2, factor=4)
    for i in range(8):
        s.append(i + 1, float(i))
    assert s.seq == 8
    assert s.last_step == 8
    merged = s.merged()
    # raw tail intact, in order
    assert [v for _, v in merged[-8:]] == [float(i) for i in range(8)]


def test_series_tier_fold_mean_and_eviction():
    s = ts_mod.Series(cap=4, tiers=2, factor=4)
    # 12 appends through a cap-4 tier0: only the last 4 raw survive,
    # but tier1 holds the mean-folded history (one sample per 4)
    for i in range(12):
        s.append(i + 1, float(i + 1))
    merged = s.merged()
    # tier1 folds: steps 4, 8, 12 with means 2.5, 6.5, 10.5; the
    # folds at/after tier0's first step (9) are deduped out
    assert (4, 2.5) in merged
    assert (8, 2.5 + 4.0) in merged
    assert merged[-4:] == [(9, 9.0), (10, 10.0), (11, 11.0), (12, 12.0)]
    # total memory bounded by cap * tiers
    assert len(merged) <= 4 * 2


def test_series_raw_since_reports_dropped():
    s = ts_mod.Series(cap=4, tiers=1, factor=4)
    for i in range(10):
        s.append(i + 1, float(i))
    samples, dropped = s.raw_since(0)
    assert len(samples) == 4          # only the ring survives
    assert dropped == 6               # the gap is reported, not hidden
    samples, dropped = s.raw_since(8)
    assert [st for st, _ in samples] == [9, 10]
    assert dropped == 0
    assert s.raw_since(10) == ([], 0)


def test_store_record_gated_and_step_defaults_to_ordinal():
    st = ts_mod.TimeseriesStore(enabled=False)
    st.record("x", 1.0)
    assert st.names() == []
    st = ts_mod.TimeseriesStore(enabled=True)
    st.record("x", 1.0)
    st.record("x", 2.0)
    assert st.series("x").last_step == 2   # ordinal clock
    snap = st.snapshot()
    assert snap["series"]["x"]["samples"] == [[1, 1.0], [2, 2.0]]
    assert snap["series"]["x"]["seq"] == 2


# -- registry last-updated stamps (satellite) --------------------------------
def test_registry_snapshot_stamps_family_updated():
    from horovod_tpu.metrics.registry import MetricsRegistry

    r = MetricsRegistry(enabled=True)
    c = r.counter("c_total")
    g = r.gauge("g")
    t0 = time.time()
    c.inc()
    snap = r.snapshot()["metrics"]
    assert snap["c_total"]["updated"] >= t0
    assert snap["g"]["updated"] is None     # never written
    g.set(1.0)
    assert r.snapshot()["metrics"]["g"]["updated"] >= t0


# -- detectors on the hand-computed fixture ----------------------------------
def test_regression_detector_pinned_crossing():
    fx = watch_fixture()
    alert = detectors.ewma_mad_regression(
        fx["regression"], alpha=0.5, k=5.0, warmup=40, confirm=3)
    exp = WATCH_EXPECTED["regression"]
    assert alert is not None
    assert alert["signal"] == "step_time_regression"
    assert alert["severity"] == exp["severity"] == "critical"
    ev = alert["evidence"]
    assert ev["baseline_median"] == pytest.approx(exp["baseline_median"])
    assert ev["baseline_mad"] == pytest.approx(exp["baseline_mad"])
    assert ev["threshold"] == pytest.approx(exp["threshold"], abs=1e-7)
    assert ev["ewma"] == pytest.approx(exp["ewma"], abs=1e-9)
    # the exact threshold-crossing step, hand-computed: EWMA walks
    # 0.1105 -> 0.11525 -> 0.117625; the 3rd breach is step 43
    assert ev["fired_step"] == exp["fired_step"] == 43
    assert alert["window"]["start_step"] == 1


def test_straggler_detector_pinned():
    fx = watch_fixture()
    alert = detectors.straggler_drift(fx["straggler"], skew=1.3,
                                      min_samples=8, window=64)
    exp = WATCH_EXPECTED["straggler"]
    assert alert is not None
    assert alert["severity"] == "warning"   # 1.4 < the 1.6 critical bar
    assert alert["evidence"]["rank"] == exp["rank"]
    assert alert["evidence"]["ratio"] == pytest.approx(exp["ratio"])
    assert alert["evidence"]["world_median"] == pytest.approx(0.100)


def test_beta_burn_detectors_pinned():
    got = evaluate_fixture()
    assert got["beta"]["severity"] == "warning"
    assert got["beta"]["evidence"]["ratio"] == pytest.approx(2.4)
    assert got["burn"]["severity"] == "critical"
    assert got["burn"]["evidence"]["burn_rate"] == pytest.approx(6.0)
    assert got["burn"]["evidence"]["breaches"] == 3


def test_quiet_traces_fire_nothing():
    """The no-alert regression pin: flat traces must stay silent."""
    assert evaluate_fixture()["quiet"] == []


def test_detectors_underfed_are_silent():
    assert detectors.ewma_mad_regression([(1, 0.1)] * 5) is None
    assert detectors.straggler_drift({"0": [(1, 0.1)] * 4}) is None
    assert detectors.comm_beta_drift([(1, 50.0)] * 3, 50.0) is None
    assert detectors.slo_burn_rate([(1, 10.0)] * 3, 100.0) is None


def test_straggler_from_verdicts_block():
    verdicts = {"ranks": {
        "0": {"verdict": "ok", "skew": 1.0, "basis": "negotiate_wait"},
        "1": {"verdict": "straggler", "skew": 1.7,
              "basis": "negotiate_wait"},
    }}
    alert = detectors.straggler_from_verdicts(verdicts, skew=1.3)
    assert alert is not None
    assert alert["evidence"]["rank"] == "1"
    assert alert["severity"] == "critical"    # 1.7 >= 1.6
    assert detectors.straggler_from_verdicts({"ranks": {}}) is None


# -- trace-merge verdict block (satellite) -----------------------------------
def test_straggler_report_emits_verdict_block():
    from horovod_tpu.timeline.merge import straggler_verdicts

    report = {
        "tensors": [{"tensor": "t0"}, {"tensor": "t1"}],
        "ranks": {
            "0": {"times_straggler": 2, "total_negotiate_wait_us": 1.0,
                  "unmatched_spans": 0},
            "1": {"times_straggler": 0, "total_negotiate_wait_us": 9.0,
                  "unmatched_spans": 0},
        },
    }
    v = straggler_verdicts(report)
    assert v["ranks"]["0"] == {"verdict": "straggler", "skew": 2.0,
                               "basis": "negotiate_wait"}
    assert v["ranks"]["1"]["verdict"] == "ok"
    # the consumer shape round-trips into an alert
    alert = detectors.straggler_from_verdicts(v)
    assert alert["evidence"]["rank"] == "0"


# -- flush protocol: deltas, 409 resync, GET /timeseries ---------------------
def test_timeseries_delta_push_and_report(fresh_observe, rdv_server):
    server, port, secret = rdv_server
    ts_mod.record(ts_mod.STEP_SECONDS, 0.1, step=1)
    ts_mod.record(ts_mod.STEP_SECONDS, 0.2, step=2)
    pusher = ts_mod.TimeseriesPusher("127.0.0.1", port, 0, secret, 60.0)
    assert pusher.push()                  # first push: full snapshot
    assert pusher.full_pushes == 1
    assert pusher._server_id is not None  # acked by the real server
    ts_mod.record(ts_mod.STEP_SECONDS, 0.3, step=3)
    assert pusher.push()                  # second: delta (1 new sample)
    assert pusher.delta_pushes == 1
    assert pusher.push()                  # nothing new: no round trip
    assert pusher.delta_pushes == 1

    report = server.timeseries_report()
    samples = report["ranks"]["0"]["series"][ts_mod.STEP_SECONDS]["samples"]
    assert [s[0] for s in samples] == [1, 2, 3]
    assert report["summary"][ts_mod.STEP_SECONDS]["ranks"]["0"]["last"] \
        == pytest.approx(0.3)
    assert report["summary"][ts_mod.STEP_SECONDS]["ranks"]["0"][
        "last_step"] == 3

    from horovod_tpu.run.http_client import get_timeseries

    over_http = get_timeseries("127.0.0.1", port, secret=secret)
    assert over_http["summary"][ts_mod.STEP_SECONDS]["ranks"]["0"][
        "count"] == 3


def test_timeseries_delta_409_resyncs_on_new_incarnation(fresh_observe):
    from horovod_tpu.run.http_server import RendezvousServer

    secret = b"observe-secret"
    server = RendezvousServer(secret=secret)
    port = server.start()
    try:
        ts_mod.record(ts_mod.STEP_SECONDS, 0.1, step=1)
        pusher = ts_mod.TimeseriesPusher("127.0.0.1", port, 0, secret, 60.0)
        assert pusher.push()
        sid = pusher._server_id
        assert sid is not None
    finally:
        server.stop()
    # a NEW incarnation on a fresh port: the stale base_id must 409 and
    # the pusher must recover with one full snapshot
    server2 = RendezvousServer(secret=secret)
    port2 = server2.start()
    try:
        pusher.port = port2
        ts_mod.record(ts_mod.STEP_SECONDS, 0.2, step=2)
        assert pusher.push()
        assert pusher.resyncs == 1
        assert pusher._server_id != sid
        report = server2.timeseries_report()
        samples = report["ranks"]["0"]["series"][
            ts_mod.STEP_SECONDS]["samples"]
        assert [s[0] for s in samples] == [1, 2]   # nothing lost
    finally:
        server2.stop()


def test_alerts_report_orders_newest_first(rdv_server):
    server, port, secret = rdv_server
    for i in range(3):
        server.put("alerts", str(i), json.dumps(
            {"id": str(i), "signal": "step_time_regression",
             "severity": "warning"}).encode())
    report = server.alerts_report()
    assert [a["id"] for a in report["alerts"]] == ["2", "1", "0"]
    assert report["counts"] == {"step_time_regression": 3}

    from horovod_tpu.run.http_client import get_alerts

    assert get_alerts("127.0.0.1", port, secret=secret)["counts"] == \
        {"step_time_regression": 3}


# -- watchdog ----------------------------------------------------------------
def _push_cadence(server, rank, samples):
    doc = {"series": {ts_mod.STEP_SECONDS: {
        "samples": [[s, v] for s, v in samples],
        "seq": len(samples), "last_step": samples[-1][0]}}}
    server.put("timeseries", str(rank), json.dumps(doc).encode())


def test_watchdog_tick_publishes_straggler_alert_and_arms(
        fresh_observe, rdv_server, monkeypatch, tmp_path):
    server, port, secret = rdv_server
    monkeypatch.setenv("HVD_TIMELINE", str(tmp_path / "trace"))
    dog = Watchdog(server, interval=60.0)
    base = [(i + 1, 0.100) for i in range(16)]
    slow = [(i + 1, 0.140) for i in range(16)]
    for rank in (0, 2, 3):
        _push_cadence(server, rank, base)
    _push_cadence(server, 1, slow)
    published = dog.tick()
    assert len(published) == 1
    alert = published[0]
    assert alert["signal"] == "straggler_drift"
    assert alert["evidence"]["rank"] == "1"
    # cooldown: the same persisting condition does not re-alert
    assert dog.tick() == []
    # the alert landed in the KV scope with the armed window attached
    report = server.alerts_report()
    assert report["alerts"][0]["evidence"]["rank"] == "1"
    armed = report["alerts"][0]["armed"]
    assert armed["start_step"] == 16 + dog.arm_margin
    assert armed["end_step"] == armed["start_step"] + dog.arm_steps - 1
    # and the arm record is broadcast for workers to poll
    raw = server.get(autoarm.ARM_SCOPE, autoarm.ARM_KEY)
    rec = json.loads(raw)
    assert rec["start_step"] == armed["start_step"]
    assert rec["signal"] == "straggler_drift"


def test_watchdog_regression_alert_fires_within_window(
        fresh_observe, rdv_server):
    server, port, secret = rdv_server
    dog = Watchdog(server, interval=60.0)
    quiet = [(i + 1, 0.100 if i % 2 else 0.101) for i in range(48)]
    for rank in (0, 1):
        _push_cadence(server, rank, quiet)
    assert dog.tick() == []          # quiet trace: silent
    regressed = quiet + [(49 + i, 0.160) for i in range(8)]
    _push_cadence(server, 0, regressed)
    published = dog.tick()
    signals = {a["signal"] for a in published}
    assert "step_time_regression" in signals
    reg = next(a for a in published
               if a["signal"] == "step_time_regression")
    assert reg["evidence"]["rank"] == "0"
    assert reg["evidence"]["ewma"] > reg["evidence"]["threshold"]


def test_watchdog_evicts_critical_straggler_via_driver(
        fresh_observe, rdv_server, monkeypatch):
    server, port, secret = rdv_server
    monkeypatch.setenv("HVD_WATCH_EVICT", "1")

    class _Driver:
        world = ["w0", "w1", "w2", "w3"]

        def __init__(self):
            self.removed = []

        def remove(self, worker, reason, *, drain=False, cause_id=None):
            self.removed.append((worker, drain))
            return True

    dog = Watchdog(server, interval=60.0)
    assert dog.evict
    driver = _Driver()
    dog.attach_driver(driver)
    for rank in (0, 2, 3):
        _push_cadence(server, rank, [(i + 1, 0.100) for i in range(16)])
    # ratio 2.0 >= the 1.6 critical bar -> eviction
    _push_cadence(server, 1, [(i + 1, 0.200) for i in range(16)])
    (alert,) = dog.tick()
    assert alert["severity"] == "critical"
    assert driver.removed == [("w1", True)]
    assert alert["evicted"] == "w1"


def test_watchdog_no_evict_by_default(fresh_observe, rdv_server):
    server, port, secret = rdv_server
    dog = Watchdog(server, interval=60.0)
    assert not dog.evict


# -- auto-arm: worker side ---------------------------------------------------
def test_autoarm_applies_once_per_id_to_timeline(
        fresh_observe, rdv_server, tmp_path, monkeypatch):
    import importlib

    tl_mod = importlib.import_module("horovod_tpu.timeline.timeline")

    server, port, secret = rdv_server
    monkeypatch.setattr(tl_mod, "timeline", tl_mod.Timeline())
    import horovod_tpu.observe.autoarm as aa

    # the rank is at training step 20 per its cadence series
    for i in range(20):
        ts_mod.record(ts_mod.STEP_SECONDS, 0.1, step=i + 1)
    autoarm.broadcast_arm(server, "arm-1", 36, 43, "straggler_drift",
                          str(tmp_path / "armtrace"))
    assert aa.poll_and_apply("127.0.0.1", port, secret=secret)
    assert tl_mod.timeline.active          # writer opened in the arm dir
    # global [36, 43] with the rank at step 20: the window opens 16
    # steps on
    for _ in range(15):
        tl_mod.timeline.record_step()
    assert not tl_mod.timeline.enabled
    tl_mod.timeline.record_step()
    assert tl_mod.timeline.enabled
    # idempotent: the same arm id is not applied twice
    assert not aa.poll_and_apply("127.0.0.1", port, secret=secret)
    tl_mod.timeline.shutdown()


def test_autoarm_disabled_by_knob(fresh_observe, rdv_server, monkeypatch):
    server, port, secret = rdv_server
    monkeypatch.setenv("HVD_WATCH_ARM", "0")
    autoarm.broadcast_arm(server, "arm-9", 10, 20, "x", None)
    assert not autoarm.poll_and_apply("127.0.0.1", port, secret=secret)


# -- hvd_watch CLI -----------------------------------------------------------
def test_hvd_watch_check_fixture():
    import subprocess
    import sys
    from pathlib import Path

    script = Path(__file__).resolve().parents[1] / "scripts" / "hvd_watch.py"
    p = subprocess.run([sys.executable, str(script), "--check"],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "OK" in p.stdout


def test_hvd_watch_renders_live_endpoint(fresh_observe, rdv_server,
                                         capsys):
    import sys
    from pathlib import Path

    server, port, secret = rdv_server
    _push_cadence(server, 0, [(1, 0.1), (2, 0.1)])
    server.put("alerts", "0", json.dumps({
        "id": "0", "signal": "step_time_regression", "severity": "warning",
        "evidence": {"rank": "0"},
        "window": {"start_step": 1, "end_step": 2, "samples": 2},
    }).encode())
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))
    try:
        import hvd_watch
    finally:
        sys.path.pop(0)
    out = hvd_watch.main([f"127.0.0.1:{port}",
                          "--secret", secret.hex()])
    text = capsys.readouterr().out
    assert "step_seconds" in text
    assert "step_time_regression" in text
    assert out["alerts"]["counts"] == {"step_time_regression": 1}


# -- e2e smoke: injected slow rank -> alert names it -> window armed ---------
def test_e2e_slow_rank_fault_alerts_arms_and_attributes(
        fresh_observe, rdv_server, tmp_path, monkeypatch):
    """Acceptance smoke (ISSUE 16): a PR-4 ``slow=`` step-seam fault on
    rank 1 shows up in its measured cadence; the watchdog raises a
    straggler alert naming rank 1 within HVD_WATCH_WINDOW steps and
    auto-arms a trace window that every rank applies at the
    KV-consistent step."""
    import importlib

    from horovod_tpu.elastic.faults import FaultInjector, parse_spec
    tl_mod = importlib.import_module("horovod_tpu.timeline.timeline")

    server, port, secret = rdv_server
    dog = Watchdog(server, interval=60.0)
    window = dog.window

    faults = parse_spec("rank=1:kind=slow=30ms:seam=step")
    stores = {r: ts_mod.TimeseriesStore(enabled=True) for r in ("0", "1")}
    injectors = {"0": FaultInjector(faults, rank=0, restart=0),
                 "1": FaultInjector(faults, rank=1, restart=0)}

    # each rank runs its own step loop; only rank 1's injector fires,
    # and the skew lands in its REAL measured dispatch-to-dispatch
    # cadence (rank 1 30 ms a step from the fault alone, rank 0 what the
    # loop costs: no sleep stands in for work, so a loaded host can only
    # widen a gap that is already over a thousandfold)
    steps = 16
    for rank, st in stores.items():
        last = 0.0
        for step in range(1, steps + 1):
            assert step <= window
            injectors[rank].fire("step")
            now = time.perf_counter()
            if last:
                st.record(ts_mod.STEP_SECONDS, now - last, step=step)
            last = now
        server.put("timeseries", rank, json.dumps(st.snapshot()).encode())

    published = dog.tick()
    stragglers = [a for a in published
                  if a["signal"] == "straggler_drift"]
    assert stragglers, f"no straggler alert in {published}"
    alert = stragglers[0]
    assert alert["evidence"]["rank"] == "1"
    assert alert["window"]["samples"] <= window
    armed = alert.get("armed")
    assert armed, "confirmed straggler alert must auto-arm"

    # worker side: every rank (a process each in a job: its own store,
    # timeline and applied ids) finds the armed record and opens its
    # window at the KV-consistent step, ``steps`` cadence steps behind it
    for rank in ("0", "1"):
        monkeypatch.setattr(ts_mod, "store", stores[rank])
        monkeypatch.setattr(tl_mod, "timeline", tl_mod.Timeline())
        autoarm.reset()
        assert autoarm.poll_and_apply("127.0.0.1", port, secret=secret)
        assert tl_mod.timeline.active
        for _ in range(armed["start_step"] - steps - 1):
            tl_mod.timeline.record_step()
        assert not tl_mod.timeline.enabled
        tl_mod.timeline.record_step()
        assert tl_mod.timeline.enabled
        tl_mod.timeline.shutdown()
    from horovod_tpu.run.http_client import get_alerts

    final = get_alerts("127.0.0.1", port, secret=secret)["alerts"][0]
    assert final["evidence"]["rank"] == "1"
    assert final["armed"]["id"] == armed["id"]
