"""Control-plane flight recorder (docs/observe.md "The flight
recorder"): the correlated event timeline — recorder ring + overflow
accounting, launcher/worker sinks, ``GET /events`` with filters, chain
extraction on the hand-written fixture, the ``hvd_events`` /
``hvd_dash`` consoles, the trace-merge instant-event row, and the
end-to-end incident: a lease expiry produces ONE connected causal
chain (expiry → removal → abort → shrink epoch → observe → resume)
across the launcher and worker actors."""

import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from horovod_tpu import metrics
from horovod_tpu.elastic import heartbeat as hb_mod, membership
from horovod_tpu.elastic.abort import HorovodAbortError
from horovod_tpu.elastic.driver import ElasticDriver
from horovod_tpu.elastic.heartbeat import HeartbeatThread
from horovod_tpu.observe import events as events_mod
from horovod_tpu.observe.fixtures import (
    EVENTS_EXPECTED,
    evaluate_events_fixture,
    events_fixture,
)
from horovod_tpu.run import http_client, relay as relay_mod
from horovod_tpu.run.http_server import RendezvousServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")
SECRET = b"events-test"


def _wait_for(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _import_script(name):
    sys.path.insert(0, SCRIPTS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _fresh_events(monkeypatch):
    """A clean recorder per test, no leaked flusher threads, and no
    accidental lazy-flusher start from ambient rendezvous env."""
    monkeypatch.delenv("HVD_METRICS_KV_ADDR", raising=False)
    monkeypatch.delenv("HVD_METRICS_KV_PORT", raising=False)
    events_mod._reset_for_tests()
    relay_mod._reset_for_tests()
    yield
    events_mod._reset_for_tests()
    relay_mod._reset_for_tests()
    http_client.reset_pool()


@pytest.fixture()
def server():
    s = RendezvousServer(secret=SECRET)
    s.start()
    yield s
    s.stop()


# -- the fixture contract (hvd_events --check, tier-1) -----------------------
def test_fixture_chain_matches_pinned_expectations():
    got = evaluate_events_fixture()
    for field, want in EVENTS_EXPECTED.items():
        if field == "duration_seconds":
            assert abs(got[field] - want) < 1e-9, (field, got[field])
        else:
            assert got[field] == want, (field, got[field])


def test_fixture_chain_excludes_unrelated_checkpoint_event():
    fx = events_fixture()
    chain = events_mod.extract_chain(fx, "worker2-9-1")
    assert "launcher-1-4" not in {e["id"] for e in chain}
    assert len(chain) == 6


def test_fixture_mid_chain_entry_reconstructs_same_chain():
    fx = events_fixture()
    tail = events_mod.extract_chain(fx, "worker2-9-1")
    mid = events_mod.extract_chain(fx, "launcher-1-2")
    root = events_mod.extract_chain(fx, "launcher-1-0")
    assert [e["id"] for e in mid] == [e["id"] for e in tail]
    assert [e["id"] for e in root] == [e["id"] for e in tail]


def test_hvd_events_check_cli_green():
    p = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "hvd_events.py"),
         "--check"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "OK" in p.stdout


# -- recorder: ids, correlation threading, overflow --------------------------
def test_record_threads_correlation_through_cause_links():
    r = events_mod.Recorder(cap=64)
    root = r.record("lease.expired", severity="critical")
    mid = r.record("epoch.remove", cause_id=root)
    leaf = r.record("abort.publish", cause_id=mid)
    other = r.record("checkpoint.save")
    evs = {e["id"]: e for e in r.drain()}
    assert evs[root]["correlation_id"] == root
    # correlation is inherited TRANSITIVELY: the leaf's cause is mid,
    # but the incident name stays the root id
    assert evs[mid]["correlation_id"] == root
    assert evs[leaf]["correlation_id"] == root
    assert evs[other]["correlation_id"] == other  # a fresh chain root
    assert len({root, mid, leaf, other}) == 4     # ids unique


def test_record_honors_explicit_correlation_id():
    r = events_mod.Recorder(cap=8)
    eid = r.record("abort.observe", correlation_id="launcher-7-0",
                   cause_id="launcher-7-3")
    (ev,) = r.drain()
    assert ev["id"] == eid
    assert ev["correlation_id"] == "launcher-7-0"
    assert ev["cause_id"] == "launcher-7-3"


def test_ring_overflow_drops_oldest_and_counts_metric():
    before = metrics.EVENTS_DROPPED.get()
    r = events_mod.Recorder(cap=4)
    ids = [r.record("epoch.commit", payload={"n": i}) for i in range(10)]
    assert r.pending() == 4
    assert r.dropped == 6
    kept = [e["id"] for e in r.drain()]
    assert kept == ids[-4:]                       # oldest evicted first
    assert metrics.EVENTS_DROPPED.get() == before + 6


def test_requeue_preserves_order_and_respects_cap():
    r = events_mod.Recorder(cap=4)
    for i in range(3):
        r.record("epoch.commit", payload={"n": i})
    batch = r.drain()
    r.record("epoch.admit")                        # arrived mid-flush
    r.requeue(batch)
    kinds = [e["kind"] for e in r.drain()]
    assert kinds == ["epoch.commit"] * 3 + ["epoch.admit"]


def test_recorder_overhead_under_one_percent_of_1ms_step():
    """The overhead pin: a record() append (dict build + deque push +
    counter inc) must average < 10 us — 1% of even a 1 ms step; real
    emitters fire at lifecycle cadence, not step cadence."""
    r = events_mod.Recorder(cap=8192)
    n = 2000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(n):
            r.record("epoch.commit", payload={"epoch": i})
        best = min(best, (time.perf_counter() - t0) / n)
        r.drain()
    assert best * 1e6 < 10.0, f"record() mean {best * 1e6:.2f} us"


# -- launcher sink + GET /events ---------------------------------------------
def test_attach_server_journals_events_and_get_roundtrip(server):
    events_mod.attach_server(server)
    root = events_mod.record_event("lease.expired", severity="critical",
                                   payload={"rank": 1}, rank=1)
    events_mod.record_event("epoch.remove", severity="warning",
                            cause_id=root)
    report = http_client.get_events("127.0.0.1", server.port,
                                    secret=SECRET)
    assert report["server_id"] == server.server_id
    assert report["version"] >= 2
    kinds = [e["kind"] for e in report["events"]]
    assert kinds == ["lease.expired", "epoch.remove"]  # oldest first
    assert report["counts"] == {"lease.expired": 1, "epoch.remove": 1}
    assert report["events"][1]["correlation_id"] == root


def test_get_events_filters_since_ts_and_kind(server):
    events_mod.attach_server(server)
    events_mod.record_event("epoch.commit")
    cut = time.time()
    time.sleep(0.01)
    events_mod.record_event("abort.publish")
    events_mod.record_event("abort.observe")
    by_ts = http_client.get_events("127.0.0.1", server.port,
                                   secret=SECRET, since_ts=cut)
    assert [e["kind"] for e in by_ts["events"]] == \
        ["abort.publish", "abort.observe"]
    by_kind = http_client.get_events("127.0.0.1", server.port,
                                     secret=SECRET, kind="abort.")
    assert {e["kind"] for e in by_kind["events"]} == \
        {"abort.publish", "abort.observe"}


def test_server_scope_pruned_to_cap(server):
    events_mod.attach_server(server)
    ids = [events_mod.record_event("epoch.commit", payload={"n": i})
           for i in range(6)]
    dropped = events_mod.prune_scope(server, cap=2)
    assert dropped == 4
    report = server.events_report()
    assert [e["id"] for e in report["events"]] == ids[-2:]  # newest kept


def test_undecodable_event_record_survives_report(server):
    server.put(events_mod.EVENTS_SCOPE, "bad", b"\x00not-json")
    report = server.events_report()
    (rec,) = report["events"]
    assert rec["id"] == "bad" and rec["error"] == "<undecodable>"


# -- worker sink: the flusher ------------------------------------------------
def test_worker_flusher_lazy_start_and_exactly_once(server, monkeypatch):
    monkeypatch.setenv("HVD_METRICS_KV_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_METRICS_KV_PORT", str(server.port))
    monkeypatch.setenv("HVD_METRICS_SECRET", SECRET.hex())
    monkeypatch.setenv("HVD_EVENTS_FLUSH_SECONDS", "3600")
    eid = events_mod.record_event("checkpoint.save", payload={"step": 3})
    rec = events_mod.recorder()
    assert rec._flusher is not None                # lazily started
    assert rec._flusher.flush_now()
    assert rec._flusher.flush_now()                # drained: a no-op
    report = http_client.get_events("127.0.0.1", server.port,
                                    secret=SECRET)
    assert [e["id"] for e in report["events"]] == [eid]  # exactly once


def test_flusher_requeues_on_dead_server_then_delivers(server,
                                                       monkeypatch):
    monkeypatch.setenv("HVD_HTTP_RETRIES", "0")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    r = events_mod.Recorder(cap=8)
    f = events_mod.EventFlusher(r, "127.0.0.1", dead_port,
                                secret=SECRET, interval=3600.0)
    eid = r.record("epoch.commit")
    assert not f.flush_now()
    assert f.errors == 1 and r.pending() == 1      # kept, not lost
    f.port = server.port                           # the server comes back
    assert f.flush_now()
    assert r.pending() == 0
    report = http_client.get_events("127.0.0.1", server.port,
                                    secret=SECRET)
    assert [e["id"] for e in report["events"]] == [eid]


def test_events_scope_rides_relay_batch_path():
    # unique per-process keys are what make last-writer-wins coalescing
    # safe for events; the scope must stay in the relay's batch set
    assert events_mod.EVENTS_SCOPE in relay_mod.BATCH_SCOPES


# -- the consoles ------------------------------------------------------------
def test_hvd_events_renders_timeline_and_chain(server, capsys):
    events_mod.attach_server(server)
    for ev in events_fixture():
        server.put(events_mod.EVENTS_SCOPE, ev["id"],
                   json.dumps(ev).encode())
    hvd_events = _import_script("hvd_events")
    hvd_events.main([f"127.0.0.1:{server.port}", "--secret",
                     SECRET.hex()])
    text = capsys.readouterr().out
    assert "lease.expired" in text and "restart.resume" in text
    out = hvd_events.main([f"127.0.0.1:{server.port}", "--secret",
                           SECRET.hex(), "--chain", "worker2-9-1"])
    text = capsys.readouterr().out
    assert "failed rank 1" in text
    assert "3 step(s) lost" in text
    assert "1.5s expiry-to-resume" in text
    assert out["summary"]["kinds"] == EVENTS_EXPECTED["kinds"]


def test_hvd_dash_one_page_and_incident_json(server, capsys):
    events_mod.attach_server(server)
    for ev in events_fixture():
        server.put(events_mod.EVENTS_SCOPE, ev["id"],
                   json.dumps(ev).encode())
    hvd_dash = _import_script("hvd_dash")
    hvd_dash.main([f"127.0.0.1:{server.port}", "--secret", SECRET.hex()])
    text = capsys.readouterr().out
    assert "events: 7" in text
    assert "incidents: 1" in text
    out = hvd_dash.main([f"127.0.0.1:{server.port}", "--secret",
                         SECRET.hex(), "--incident", "--json"])
    payload = json.loads(capsys.readouterr().out)
    # the incident report joins the peer state plane's recovery
    # capital; with no snapshots pushed the digest is empty but present
    assert payload == {"incidents": out["incidents"],
                       "peerstate": out["peerstate"]}
    assert payload["peerstate"]["newest_committed_gen"] is None
    (incident,) = out["incidents"]
    assert incident["summary"]["failed_rank"] == 1
    assert incident["summary"]["steps_lost"] == 3
    assert [e["id"] for e in incident["chain"]] == \
        [e["id"] for e in
         events_mod.extract_chain(events_fixture(), "worker2-9-1")]


def test_follow_consoles_mark_server_restart(tmp_path):
    """Satellite: a new server incarnation on the same port must print
    the restart marker in both following consoles (hvd_watch resets its
    seen-alert set; hvd_events resets its ts cursor)."""
    first = RendezvousServer(secret=SECRET)
    port = first.start()
    first.put("alerts", "0", json.dumps(
        {"id": "0", "signal": "step_time_regression", "severity": "warning",
         "evidence": {}, "window": {}}).encode())
    first.put(events_mod.EVENTS_SCOPE, "e0", json.dumps(
        {"id": "e0", "ts": 1.0, "kind": "epoch.commit",
         "severity": "info"}).encode())
    outs = {s: tmp_path / f"{s}.out" for s in ("hvd_watch", "hvd_events")}
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(SCRIPTS, f"{script}.py"),
         f"127.0.0.1:{port}", "--secret", SECRET.hex(),
         "--follow", "--interval", "0.15"],
        stdout=open(outs[script], "w"), stderr=subprocess.DEVNULL)
        for script in outs]
    second = None
    try:
        # each console proved it polled incarnation 1 (slow interpreter
        # start must not race the restart)
        assert _wait_for(lambda: "step_time_regression" in outs["hvd_watch"]
                         .read_text(), timeout=60.0), procs
        assert _wait_for(lambda: "epoch.commit" in outs["hvd_events"]
                         .read_text(), timeout=60.0)
        first.stop()
        second = RendezvousServer(secret=SECRET, port=port)
        second.start()
        for name, path in outs.items():
            assert _wait_for(
                lambda: "--- server restarted ---" in path.read_text(),
                timeout=30.0), (name, path.read_text())
    finally:
        for p in procs:
            p.kill()
            p.wait(timeout=30)
        if second is not None:
            second.stop()


# -- trace merge: the control-plane instant-event row ------------------------
def test_trace_merge_adds_control_plane_row(tmp_path):
    from horovod_tpu.timeline import merge as merge_mod

    d = tmp_path / "0"
    d.mkdir()
    (d / "comm.json").write_text(json.dumps([
        {"name": "ALLREDUCE", "cat": "t", "ph": "X", "ts": 100.0,
         "dur": 50.0, "pid": 0, "tid": "t"}]))
    (tmp_path / merge_mod.EVENTS_JSON).write_text(json.dumps(
        {"events": events_fixture()}))
    merged = merge_mod.merge_traces(str(tmp_path))
    evs = merged["traceEvents"]
    row = [e for e in evs
           if e.get("pid") == merge_mod.EVENTS_PID and e.get("ph") == "i"]
    assert len(row) == 7
    # anchored: the earliest recorder event lands on the earliest trace
    # ts; relative spacing survives (100.0 -> 101.5 s = 1.5e6 us)
    by_name = {e["args"]["id"]: e for e in row}
    comm_ts = min(e["ts"] for e in evs if e.get("ph") == "X")
    assert by_name["launcher-1-0"]["ts"] == pytest.approx(comm_ts)
    assert by_name["worker2-9-1"]["ts"] - \
        by_name["launcher-1-0"]["ts"] == pytest.approx(1.5e6)
    assert by_name["launcher-1-2"]["name"] == "abort.publish"
    assert by_name["worker2-9-1"]["args"]["correlation_id"] == \
        "launcher-1-0"
    meta = [e for e in evs if e.get("ph") == "M"
            and e.get("pid") == merge_mod.EVENTS_PID
            and e.get("name") == "process_name"]
    assert meta and meta[0]["args"]["name"] == "control plane"


def test_trace_merge_without_events_artifact_unchanged(tmp_path):
    from horovod_tpu.timeline import merge as merge_mod

    d = tmp_path / "0"
    d.mkdir()
    (d / "comm.json").write_text(json.dumps([
        {"name": "ALLREDUCE", "cat": "t", "ph": "X", "ts": 1.0,
         "dur": 2.0, "pid": 0, "tid": "t"}]))
    merged = merge_mod.merge_traces(str(tmp_path))
    assert not any(e.get("pid") == merge_mod.EVENTS_PID
                   for e in merged["traceEvents"])


# -- end to end: one incident, one connected chain ---------------------------
@pytest.fixture()
def elastic_rdv(server, monkeypatch):
    """Launcher-attached recorder + worker-side env at the same server,
    heartbeat/membership singletons reset around the test."""
    monkeypatch.setenv("HVD_METRICS_KV_ADDR", "127.0.0.1")
    monkeypatch.setenv("HVD_METRICS_KV_PORT", str(server.port))
    monkeypatch.setenv("HVD_METRICS_SECRET", SECRET.hex())
    monkeypatch.setenv("HVD_ELASTIC", "1")
    monkeypatch.setenv("HVD_ELASTIC_TIMEOUT_SECONDS", "10")
    monkeypatch.setenv("HVD_HEARTBEAT_INTERVAL_SECONDS", "0.1")
    membership._reset_for_tests()
    events_mod.attach_server(server)
    yield server
    hb_mod.stop()
    membership._reset_for_tests()


class _SyncedState:
    """A 12-step state whose post-shrink sync replays back to step 9 —
    the 3 lost steps the incident report must name."""

    def __init__(self):
        self.step = 12

    def sync(self, epoch):
        self.step = 9


def test_e2e_lease_expiry_produces_connected_chain(elastic_rdv,
                                                   monkeypatch, capsys):
    """The acceptance drive, in process over the real wire: rank 1's
    lease expires; the driver removes it, publishes the abort, commits
    the shrink epoch; a surviving rank observes the abort and resumes 3
    steps back — and GET /events holds ONE connected chain for the
    whole incident, which both consoles render naming the failed rank
    and the steps lost."""
    server = elastic_rdv
    drv = ElasticDriver(server, ["0", "1", "2"], min_np=1,
                        controller="xla")
    monkeypatch.setenv("HVD_ELASTIC_WORKER_ID", "0")
    monkeypatch.setenv("HVD_PROCESS_ID", "0")
    monkeypatch.setenv("HVD_NUM_PROCESSES", "3")
    # every worker acked epoch 0: lease enforcement needs a stable epoch
    for w in ("0", "1", "2"):
        server.put("membership", f"ready.0.{w}", b"{}")
    # the survivor's heartbeat (it will observe the abort flag)
    hb = HeartbeatThread(0, 3, "127.0.0.1", server.port, secret=SECRET,
                         interval=0.05)
    hb.start()
    calls = []

    def train(state):
        calls.append(membership.current_epoch())
        if len(calls) > 1:
            return "done"
        # rank 1 held a lease once, then went silent long past the bar
        server.put("health", "1", json.dumps(
            {"rank": 1, "interval": 0.1, "count": 3, "pid": 4242}
        ).encode())
        with server._httpd.lock:
            server._httpd.lease_times["/health/1"] = \
                time.monotonic() - 60.0
        assert _wait_for(
            lambda: (drv.poll() or drv.world == ["0", "2"]),
            timeout=10.0), drv.world
        assert _wait_for(lambda: hb.abort_info is not None)
        raise HorovodAbortError("coordinated abort: lease expired")

    state = _SyncedState()
    try:
        assert membership.run(train, state) == "done"
        report = http_client.get_events("127.0.0.1", server.port,
                                        secret=SECRET)
        evs = report["events"]
        resume = [e for e in evs if e["kind"] == "restart.resume"][-1]
        chain = events_mod.extract_chain(evs, resume["id"])
        kinds = [e["kind"] for e in chain]
        assert sorted(kinds) == sorted(EVENTS_EXPECTED["kinds"]), kinds
        assert kinds[0] == "lease.expired"
        assert kinds[-1] == "restart.resume"
        # every link resolves inside the chain — it is CONNECTED, not
        # just co-sorted
        ids = {e["id"] for e in chain}
        for e in chain:
            assert e["cause_id"] is None or e["cause_id"] in ids, e
        summary = events_mod.chain_summary(chain)
        assert summary["failed_rank"] == 1
        assert summary["steps_lost"] == 3
        assert summary["duration_seconds"] is not None
        # the epoch record carried the ids across the process boundary
        rec = json.loads(server.get("membership", "epoch"))
        assert rec["event_id"] in ids
        assert resume["cause_id"] == rec["event_id"]
        # console renderings of the SAME incident
        hvd_events = _import_script("hvd_events")
        hvd_events.main([f"127.0.0.1:{server.port}", "--secret",
                         SECRET.hex(), "--chain", resume["id"]])
        text = capsys.readouterr().out
        assert "failed rank 1" in text and "3 step(s) lost" in text
        hvd_dash = _import_script("hvd_dash")
        out = hvd_dash.main([f"127.0.0.1:{server.port}", "--secret",
                             SECRET.hex(), "--incident", resume["id"],
                             "--json"])
        payload = json.loads(capsys.readouterr().out)
        (incident,) = payload["incidents"]
        assert [e["id"] for e in incident["chain"]] == \
            [e["id"] for e in chain]
        assert incident["summary"]["failed_rank"] == 1
        assert incident["summary"]["steps_lost"] == 3
        assert out["incidents"][0]["summary"] == incident["summary"]
    finally:
        hb.stop()
        drv.shutdown()


def test_e2e_fault_spec_crash_chains_exit_to_epoch(elastic_rdv,
                                                   monkeypatch):
    """The HVD_FAULT_SPEC leg: a worker killed by the injected crash
    (exit 17) is removed by the launcher path, and the abort/commit
    events form one chain a survivor's observe joins."""
    server = elastic_rdv
    drv = ElasticDriver(server, ["0", "1"], min_np=1, controller="xla")
    hb = HeartbeatThread(0, 2, "127.0.0.1", server.port, secret=SECRET,
                         interval=0.05)
    hb.start()
    try:
        # the supervisor's reaction to the fault-injected exit code
        # (faults.FAULT_EXIT_CODE == 17; the process-spawn drive is
        # test_elastic_membership's slow e2e)
        assert drv.remove("1", "worker 1 exited with code 17")
        assert _wait_for(lambda: hb.abort_info is not None)
        report = http_client.get_events("127.0.0.1", server.port,
                                        secret=SECRET)
        evs = report["events"]
        observe = [e for e in evs if e["kind"] == "abort.observe"][-1]
        chain = events_mod.extract_chain(evs, observe["id"])
        kinds = [e["kind"] for e in chain]
        assert "epoch.remove" in kinds and "abort.publish" in kinds \
            and "epoch.commit" in kinds
        assert observe["cause_id"] in {e["id"] for e in chain}
        assert "code 17" in str(
            [e for e in chain if e["kind"] == "epoch.remove"]
            [0]["payload"]["reason"])
    finally:
        hb.stop()
        drv.shutdown()
