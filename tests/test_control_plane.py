"""Hierarchical HA control plane (docs/control_plane.md): sharded KV
store, batch endpoints, keep-alive/failover client, per-host relay,
journal + warm-standby takeover, heartbeat piggyback, metrics deltas,
and the churn-bench fixture.

Everything runs against REAL servers (HMAC-signed HTTP over loopback) —
the same wire path a pod takes, minus process spawn — so the failover
and fencing guarantees are pinned deterministically inside tier-1."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error

import pytest

from horovod_tpu import metrics
from horovod_tpu.elastic.driver import ElasticDriver
from horovod_tpu.elastic.heartbeat import HeartbeatThread
from horovod_tpu.run import http_client, relay as relay_mod
from horovod_tpu.run.http_server import (
    EpochFencedError,
    RendezvousServer,
)
from horovod_tpu.run.journal import (
    Journal,
    StandbyServer,
    read_entries,
    replay,
)
from horovod_tpu.run.store import ShardedKVStore
from horovod_tpu.utils import env as env_util

SECRET = b"control-plane-test"


def _wait_for(cond, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


@pytest.fixture()
def server():
    s = RendezvousServer(secret=SECRET)
    s.start()
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def _fresh_client_state():
    """Pooled connections and the cached relay endpoint must not leak
    across tests (a pool entry for a dead server is handled, but a
    cached relay endpoint would reroute unrelated tests)."""
    relay_mod._reset_for_tests()
    yield
    relay_mod._reset_for_tests()
    http_client.reset_pool()


# -- sharded store -----------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 8])
def test_sharded_store_roundtrip(shards):
    st = ShardedKVStore(shards=shards)
    st.put("/health/0", b"a")
    st.put("/health/1", b"b")
    st.put("/membership/epoch", b"c")
    assert st.get("/health/0") == b"a"
    assert len(st) == 3
    assert st.prefix_items("/health/") == {"/health/0": b"a",
                                           "/health/1": b"b"}
    assert st.pop("/health/1") == b"b"
    assert st.pop("/health/1") is None
    # DELETE semantics: exact key + everything under path/
    st.put("/membership/ready.0.w", b"1")
    deleted = st.delete_matching("/membership")
    assert sorted(deleted) == ["/membership/epoch", "/membership/ready.0.w"]
    st.put("/abort/flag", b"x")
    st.clear_scope("abort")
    assert st.get("/abort/flag") is None


def test_scope_since_change_protocol():
    st = ShardedKVStore(shards=4)
    first = st.scope_since("health")
    assert first["full"] and first["version"] == 0 and first["entries"] == {}
    st.put("/health/0", b"a")
    st.put("/health/1", b"b")
    v2 = st.scope_since("health", since=0)
    assert not v2["full"] and sorted(v2["entries"]) == ["0", "1"]
    cursor = v2["version"]
    # no changes → empty incremental
    idle = st.scope_since("health", since=cursor)
    assert idle["entries"] == {} and idle["removed"] == []
    # one change + one removal land in the next incremental
    st.put("/health/0", b"a2")
    st.pop("/health/1")
    inc = st.scope_since("health", since=cursor)
    assert inc["entries"] == {"0": b"a2"} and inc["removed"] == ["1"]
    # a cursor AHEAD of the version (another server incarnation) → full
    assert st.scope_since("health", since=10_000)["full"]
    # a scope clear invalidates cursors → full resync
    st.clear_scope("health")
    assert st.scope_since("health", since=cursor)["full"]


def test_scope_since_tombstone_pruning_forces_full():
    from horovod_tpu.run import store as store_mod

    st = ShardedKVStore(shards=2)
    st.put("/sanitizer/seed", b"s")
    cursor = st.scope_since("sanitizer")["version"]
    for i in range(store_mod.TOMBSTONE_LIMIT + 10):
        st.put(f"/sanitizer/k{i}", b"v")
        st.pop(f"/sanitizer/k{i}")
    out = st.scope_since("sanitizer", since=cursor)
    # the tombstone window was pruned past the cursor: the only honest
    # answer is a full snapshot
    assert out["full"] and sorted(out["entries"]) == ["seed"]


# -- server surface ----------------------------------------------------------
def test_scope_route_and_batch_put_over_http(server):
    port = server.port
    reply = http_client.put_batch("127.0.0.1", port, [
        ("/health/0", b'{"interval": 1}'),
        ("/sanitizer/world.0.0.0", b"{}"),
        ("not-a-path", b""),  # undecodable entry: skipped, counted
    ], secret=SECRET)
    assert reply["applied"] == 2 and reply["skipped"] == 1
    assert reply["server_id"] == server.server_id
    out = http_client.get_scope("127.0.0.1", port, "health", secret=SECRET)
    assert out["full"] and out["entries"] == {"0": b'{"interval": 1}'}
    # incremental cursor over HTTP
    http_client.put_kv("127.0.0.1", port, "health", "1", b"{}",
                       secret=SECRET)
    inc = http_client.get_scope("127.0.0.1", port, "health",
                                since=out["version"], secret=SECRET)
    assert not inc["full"] and sorted(inc["entries"]) == ["1"]
    # batch PUTs stamp health leases on the server clock
    assert "0" in server.health_report()["ranks"]


def test_health_put_reply_carries_abort_verdict(server):
    port = server.port
    reply = http_client.put_kv_reply("127.0.0.1", port, "health", "0",
                                     b'{"interval": 1}', secret=SECRET)
    assert reply["abort"] is None
    server.put("abort", "flag", json.dumps({"reason": "boom"}).encode())
    reply = http_client.put_kv_reply("127.0.0.1", port, "health", "0",
                                     b'{"interval": 1}', secret=SECRET)
    assert reply["abort"]["reason"] == "boom"


def test_epoch_fencing_in_process_and_http(server):
    server.put("membership", "epoch", json.dumps({"epoch": 3}).encode())
    with pytest.raises(EpochFencedError):
        server.put("membership", "epoch", json.dumps({"epoch": 2}).encode())
    # same-epoch re-commit is an idempotent overwrite, not a regression
    server.put("membership", "epoch", json.dumps({"epoch": 3}).encode())
    with pytest.raises(urllib.error.HTTPError) as ei:
        http_client.put_kv("127.0.0.1", server.port, "membership", "epoch",
                           json.dumps({"epoch": 1}).encode(), secret=SECRET)
    assert ei.value.code == 409
    # the fence also guards /batch
    with pytest.raises(urllib.error.HTTPError) as ei:
        http_client.put_batch("127.0.0.1", server.port, [
            ("/membership/epoch", json.dumps({"epoch": 0}).encode()),
        ], secret=SECRET)
    assert ei.value.code == 409
    assert json.loads(server.get("membership", "epoch"))["epoch"] == 3


# -- keep-alive pooling ------------------------------------------------------
def test_keepalive_reuses_connections(server):
    http_client.reset_pool()
    before = metrics.HTTP_REUSE.get()
    for i in range(4):
        http_client.put_kv("127.0.0.1", server.port, "s", f"k{i}", b"v",
                           secret=SECRET)
    assert metrics.HTTP_REUSE.get() >= before + 3


def test_keepalive_disabled_by_knob(server, monkeypatch):
    monkeypatch.setenv(env_util.HVD_HTTP_KEEPALIVE, "0")
    http_client.reset_pool()
    before = metrics.HTTP_REUSE.get()
    for i in range(3):
        http_client.put_kv("127.0.0.1", server.port, "s", f"k{i}", b"v",
                           secret=SECRET)
    assert metrics.HTTP_REUSE.get() == before
    assert not getattr(http_client._pool_local, "conns", None)


def test_stale_pooled_connection_replaced_silently(server):
    """A server restart between requests closes the pooled connection;
    the client replaces it without burning the retry budget."""
    http_client.put_kv("127.0.0.1", server.port, "s", "k", b"v",
                       secret=SECRET)
    port = server.port
    server.stop()
    s2 = RendezvousServer(secret=SECRET, port=port)
    s2.start()
    try:
        before = metrics.HTTP_RETRIES.get()
        assert http_client.get_kv("127.0.0.1", port, "s", "k",
                                  secret=SECRET) is None  # fresh store
        assert metrics.HTTP_RETRIES.get() == before
    finally:
        s2.stop()


# -- ordered failover --------------------------------------------------------
def test_env_addr_failover(server, monkeypatch):
    standby = RendezvousServer(secret=SECRET)
    standby.start()
    primary_port = server.port
    try:
        monkeypatch.setenv(
            env_util.HVD_RENDEZVOUS_ADDRS,
            f"127.0.0.1:{primary_port},127.0.0.1:{standby.port}")
        standby.put("s", "k", b"from-standby")
        server.stop()
        http_client._active_target.clear()
        # the request names the dead primary; the env list reroutes it
        assert http_client.get_kv("127.0.0.1", primary_port, "s", "k",
                                  secret=SECRET) == b"from-standby"
    finally:
        standby.stop()
        http_client._active_target.clear()


def test_remote_store_failover_and_fencing(server):
    standby = RendezvousServer(secret=SECRET)
    standby.start()
    try:
        store = http_client.RemoteStore(
            [("127.0.0.1", server.port), ("127.0.0.1", standby.port)],
            secret=SECRET)
        store.put("membership", "epoch", json.dumps({"epoch": 5}).encode())
        standby.put("membership", "epoch",
                    json.dumps({"epoch": 5}).encode())
        server.stop()
        assert json.loads(store.get("membership", "epoch"))["epoch"] == 5
        with pytest.raises(EpochFencedError):
            store.put("membership", "epoch",
                      json.dumps({"epoch": 4}).encode())
        assert store.scope_items("membership")  # reads keep working
    finally:
        standby.stop()


# -- journal + warm standby --------------------------------------------------
def test_journal_records_and_replays(tmp_path):
    jp = str(tmp_path / "rdv.journal")
    journal = Journal(jp)
    store = ShardedKVStore(shards=4, journal=journal)
    store.put("/membership/epoch", b'{"epoch": 0}')
    store.put("/abort/flag", b"f")
    store.put("/metrics/0", b"{}")      # excluded scope: not journaled
    store.put("/health/0", b"{}")       # excluded scope: not journaled
    store.pop("/abort/flag")
    store.clear_scope("membership")
    store.put("/autotune/plan.1", b"p")
    journal.close()
    fresh = ShardedKVStore(shards=2)
    n = replay(jp, fresh)
    assert n == 5  # 2 puts + del + clear + put; excluded scopes absent
    assert fresh.items() == {"/autotune/plan.1": b"p"}


def test_journal_partial_trailing_line(tmp_path):
    jp = str(tmp_path / "j")
    rec = json.dumps({"op": "put", "p": "/a/b", "v": "YQ=="})
    with open(jp, "w") as f:
        f.write(rec + "\n" + rec[:10])  # primary mid-append
    entries, offset = read_entries(jp)
    assert len(entries) == 1
    with open(jp, "a") as f:
        f.write(rec[10:] + "\n")
    entries2, _ = read_entries(jp, offset)
    assert len(entries2) == 1 and entries2[0]["p"] == "/a/b"


def test_standby_tails_primary_mutations(tmp_path):
    jp = str(tmp_path / "rdv.journal")
    primary = RendezvousServer(secret=SECRET, journal_path=jp)
    primary.start()
    standby = StandbyServer(jp, secret=SECRET, poll_seconds=0.02)
    standby.start()
    try:
        primary.put("membership", "epoch",
                    json.dumps({"epoch": 0, "world": ["0"]}).encode())
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if standby.server.get("membership", "epoch") is not None:
                break
            time.sleep(0.02)
        rec = json.loads(standby.server.get("membership", "epoch"))
        assert rec["epoch"] == 0 and rec["world"] == ["0"]
        # the standby serves the same signed HTTP surface
        out = http_client.get_membership("127.0.0.1", standby.port,
                                         secret=SECRET)
        assert out["epoch"]["epoch"] == 0
    finally:
        standby.stop()
        primary.stop()


def test_failover_mid_shrink_keeps_epochs_consistent(tmp_path):
    """The acceptance e2e in-process: an elastic shrink in flight when
    the primary rendezvous dies must complete against the warm standby
    with zero lost membership epochs and no split-brain."""
    jp = str(tmp_path / "rdv.journal")
    primary = RendezvousServer(secret=SECRET, journal_path=jp)
    primary.start()
    standby = StandbyServer(jp, secret=SECRET, poll_seconds=0.02)
    standby.start()
    addrs = [("127.0.0.1", primary.port), ("127.0.0.1", standby.port)]
    store = http_client.RemoteStore(addrs, secret=SECRET)
    driver = ElasticDriver(store, ["0", "1", "2"], controller="xla")
    try:
        assert driver.epoch == 0
        # workers ack the initial epoch (driver's stability barrier)
        for w in ("0", "1", "2"):
            store.put("membership", f"ready.0.{w}", b"{}")
        driver.poll()
        assert driver._stable
        # let the standby catch up with epoch 0, then KILL the primary
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if standby.server.get("membership", "epoch") is not None:
                break
            time.sleep(0.02)
        primary.stop()
        # the shrink commits THROUGH the failover, on the standby
        assert driver.remove("2", "worker 2 exited with code 1")
        rec = json.loads(standby.server.get("membership", "epoch"))
        assert rec["epoch"] == 1 and rec["world"] == ["0", "1"]
        assert rec["removed"] == ["2"]
        out = http_client.get_membership("127.0.0.1", standby.port,
                                         secret=SECRET)
        assert out["epoch"]["epoch"] == 1  # /membership is consistent
        # split-brain fence: a resurrected stale driver (fresh epoch
        # counter) cannot roll the committed world back
        stale = http_client.RemoteStore(
            [("127.0.0.1", standby.port)], secret=SECRET)
        with pytest.raises(EpochFencedError):
            ElasticDriver(stale, ["0", "1", "2"], controller="xla")
        rec = json.loads(standby.server.get("membership", "epoch"))
        assert rec["epoch"] == 1 and rec["world"] == ["0", "1"]
    finally:
        driver.shutdown()
        standby.stop()


@pytest.mark.slow
def test_elastic_job_survives_launcher_death_with_heartbeats(
        tmp_path, monkeypatch):
    """The fuller e2e: REAL heartbeat daemons renew leases through the
    env failover list while the primary dies mid-job; the driver keeps
    supervising through the standby, detects a genuinely dead worker by
    lease expiry there, shrinks, and the survivor acks — zero lost
    epochs, no split-brain."""
    monkeypatch.setenv(env_util.HVD_HEARTBEAT_INTERVAL_SECONDS, "0.2")
    jp = str(tmp_path / "rdv.journal")
    primary = RendezvousServer(secret=SECRET, journal_path=jp)
    primary.start()
    standby = StandbyServer(jp, secret=SECRET, poll_seconds=0.02)
    standby.start()
    monkeypatch.setenv(
        env_util.HVD_RENDEZVOUS_ADDRS,
        f"127.0.0.1:{primary.port},127.0.0.1:{standby.port}")
    http_client._active_target.clear()
    store = http_client.RemoteStore(
        [("127.0.0.1", primary.port), ("127.0.0.1", standby.port)],
        secret=SECRET)
    driver = ElasticDriver(store, ["0", "1"], controller="xla")
    hbs = [HeartbeatThread(r, 2, "127.0.0.1", primary.port, secret=SECRET,
                           interval=0.2) for r in (0, 1)]
    try:
        for hb in hbs:
            hb.start()
        for w in ("0", "1"):
            store.put("membership", f"ready.0.{w}", b"{}")
        driver.poll()
        assert driver._stable
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if standby.server.get("membership", "epoch") is not None:
                break
            time.sleep(0.02)
        # launcher's rendezvous dies mid-job; renewals fail over via the
        # env address list (the daemons still name the dead primary)
        primary.stop()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if len(standby.server.health_report()["ranks"]) == 2:
                break
            time.sleep(0.05)
        assert len(standby.server.health_report()["ranks"]) == 2
        # worker 1 genuinely dies: its lease expires ON THE STANDBY and
        # the driver (already failed over) shrinks past it
        hbs[1].stop()
        deadline = time.monotonic() + 10.0
        while driver.epoch == 0 and time.monotonic() < deadline:
            driver.poll()
            time.sleep(0.1)
        rec = json.loads(standby.server.get("membership", "epoch"))
        assert rec["epoch"] == 1 and rec["world"] == ["0"]
        # the survivor acks the shrink epoch; the job completes
        store.put("membership", "ready.1.0", b"{}")
        driver.poll()
        assert driver._stable and driver.failed_reason is None
    finally:
        for hb in hbs:
            hb.stop()
        driver.shutdown()
        standby.stop()
        http_client._active_target.clear()


def test_primary_restart_recovers_journal_and_keeps_fence(tmp_path):
    """A restarted primary replays its own journal BEFORE serving, so
    its store (and the epoch the fence compares against) survives the
    restart — a resurrected stale incarnation cannot start from an
    empty store and accept a regressed commit."""
    jp = str(tmp_path / "rdv.journal")
    first = RendezvousServer(secret=SECRET, journal_path=jp)
    first.start()
    first.put("membership", "epoch",
              json.dumps({"epoch": 7, "world": ["0"]}).encode())
    first.put("autotune", "plan.1", b"p")
    first.stop()
    second = RendezvousServer(secret=SECRET, journal_path=jp)
    second.start()
    try:
        assert json.loads(second.get("membership", "epoch"))["epoch"] == 7
        assert second.get("autotune", "plan.1") == b"p"
        with pytest.raises(EpochFencedError):
            second.put("membership", "epoch",
                       json.dumps({"epoch": 3}).encode())
    finally:
        second.stop()


def test_journal_replay_fences_regressed_epochs(tmp_path):
    """Even a journal POISONED with a regressed epoch record (written
    by a stale incarnation) cannot roll a replaying store back."""
    import base64

    jp = str(tmp_path / "j")
    with open(jp, "w") as f:
        for epoch in (5, 2):  # the 2 is the stale writer's record
            f.write(json.dumps({
                "op": "put", "p": "/membership/epoch",
                "v": base64.b64encode(
                    json.dumps({"epoch": epoch}).encode()).decode(),
            }) + "\n")
    store = ShardedKVStore(shards=2)
    replay(jp, store)
    assert json.loads(store.get("/membership/epoch"))["epoch"] == 5


def test_epoch_fence_survives_concurrent_writers(server):
    """The check-then-put is atomic: racing writers (live driver vs a
    partitioned stale one) can only move the epoch forward."""
    epochs = list(range(1, 21)) * 2
    import random as _random

    _random.shuffle(epochs)

    def write(e):
        try:
            server.put("membership", "epoch",
                       json.dumps({"epoch": e}).encode())
        except EpochFencedError:
            pass

    threads = [threading.Thread(target=write, args=(e,)) for e in epochs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert json.loads(server.get("membership", "epoch"))["epoch"] == 20


# -- heartbeat piggyback -----------------------------------------------------
def test_heartbeat_beat_is_one_round_trip(server):
    hb = HeartbeatThread(0, 2, "127.0.0.1", server.port, secret=SECRET,
                         interval=60.0)
    before = server.requests_served
    hb.beat()
    assert server.requests_served - before == 1
    assert hb.beats == 1 and hb.abort_info is None
    assert "0" in server.health_report()["ranks"]


def test_heartbeat_abort_latency_within_two_intervals(server):
    interval = 0.5
    hb = HeartbeatThread(0, 2, "127.0.0.1", server.port, secret=SECRET,
                         interval=interval)
    hb.start()
    try:
        time.sleep(interval / 2)  # between beats
        t0 = time.monotonic()
        server.put("abort", "flag", json.dumps(
            {"reason": "die", "source": "test"}).encode())
        while hb.abort_info is None \
                and time.monotonic() - t0 < 4 * interval:
            time.sleep(0.01)
        elapsed = time.monotonic() - t0
        assert hb.abort_info is not None
        assert elapsed <= 2 * interval, (
            f"abort observed after {elapsed:.2f}s > 2x{interval}s interval")
    finally:
        hb.stop()


def test_heartbeat_epoch_filter_still_applies_to_piggyback(server):
    hb = HeartbeatThread(0, 2, "127.0.0.1", server.port, secret=SECRET,
                         interval=60.0, epoch=5)
    server.put("abort", "flag", json.dumps(
        {"reason": "old", "epoch": 4}).encode())
    hb.beat()
    assert hb.abort_info is None  # stale epoch ignored
    server.put("abort", "flag", json.dumps(
        {"reason": "now", "epoch": 5}).encode())
    hb.beat()
    assert hb.abort_info is not None


# -- per-host relay ----------------------------------------------------------
def test_relay_aggregates_and_coalesces(server):
    daemon = relay_mod.RelayDaemon("127.0.0.1", server.port, secret=SECRET,
                                   flush_ms=10_000)  # manual flush
    rport = daemon.start()
    try:
        # two renewals of the SAME key coalesce; distinct keys batch
        for count in (0, 1):
            http_client.put_kv_reply(
                "127.0.0.1", rport, "health", "0",
                json.dumps({"interval": 1, "count": count}).encode(),
                secret=SECRET)
        http_client.put_kv("127.0.0.1", rport, "metrics", "0", b"{}",
                           secret=SECRET)
        assert daemon.pending() == 2
        before = server.requests_served
        assert daemon.flush_now()
        assert server.requests_served - before == 1  # ONE upstream PUT
        assert json.loads(server.get("health", "0"))["count"] == 1
        assert server.get("metrics", "0") == b"{}"
        # non-batch scopes pass through synchronously
        http_client.put_kv("127.0.0.1", rport, "membership", "ready.0.w",
                           b"1", secret=SECRET)
        assert server.get("membership", "ready.0.w") == b"1"
        # GETs are proxied
        assert http_client.get_kv("127.0.0.1", rport, "membership",
                                  "ready.0.w", secret=SECRET) == b"1"
    finally:
        daemon.stop()


def test_relay_serves_cached_abort_on_renewal(server):
    daemon = relay_mod.RelayDaemon("127.0.0.1", server.port, secret=SECRET,
                                   flush_ms=10_000)
    rport = daemon.start()
    try:
        server.put("abort", "flag", json.dumps({"reason": "r"}).encode())
        reply = http_client.put_kv_reply("127.0.0.1", rport, "health", "0",
                                         b"{}", secret=SECRET)
        assert reply["abort"] is None  # cache not refreshed yet
        daemon.flush_now()
        reply = http_client.put_kv_reply("127.0.0.1", rport, "health", "0",
                                         b"{}", secret=SECRET)
        assert reply["abort"]["reason"] == "r"
    finally:
        daemon.stop()


def test_relay_flush_failure_keeps_entries(server):
    daemon = relay_mod.RelayDaemon("127.0.0.1", server.port, secret=SECRET,
                                   flush_ms=10_000)
    daemon.start()
    try:
        daemon.buffer("/health/0", b"old")
        port = server.port
        server.stop()
        assert not daemon.flush_now()
        assert daemon.pending() == 1 and daemon.flush_errors == 1
        # a newer value arriving during the outage must not be clobbered
        daemon.buffer("/health/0", b"new")
        revived = RendezvousServer(secret=SECRET, port=port)
        revived.start()
        try:
            assert daemon.flush_now()
            assert revived.get("health", "0") == b"new"
        finally:
            revived.stop()
    finally:
        daemon.stop()


def test_relay_election_and_fallback(server, monkeypatch):
    monkeypatch.setenv(env_util.HVD_RELAY, "1")
    monkeypatch.setenv(env_util.HVD_METRICS_KV_ADDR, "127.0.0.1")
    monkeypatch.setenv(env_util.HVD_METRICS_KV_PORT, str(server.port))
    monkeypatch.setenv(env_util.HVD_METRICS_SECRET, SECRET.hex())
    monkeypatch.setenv(env_util.HVD_LOCAL_RANK, "1")
    assert relay_mod.start_from_env() is None  # only local rank 0 elects
    monkeypatch.setenv(env_util.HVD_LOCAL_RANK, "0")
    daemon = relay_mod.start_from_env()
    assert daemon is not None
    try:
        # the published address resolves for local peers
        rec = json.loads(server.get("relay", relay_mod.host_slug()))
        assert rec["port"] == daemon.port
        ep = relay_mod.control_endpoint()
        assert ep == ("127.0.0.1", daemon.port, True)
        # a heartbeat through the relay falls back when the relay dies
        hb = HeartbeatThread(0, 2, "127.0.0.1", server.port, secret=SECRET,
                             interval=60.0)
        daemon.stop()
        hb.beat()
        assert hb.beats == 1  # renewed via the direct fallback
        assert "0" in server.health_report()["ranks"]
        assert relay_mod.control_endpoint()[2] is False
    finally:
        relay_mod.stop()


def test_relay_routed_heartbeat_observes_abort(server):
    """The full relay path: renewals buffered at the relay, abort set
    upstream, verdict reaches the rank via flush-refreshed cache within
    2 intervals + a couple of flushes."""
    daemon = relay_mod.RelayDaemon("127.0.0.1", server.port, secret=SECRET,
                                   flush_ms=100)
    rport = daemon.start()
    interval = 0.4
    hb = HeartbeatThread(0, 2, "127.0.0.1", rport, secret=SECRET,
                         interval=interval)
    hb.start()
    try:
        time.sleep(interval / 2)
        t0 = time.monotonic()
        server.put("abort", "flag", json.dumps(
            {"reason": "die", "source": "test"}).encode())
        while hb.abort_info is None \
                and time.monotonic() - t0 < 3 * interval + 1.0:
            time.sleep(0.01)
        elapsed = time.monotonic() - t0
        assert hb.abort_info is not None
        assert elapsed <= 2 * interval + 0.5
    finally:
        hb.stop()
        daemon.stop()


def test_events_flush_survives_relay_death_no_loss_no_dup(server,
                                                          monkeypatch):
    """Flight-recorder pushes ride the relay batch path (events is a
    BATCH_SCOPE); when the relay dies mid-run the flusher must fall
    back to the primary permanently with every event delivered exactly
    once — an event key is unique, so a duplicate would surface as a
    second record and a loss as a missing one."""
    from horovod_tpu.observe import events as events_mod

    monkeypatch.setenv(env_util.HVD_METRICS_KV_ADDR, "127.0.0.1")
    monkeypatch.setenv(env_util.HVD_METRICS_KV_PORT, str(server.port))
    daemon = relay_mod.RelayDaemon("127.0.0.1", server.port, secret=SECRET,
                                   flush_ms=50)
    rport = daemon.start()
    relay_mod._endpoint = ("127.0.0.1", rport, True)
    rec = events_mod.Recorder(cap=64)
    flusher = events_mod.EventFlusher(rec, "127.0.0.1", server.port,
                                      secret=SECRET, interval=3600.0)
    e1 = rec.record("epoch.commit", payload={"epoch": 0})
    try:
        assert flusher.flush_now()
        # e1 went via the relay loopback; its flush thread lands it
        assert _wait_for(
            lambda: server.get(events_mod.EVENTS_SCOPE, e1) is not None)
    finally:
        daemon.stop()
    e2 = rec.record("epoch.commit", payload={"epoch": 1}, cause_id=e1)
    assert flusher.flush_now()                  # silent direct fallback
    assert relay_mod.control_endpoint()[2] is False
    report = server.events_report()
    assert [e["id"] for e in report["events"]] == [e1, e2]
    assert rec.pending() == 0 and rec.dropped == 0
    # and the fallback is PERMANENT: the next flush goes direct too
    e3 = rec.record("epoch.commit", payload={"epoch": 2})
    assert flusher.flush_now()
    assert [e["id"] for e in server.events_report()["events"]] == \
        [e1, e2, e3]


def test_alerts_push_survives_relay_death(server, monkeypatch):
    """The watchdog's alert pushes take the same control_put road: a
    dead relay must not eat an alert (ids are unique, so loss —
    not coalescing — is the failure mode)."""
    monkeypatch.setenv(env_util.HVD_METRICS_KV_ADDR, "127.0.0.1")
    monkeypatch.setenv(env_util.HVD_METRICS_KV_PORT, str(server.port))
    daemon = relay_mod.RelayDaemon("127.0.0.1", server.port, secret=SECRET,
                                   flush_ms=50)
    rport = daemon.start()
    relay_mod._endpoint = ("127.0.0.1", rport, True)
    relay_mod.control_put("127.0.0.1", server.port, "alerts", "0",
                          json.dumps({"id": "0", "signal": "step_time_regression",
                                      "severity": "warning"}).encode(),
                          secret=SECRET)
    assert _wait_for(lambda: server.get("alerts", "0") is not None)
    daemon.stop()
    relay_mod.control_put("127.0.0.1", server.port, "alerts", "1",
                          json.dumps({"id": "1", "signal": "slo_burn",
                                      "severity": "critical"}).encode(),
                          secret=SECRET)
    assert relay_mod.control_endpoint()[2] is False
    assert server.get("alerts", "1") is not None  # direct fallback
    report = http_client.get_alerts("127.0.0.1", server.port,
                                    secret=SECRET)
    assert {a["id"] for a in report["alerts"]} == {"0", "1"}


# -- metrics delta pushes ----------------------------------------------------
def _pusher_for(server, rank=0):
    from horovod_tpu.metrics.push import MetricsPusher

    return MetricsPusher("127.0.0.1", server.port, rank, SECRET, 60.0)


def test_metrics_delta_push_shrinks_bytes_on_wire(server):
    pusher = _pusher_for(server)
    assert pusher.push()
    full_bytes = pusher.last_push_bytes
    assert pusher.full_pushes == 1
    metrics.HEARTBEATS.inc()  # exactly one family changes
    assert pusher.push()
    assert pusher.delta_pushes == 1
    # the bytes-on-wire pin: one changed family costs a fraction of the
    # full snapshot (the registry has 100+ families)
    assert pusher.last_push_bytes < full_bytes / 4, (
        pusher.last_push_bytes, full_bytes)
    # server-side merge: the stored snapshot stays FULL and current
    stored = json.loads(server.get("metrics", "0"))
    assert stored["metrics"]["hvd_heartbeats_total"] is not None
    assert len(stored["metrics"]) >= 50  # unchanged families survived


def test_metrics_delta_merge_updates_value(server):
    pusher = _pusher_for(server)
    pusher.push()
    before = metrics.HEARTBEATS.get()
    metrics.HEARTBEATS.inc(3)
    pusher.push()
    stored = json.loads(server.get("metrics", "0"))
    fam = stored["metrics"]["hvd_heartbeats_total"]
    assert fam["samples"][0]["value"] == before + 3


def test_metrics_delta_resyncs_after_failover(server):
    pusher = _pusher_for(server)
    pusher.push()
    metrics.HEARTBEATS.inc()
    # the server "fails over": a different incarnation answers
    standby = RendezvousServer(secret=SECRET)
    standby.start()
    try:
        pusher.addr, pusher.port = "127.0.0.1", standby.port
        assert pusher.push()
        assert pusher.resyncs == 1
        assert pusher.full_pushes == 2  # the resync was a full snapshot
        assert standby.get("metrics", "0") is not None
    finally:
        standby.stop()


def test_metrics_delta_disabled_by_knob(server, monkeypatch):
    monkeypatch.setenv(env_util.HVD_METRICS_DELTA, "0")
    pusher = _pusher_for(server)
    pusher.push()
    metrics.HEARTBEATS.inc()
    pusher.push()
    assert pusher.delta_pushes == 0 and pusher.full_pushes == 2


def test_metrics_pusher_falls_back_from_dead_relay(server, monkeypatch):
    """A dead relay must degrade the pusher to direct per-rank pushes
    (the shared control_put fallback), never silence it."""
    import socket as _socket

    with _socket.socket() as s:
        s.bind(("", 0))
        dead_port = s.getsockname()[1]
    monkeypatch.setenv(env_util.HVD_METRICS_KV_ADDR, "127.0.0.1")
    monkeypatch.setenv(env_util.HVD_METRICS_KV_PORT, str(server.port))
    relay_mod._endpoint = ("127.0.0.1", dead_port, True)
    pusher = _pusher_for(server)
    assert pusher.push()
    assert server.get("metrics", "0") is not None
    assert relay_mod.control_endpoint()[2] is False  # marked failed


def test_sanitizer_cache_prune_keeps_newest_per_stream():
    """Pruning follows the peers' GC window per (group, epoch, rank)
    stream and never evicts a stream's newest fingerprint — the bug
    class where a full resync over a big world evicted a quiet peer's
    current entry and manufactured a false silent-peer divergence."""
    from horovod_tpu.analysis import sanitizer as san_mod
    from horovod_tpu.analysis.sanitizer import Sanitizer

    s = Sanitizer(0, 2, "127.0.0.1", 1, secret=None)
    for seq in range(200):
        s._scope_cache[f"world.0.{seq}.1"] = {"seq": seq}
    s._scope_cache["slow_group.0.0.1"] = {"seq": 0}  # quiet peer stream
    s._prune_cache()
    assert "world.0.199.1" in s._scope_cache
    assert "slow_group.0.0.1" in s._scope_cache  # newest of its stream
    assert f"world.0.{199 - san_mod.GC_WINDOW - 1}.1" not in s._scope_cache
    assert f"world.0.{199 - san_mod.GC_WINDOW}.1" in s._scope_cache


# -- sanitizer batched reads -------------------------------------------------
def test_sanitizer_check_uses_batched_scope_reads(server):
    """A 4-rank world's check round costs each rank O(1) scope reads,
    not one GET per peer (the O(ranks x groups) reduction)."""
    from horovod_tpu.analysis.sanitizer import Sanitizer

    sans = [Sanitizer(r, 4, "127.0.0.1", server.port, secret=SECRET,
                      timeout=10.0) for r in range(4)]
    results = [None] * 4

    def go(i):
        try:
            results[i] = sans[i].check(op="allreduce", name="g", shape=(4,),
                                       dtype="float32")
        except Exception as e:  # noqa: BLE001
            results[i] = e

    before = server.requests_served
    threads = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    assert results == [0, 0, 0, 0]
    spent = server.requests_served - before
    # 4 publishes + a few scope polls; the old per-peer protocol needed
    # >= 4 publishes + 12 peer GETs even in the zero-wait best case
    assert spent < 16, spent


# -- churn bench fixture -----------------------------------------------------
def test_control_plane_bench_check_passes():
    """Tier-1 wiring for the churn harness: the small-world fixture
    must clear the >=5x reduction and latency bars."""
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "control_plane_bench.py")
    p = subprocess.run([sys.executable, script, "--check"],
                       capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "CONTROL PLANE BENCH CHECK PASSED" in p.stdout
