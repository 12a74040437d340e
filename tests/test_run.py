"""Launcher tests without a cluster — modeled on reference test/test_run.py:
arg/env translation (:68-176), YAML config override (:176-233), command-line
string assertions with no execution (:259-362), plus live KV-store and
local-spawn integration (reference test_interactiverun.py launches real
2-proc jobs in-process)."""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.run.config_parser import env_from_args
from horovod_tpu.run.hosts import (
    HostInfo, allocate_slots, parse_hostfile, parse_hosts,
)
from horovod_tpu.run.http_client import delete_scope, get_kv, put_kv
from horovod_tpu.run.http_server import RendezvousServer
from horovod_tpu.run.run import parse_args, ssh_command, worker_envs


# -- host parsing -----------------------------------------------------------
def test_parse_hosts():
    hosts = parse_hosts("h1:4,h2:8,h3")
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("h1", 4), ("h2", 8), ("h3", 1),
    ]


def test_parse_hostfile(tmp_path):
    p = tmp_path / "hosts"
    p.write_text("h1 slots=2\n# comment\nh2 slots=4\nh3\n")
    hosts = parse_hostfile(str(p))
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("h1", 2), ("h2", 4), ("h3", 1),
    ]


def test_allocate_slots_ranks():
    slots = allocate_slots(parse_hosts("a:2,b:2"), 4)
    assert [(s.rank, s.hostname, s.local_rank, s.cross_rank)
            for s in slots] == [
        (0, "a", 0, 0), (1, "a", 1, 0), (2, "b", 0, 1), (3, "b", 1, 1),
    ]
    assert all(s.size == 4 and s.local_size == 2 and s.cross_size == 2
               for s in slots)


def test_allocate_slots_partial_last_host():
    slots = allocate_slots(parse_hosts("a:4,b:4"), 6)
    assert len(slots) == 6
    assert slots[-1].hostname == "b"
    assert slots[-1].local_size == 2
    # cross sizes differ by column: local ranks 0,1 exist on both hosts;
    # 2,3 only on a
    assert slots[2].cross_size == 1  # a local_rank=2
    assert slots[4].cross_size == 2  # b local_rank=0


def test_allocate_too_many_raises():
    with pytest.raises(ValueError):
        allocate_slots([HostInfo("a", 2)], 3)


# -- arg/env translation (reference test_run.py:68-176) ---------------------
def test_env_from_args_all_groups():
    args = parse_args([
        "-np", "8",
        "--fusion-threshold-mb", "32",
        "--cycle-time-ms", "3.5",
        "--cache-capacity", "2048",
        "--hierarchical-allreduce",
        "--autotune", "--autotune-log-file", "/tmp/at.csv",
        "--autotune-warmup-samples", "5",
        "--timeline-filename", "/tmp/tl",
        "--timeline-mark-cycles",
        "--trace-start-step", "10", "--trace-end-step", "20",
        "--no-stall-check",
        "--log-level", "debug",
        "python", "train.py",
    ])
    env = env_from_args(args)
    assert env["HVD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HVD_CYCLE_TIME"] == "3.5"
    assert env["HVD_CACHE_CAPACITY"] == "2048"
    assert env["HVD_HIERARCHICAL_ALLREDUCE"] == "1"
    assert env["HVD_AUTOTUNE"] == "1"
    assert env["HVD_AUTOTUNE_LOG"] == "/tmp/at.csv"
    assert env["HVD_AUTOTUNE_WARMUP_SAMPLES"] == "5"
    assert env["HVD_TIMELINE"] == "/tmp/tl"
    assert env["HVD_TIMELINE_MARK_CYCLES"] == "1"
    assert env["HVD_TRACE_START_STEP"] == "10"
    assert env["HVD_TRACE_END_STEP"] == "20"
    assert env["HVD_STALL_CHECK_DISABLE"] == "1"
    assert env["HVD_LOG_LEVEL"] == "debug"
    assert args.command == ["python", "train.py"]


def test_stall_check_seconds():
    args = parse_args([
        "-np", "2",
        "--stall-check-warning-time-seconds", "120",
        "--stall-check-shutdown-time-seconds", "300",
        "cmd",
    ])
    env = env_from_args(args)
    assert env["HVD_STALL_CHECK_TIME_SECONDS"] == "120"
    assert env["HVD_STALL_SHUTDOWN_TIME_SECONDS"] == "300"


# -- YAML config override (reference test_run.py:176-233) --------------------
def test_yaml_config_override(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(textwrap.dedent("""
        params:
          fusion_threshold_mb: 16
          cycle_time_ms: 2.5
          ring_min_bytes: 65536
        autotune:
          enabled: true
          warmup_samples: 7
        timeline:
          filename: /tmp/yaml_tl
        logging:
          level: info
    """))
    args = parse_args(["-np", "2", "--config-file", str(cfg), "cmd"])
    env = env_from_args(args)
    assert env["HVD_FUSION_THRESHOLD"] == str(16 * 1024 * 1024)
    assert env["HVD_CYCLE_TIME"] == "2.5"
    assert env["HVD_RING_MIN_BYTES"] == "65536"
    assert env["HVD_AUTOTUNE"] == "1"
    assert env["HVD_AUTOTUNE_WARMUP_SAMPLES"] == "7"
    assert env["HVD_TIMELINE"] == "/tmp/yaml_tl"
    assert env["HVD_LOG_LEVEL"] == "info"


def test_yaml_does_not_override_explicit_cli(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("params:\n  cycle_time_ms: 2.5\n")
    args = parse_args([
        "-np", "2", "--cycle-time-ms", "9.0",
        "--config-file", str(cfg), "cmd",
    ])
    assert env_from_args(args)["HVD_CYCLE_TIME"] == "9.0"


# -- worker env + ssh command strings (reference test_run.py:259-362) --------
def test_worker_envs_per_host():
    slots = allocate_slots(parse_hosts("h1:4,h2:4"), 8)
    envs = worker_envs(slots, {"HVD_LOG_LEVEL": "info"}, "coord:1234")
    assert len(envs) == 2
    e0, e1 = envs
    assert e0["HVD_RANK"] == "0" and e1["HVD_RANK"] == "4"
    assert e0["HVD_SIZE"] == e1["HVD_SIZE"] == "8"
    assert e0["HVD_LOCAL_SIZE"] == "4"
    assert e0["HVD_NUM_PROCESSES"] == "2"
    assert e0["HVD_PROCESS_ID"] == "0" and e1["HVD_PROCESS_ID"] == "1"
    assert e0["HVD_COORDINATOR_ADDR"] == "coord:1234"
    assert e0["HVD_LOG_LEVEL"] == "info"
    # multi-process jobs get the native eager controller by default
    # (reference always stands its controller up, operations.cc:596-640)
    assert e0["HVD_CONTROLLER"] == "native"


def test_worker_envs_controller_selection():
    slots = allocate_slots(parse_hosts("h1:4,h2:4"), 8)
    envs = worker_envs(slots, {}, "coord:1", controller="native",
                       controller_addr="h1:9999")
    assert all(e["HVD_CONTROLLER"] == "native" for e in envs)
    assert all(e["HVD_CONTROLLER_ADDR"] == "h1:9999" for e in envs)
    # each worker's ring listener is addressed by its launcher-known host
    assert [e["HVD_RING_HOST"] for e in envs] == ["h1", "h2"]
    envs = worker_envs(slots, {}, "coord:1", controller="xla")
    assert all(e["HVD_CONTROLLER"] == "xla" for e in envs)
    assert all("HVD_CONTROLLER_ADDR" not in e for e in envs)
    # single host auto-selects xla
    slots1 = allocate_slots(parse_hosts("localhost:8"), 8)
    envs = worker_envs(slots1, {}, "coord:1")
    assert envs[0]["HVD_CONTROLLER"] == "xla"


def test_single_host_no_coordinator():
    slots = allocate_slots(parse_hosts("localhost:8"), 8)
    envs = worker_envs(slots, {}, "coord:1")
    assert len(envs) == 1
    assert "HVD_COORDINATOR_ADDR" not in envs[0]


def test_ssh_command_string():
    cmd = ssh_command(
        "worker1", {"HVD_RANK": "1", "HVD_SIZE": "2"},
        ["python", "train.py", "--lr", "0.1"],
        ssh_port=2222, cwd="/job",
    )
    assert cmd.startswith(
        "ssh -o PasswordAuthentication=no -o StrictHostKeyChecking=no "
        "-p 2222 worker1 "
    )
    assert "HVD_RANK=1" in cmd and "HVD_SIZE=2" in cmd
    assert "cd /job" in cmd
    assert "python train.py --lr 0.1" in cmd


# -- live KV store ----------------------------------------------------------
def test_kvstore_roundtrip_and_auth():
    secret = b"s3cret"
    server = RendezvousServer(secret=secret)
    port = server.start()
    try:
        put_kv("127.0.0.1", port, "scope", "k", b"hello", secret=secret)
        assert get_kv("127.0.0.1", port, "scope", "k", secret=secret) == b"hello"
        assert get_kv("127.0.0.1", port, "scope", "missing",
                      secret=secret) is None
        # wrong secret rejected
        import urllib.error

        with pytest.raises(urllib.error.HTTPError):
            put_kv("127.0.0.1", port, "scope", "k", b"x", secret=b"wrong")
        delete_scope("127.0.0.1", port, "scope", secret=secret)
        assert get_kv("127.0.0.1", port, "scope", "k", secret=secret) is None
    finally:
        server.stop()


# -- real local launches ----------------------------------------------------
def test_tpurun_local_launch(tmp_path):
    """End-to-end: tpurun spawns a local worker with the right env."""
    from horovod_tpu.run.run import run_commandline

    marker = tmp_path / "out.txt"
    script = (
        "import os;"
        "open(r'%s','w').write("
        "os.environ['HVD_RANK']+','+os.environ['HVD_SIZE']+','"
        "+os.environ['HVD_LOCAL_SIZE'])" % marker
    )
    rc = run_commandline([
        "-np", "4", "-H", "localhost:4",
        "--output-filename", str(tmp_path / "logs"),
        sys.executable, "-c", script,
    ])
    assert rc == 0
    assert marker.read_text() == "0,4,4"
    assert (tmp_path / "logs" / "rank.0.txt").exists()


def test_tpurun_failure_propagates(tmp_path):
    from horovod_tpu.run.run import run_commandline

    rc = run_commandline([
        "-np", "1", "-H", "localhost:1",
        sys.executable, "-c", "import sys; sys.exit(3)",
    ])
    assert rc == 3


def test_function_mode_run():
    # note: `import horovod_tpu.run.run as x` would bind the FUNCTION
    # (the package __init__ re-exports `run` over the submodule
    # attribute, exactly like reference horovod/run/__init__.py); load
    # the module through sys.modules semantics instead
    import importlib

    tpurun = importlib.import_module("horovod_tpu.run.run")

    def fn(a, b):
        import os

        return a + b + int(os.environ["HVD_RANK"])

    results = tpurun.run(fn, args=(10, 20), np=2)
    assert results == [30, 31]


def test_function_mode_children_do_not_share_the_chip(monkeypatch):
    """A chip belongs to one process and function mode assigns none:
    with np > 1 every worker is pinned to the host platform, whatever
    the parent ran on; a single worker inherits the parent's platform
    (it owns all local chips); a caller that places the workers itself
    is obeyed."""
    import importlib

    tpurun = importlib.import_module("horovod_tpu.run.run")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    for pid in range(2):
        env = tpurun.function_mode_env(pid, 2, 1234, b"s", {})
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["HVD_PROCESS_ID"] == str(pid)
        assert env["HVD_NUM_PROCESSES"] == "2"
    assert tpurun.function_mode_env(
        0, 1, 1234, b"s", {})["JAX_PLATFORMS"] == "tpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in tpurun.function_mode_env(
        0, 1, 1234, b"s", {})
    placed = tpurun.function_mode_env(
        1, 2, 1234, b"s", {"JAX_PLATFORMS": "tpu"})
    assert placed["JAX_PLATFORMS"] == "tpu"


def test_tpu_host_discovery_env_override(monkeypatch):
    """--tpu resolves hosts from HVD_TPU_HOSTS / TPU_WORKER_HOSTNAMES
    (SURVEY §7.1's replacement for the reference's ssh/NIC probing)."""
    from horovod_tpu.run.discovery import discover_tpu_hosts

    monkeypatch.setenv("HVD_TPU_HOSTS", "podhost-0:4,podhost-1:4")
    hosts = discover_tpu_hosts()
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("podhost-0", 4), ("podhost-1", 4)]

    monkeypatch.delenv("HVD_TPU_HOSTS")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1,w2")
    hosts = discover_tpu_hosts(default_slots=8)
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("w0", 8), ("w1", 8), ("w2", 8)]


def test_tpu_host_discovery_metadata(monkeypatch):
    from horovod_tpu.run import discovery

    monkeypatch.delenv("HVD_TPU_HOSTS", raising=False)
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    # real worker-network-endpoints entries carry the worker IP in the
    # last :-field (jax cloud_tpu_cluster parses worker.split(':')[2])
    monkeypatch.setattr(
        discovery, "_metadata_endpoints",
        lambda timeout=2.0: "0:worker-0:10.0.0.2,1:worker-1:10.0.0.3",
    )
    hosts = discovery.discover_tpu_hosts(default_slots=4)
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("10.0.0.2", 4), ("10.0.0.3", 4)]


def test_tpu_host_discovery_http_metadata_server(monkeypatch):
    """All three sources end-to-end with a REAL mocked GCE metadata
    endpoint: the HTTP fetch (incl. the Metadata-Flavor header contract)
    and the HVD_TPU_HOSTS > TPU_WORKER_HOSTNAMES > metadata precedence
    (reference run/run.py:62-115 tests its host checks similarly)."""
    import http.server
    import threading

    from horovod_tpu.run import discovery

    seen_headers = {}

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            seen_headers.update(self.headers)
            body = b"0:w0:10.9.0.2,1:w1:10.9.0.3"
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        monkeypatch.setattr(
            discovery, "_METADATA_URL",
            f"http://127.0.0.1:{srv.server_port}/attr",
        )
        monkeypatch.delenv("HVD_TPU_HOSTS", raising=False)
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)

        hosts = discovery.discover_tpu_hosts(default_slots=4)
        assert [(h.hostname, h.slots) for h in hosts] == [
            ("10.9.0.2", 4), ("10.9.0.3", 4)]
        assert seen_headers.get("Metadata-Flavor") == "Google"

        # precedence: the worker-hostnames env beats the metadata server
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "w0,w1")
        hosts = discovery.discover_tpu_hosts(default_slots=2)
        assert [(h.hostname, h.slots) for h in hosts] == [
            ("w0", 2), ("w1", 2)]

        # ...and the explicit override beats both
        monkeypatch.setenv("HVD_TPU_HOSTS", "explicit-0:8")
        hosts = discovery.discover_tpu_hosts()
        assert [(h.hostname, h.slots) for h in hosts] == [("explicit-0", 8)]
    finally:
        srv.shutdown()
        thread.join(timeout=5)


def test_tpu_flag_resolves_hosts(monkeypatch):
    from horovod_tpu.run.run import _resolve_hosts, parse_args

    monkeypatch.setenv("HVD_TPU_HOSTS", "pod-a:8,pod-b:8")
    args = parse_args(["--tpu", "python", "train.py"])
    hosts = _resolve_hosts(args)
    assert [(h.hostname, h.slots) for h in hosts] == [
        ("pod-a", 8), ("pod-b", 8)]


def test_check_build_report():
    """tpurun --check-build prints the availability matrix and exits 0
    (reference run/run.py:289-324 check_build)."""
    import contextlib
    import io

    from horovod_tpu.run.run import check_build, run_commandline

    report = check_build()
    assert "Available Frameworks" in report
    assert "[X] JAX / flax" in report
    assert "PyTorch" in report and "MXNet" in report and "Spark" in report
    assert "Available Controllers" in report
    assert "native (C++ TCP negotiation" in report
    assert "XLA collectives (ICI/DCN)" in report

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_commandline(["--check-build"])
    assert rc == 0
    assert "Available Frameworks" in buf.getvalue()


def test_network_interface_flag_and_resolution(monkeypatch):
    """--network-interface reaches workers as HVD_NETWORK_INTERFACE and
    each worker resolves the first live NIC locally (reference
    --network-interface; loopback is always resolvable in CI)."""
    from horovod_tpu.run import config_parser
    from horovod_tpu.run.run import parse_args
    from horovod_tpu.runtime.ring import _iface_ip

    args = parse_args(["--network-interface", "eth0,lo",
                       "-np", "2", "python", "x.py"])
    env = config_parser.env_from_args(args)
    assert env["HVD_NETWORK_INTERFACE"] == "eth0,lo"

    assert _iface_ip("lo") == "127.0.0.1"
    assert _iface_ip("definitely-not-a-nic") is None
    # the comma list takes the first interface that resolves
    assert _iface_ip("definitely-not-a-nic,lo") == "127.0.0.1"


def test_unresolvable_mandated_nic_raises(monkeypatch):
    """A --network-interface list that resolves on no NIC must FAIL the
    launch, not silently advertise another interface (reference errors
    on an absent GLOO_IFACE/NCCL_SOCKET_IFNAME the same way)."""
    import pytest as _pytest

    from horovod_tpu.runtime import ring as ring_mod

    monkeypatch.setenv("HVD_NETWORK_INTERFACE", "definitely-not-a-nic")
    with _pytest.raises(RuntimeError, match="network-interface"):
        ring_mod.establish(None, 0, 2)


def test_package_level_run_export():
    """from horovod_tpu.run import run — the reference's import path
    (reference horovod/run/__init__.py:16)."""
    from horovod_tpu.run import run as fn
    from horovod_tpu.run.run import run as fn_module_path

    assert fn is fn_module_path


def test_ring_min_bytes_flag_and_env():
    """--ring-min-bytes reaches workers as HVD_RING_MIN_BYTES, and the
    eager transport reads it (the ring/star crossover is fabric-specific:
    calibrate with scripts/host_plane_bench.py --crossover)."""
    import subprocess
    import sys

    from horovod_tpu.run.config_parser import env_from_args
    from horovod_tpu.run.run import parse_args

    args = parse_args(["--ring-min-bytes", "131072", "-np", "2", "cmd"])
    assert env_from_args(args)["HVD_RING_MIN_BYTES"] == "131072"

    # the runtime honors the env override (read at import)
    import os

    env = dict(os.environ)
    env["HVD_RING_MIN_BYTES"] = "12345"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "from horovod_tpu import eager; print(eager._RING_MIN_BYTES)"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert out.stdout.strip() == "12345", out.stderr[-500:]
