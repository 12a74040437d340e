"""bench.py — the driver-benchmark contract.

No TPU, or a failed measurement child, is a non-zero exit with the
reason on stderr: never a zero-valued line on rc 0, never a CPU number
under the per-chip metric's name.  The result line names the device it
ran on.  The parent stays off JAX so each child gets the chip."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_exits_nonzero_with_reason():
    """The real thing against this host's CPU backend: `python bench.py`
    exits non-zero, prints no result line, and says which platform it
    found."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr and "'cpu'" in p.stderr


def test_child_refuses_cpu_in_process():
    """The measurement child's own guard (no probe process in front of
    it any more) names the platform it found."""
    bench = _load_bench()
    with pytest.raises(RuntimeError, match="no TPU found.*'cpu'"):
        bench._require_tpu()


def test_failed_child_is_a_nonzero_exit(monkeypatch, capsys):
    """A hung measurement child: one attempt, no retry, no
    `"value": 0.0` line — rc 1 and the reason."""
    bench = _load_bench()
    calls = []

    def raise_timeout(cmd, *a, **k):
        calls.append(cmd)
        raise bench.subprocess.TimeoutExpired(cmd="x", timeout=1)

    monkeypatch.setattr(bench.subprocess, "run", raise_timeout)
    assert bench.main() == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "timeout" in captured.err
    assert len(calls) == 1


def test_result_line_names_the_device(monkeypatch, capsys):
    """The measurement child's RESULT line carries platform, device_kind
    and device_count as JAX reported them, next to the metric."""
    import examples.synthetic_benchmark as synth

    bench = _load_bench()
    device = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_count": 1}
    monkeypatch.setattr(bench, "_require_tpu", lambda: device)
    monkeypatch.setattr(synth, "run",
                        lambda args: {"img_sec_per_chip": 2700.0})
    monkeypatch.setenv("HVD_PEAK_FLOPS", "197e12")
    bench._measure()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("RESULT ")
    out = json.loads(line[len("RESULT "):])
    assert out["value"] == 2700.0
    assert {k: out[k] for k in device} == device
    assert out["mfu"] == pytest.approx(2700.0 * 24.30e9 / 197e12, abs=1e-4)


def test_parent_imports_start_no_backend():
    """bench.py's parent imports horovod_tpu (the leg switches, the MFU
    helper's module) and must stay off JAX: a process that has started a
    backend holds the chip, and every measurement child then fails or
    hangs.  A module-level jnp constant anywhere in the package would
    break this quietly."""
    code = (
        "import horovod_tpu\n"
        "from horovod_tpu.utils import env, flops\n"
        "assert flops.peak_flops('TPU v5 lite') == 197e12\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO, env=env, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_successful_run_passes_result_through(monkeypatch, capsys):
    """When the child run emits a RESULT line, main() prints exactly its
    JSON payload (the autotune tail disabled here; covered below)."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07, "platform": "tpu",
               "device_kind": "TPU v5 lite", "device_count": 1}

    class FakeProc:
        returncode = 0
        stdout = "noise\nRESULT " + json.dumps(payload) + "\n"
        stderr = ""

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench, "_compression_delta", lambda v: {})
    monkeypatch.setattr(bench, "_serving_leg", lambda: {})
    monkeypatch.setattr(bench, "_projection_leg", lambda: {})
    monkeypatch.setattr(bench, "_control_leg", lambda: {})
    monkeypatch.setattr(bench, "_watch_leg", lambda: {})
    monkeypatch.setattr(bench, "_restore_leg", lambda: {})
    monkeypatch.setattr(bench, "_chaos_leg", lambda: {})
    monkeypatch.setattr(bench.subprocess, "run",
                        lambda *a, **k: FakeProc())
    assert bench.main() == 0
    out = capsys.readouterr().out.strip()
    assert json.loads(out) == payload


def test_autotune_delta_merged_into_tail(monkeypatch, capsys):
    """The autotuned comparison leg's number lands in the JSON tail as
    autotuned_img_sec_per_chip + autotune_delta_pct (whether the loop
    moved the MFU number)."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line):
            self.returncode = 0
            self.stdout = "RESULT " + line + "\n"
            self.stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-autotune" in cmd:
            return FakeProc(json.dumps({"img_sec_per_chip": 2808.0}))
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench, "_compression_delta", lambda v: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_AUTOTUNE", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["autotuned_img_sec_per_chip"] == 2808.0
    assert out["autotune_delta_pct"] == 4.0
    assert any("--child-autotune" in c for c in calls)


def test_autotune_leg_failure_cannot_cost_the_main_number(monkeypatch,
                                                          capsys):
    """A hung autotuned leg degrades to autotune_delta_pct: None — the
    default number still publishes."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        returncode = 0
        stdout = "RESULT " + json.dumps(payload) + "\n"
        stderr = ""

    def fake_run(cmd, *a, **k):
        if "--child-autotune" in cmd:
            raise bench.subprocess.TimeoutExpired(cmd="x", timeout=1)
        return FakeProc()

    monkeypatch.setattr(bench, "_compression_delta", lambda v: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_AUTOTUNE", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["autotune_delta_pct"] is None
    assert "timeout" in out["autotune_error"]


def test_compression_delta_merged_into_tail(monkeypatch, capsys):
    """The compressed comparison leg (error-feedback int8,
    docs/compression.md) lands in the JSON tail as
    compressed_img_sec_per_chip + compression_delta_pct."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line):
            self.returncode = 0
            self.stdout = "RESULT " + line + "\n"
            self.stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-compression" in cmd:
            return FakeProc(json.dumps({"img_sec_per_chip": 2646.0}))
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_COMPRESSION", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["compressed_img_sec_per_chip"] == 2646.0
    assert out["compression_delta_pct"] == -2.0
    assert any("--child-compression" in c for c in calls)


def test_compression_leg_failure_cannot_cost_the_main_number(monkeypatch,
                                                             capsys):
    """A hung compression leg degrades to compression_delta_pct: None —
    the default number still publishes (the acceptance contract)."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        returncode = 0
        stdout = "RESULT " + json.dumps(payload) + "\n"
        stderr = ""

    def fake_run(cmd, *a, **k):
        if "--child-compression" in cmd:
            raise bench.subprocess.TimeoutExpired(cmd="x", timeout=1)
        return FakeProc()

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_COMPRESSION", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["compression_delta_pct"] is None
    assert "timeout" in out["compression_error"]


def test_compression_leg_skippable(monkeypatch, capsys):
    """HVD_BENCH_COMPRESSION=0 skips the leg entirely — no child run,
    no tail fields."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        returncode = 0
        stdout = "RESULT " + json.dumps(payload) + "\n"
        stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        return FakeProc()

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setenv("HVD_BENCH_COMPRESSION", "0")
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert "compression_delta_pct" not in out
    assert not any("--child-compression" in c for c in calls)


def test_serving_leg_merged_and_skippable(monkeypatch, capsys):
    """The serving leg (docs/inference.md) lands serve_p50_ms /
    serve_p99_ms / goodput_under_burst in the JSON tail, and
    HVD_BENCH_SERVE=0 skips it entirely — same contract as the
    autotune/compression legs."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line):
            self.returncode = 0
            self.stdout = "RESULT " + line + "\n"
            self.stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-serve" in cmd:
            return FakeProc(json.dumps(
                {"serve_p50_ms": 3.2, "serve_p99_ms": 11.5,
                 "goodput_under_burst": 0.98}))
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench, "_compression_delta", lambda v: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_SERVE", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["serve_p50_ms"] == 3.2 and out["serve_p99_ms"] == 11.5
    assert out["goodput_under_burst"] == 0.98
    assert any("--child-serve" in c for c in calls)

    # HVD_BENCH_SERVE=0: no child run, no tail fields
    calls.clear()
    monkeypatch.setenv("HVD_BENCH_SERVE", "0")
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert "serve_p50_ms" not in out
    assert not any("--child-serve" in c for c in calls)


def test_control_leg_merged_and_skippable(monkeypatch, capsys):
    """The control-plane churn leg (docs/control_plane.md) lands
    control_p99_lease_ms / control_p99_epoch_ms / control_abort_ms /
    control_request_reduction_x in the JSON tail, degrades to nulls on
    a hung child, and HVD_BENCH_CONTROL=0 skips it."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line):
            self.returncode = 0
            self.stdout = "RESULT " + line + "\n"
            self.stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-control" in cmd:
            return FakeProc(json.dumps(
                {"control_p99_lease_ms": 12.5, "control_p99_epoch_ms": 1.4,
                 "control_abort_ms": 80.0,
                 "control_request_reduction_x": 24.0}))
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench, "_autotune_delta", lambda v: {})
    monkeypatch.setattr(bench, "_compression_delta", lambda v: {})
    monkeypatch.setattr(bench, "_serving_leg", lambda: {})
    monkeypatch.setattr(bench, "_projection_leg", lambda: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_CONTROL", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["control_p99_lease_ms"] == 12.5
    assert out["control_p99_epoch_ms"] == 1.4
    assert out["control_request_reduction_x"] == 24.0
    assert any("--child-control" in c for c in calls)

    # a hung churn child degrades to nulls, never costs the main number
    def raise_for_leg(cmd, *a, **k):
        if "--child-control" in cmd:
            raise bench.subprocess.TimeoutExpired(cmd="x", timeout=1)
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench.subprocess, "run", raise_for_leg)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["control_p99_lease_ms"] is None
    assert out["control_p99_epoch_ms"] is None
    assert "timeout" in out["control_error"]

    # HVD_BENCH_CONTROL=0: no child run, no tail fields
    calls.clear()
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setenv("HVD_BENCH_CONTROL", "0")
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert "control_p99_lease_ms" not in out
    assert not any("--child-control" in c for c in calls)


def test_watch_leg_merged_and_skippable(monkeypatch, capsys):
    """The watchdog leg (docs/observe.md) lands watch_detect_steps /
    watch_false_positives / watch_armed / watch_append_us in the JSON
    tail, degrades to nulls on a hung child, and HVD_BENCH_WATCH=0
    skips it."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line):
            self.returncode = 0
            self.stdout = "RESULT " + line + "\n"
            self.stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-watch" in cmd:
            return FakeProc(json.dumps(
                {"watch_detect_steps": 5, "watch_false_positives": 0,
                 "watch_armed": True, "watch_append_us": 1.6,
                 "watch_overhead_pct_1ms_step": 0.16}))
        return FakeProc(json.dumps(payload))

    for leg in ("_autotune_delta", "_compression_delta"):
        monkeypatch.setattr(bench, leg, lambda v: {})
    for leg in ("_serving_leg", "_projection_leg", "_control_leg"):
        monkeypatch.setattr(bench, leg, lambda: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_WATCH", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["watch_detect_steps"] == 5
    assert out["watch_false_positives"] == 0
    assert out["watch_armed"] is True
    assert out["watch_append_us"] == 1.6
    assert any("--child-watch" in c for c in calls)

    # a hung watch child degrades to nulls, never costs the main number
    def raise_for_leg(cmd, *a, **k):
        if "--child-watch" in cmd:
            raise bench.subprocess.TimeoutExpired(cmd="x", timeout=1)
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench.subprocess, "run", raise_for_leg)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["watch_detect_steps"] is None
    assert out["watch_armed"] is None
    assert "timeout" in out["watch_error"]

    # HVD_BENCH_WATCH=0: no child run, no tail fields
    calls.clear()
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setenv("HVD_BENCH_WATCH", "0")
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert "watch_detect_steps" not in out
    assert not any("--child-watch" in c for c in calls)


def test_restore_leg_merged_and_skippable(monkeypatch, capsys):
    """The peer-state-plane leg (docs/fault_tolerance.md) lands
    restore_ckpt_stall_us / restore_p99_ms / restore_steps_lost in the
    JSON tail, degrades to nulls on a dead child, and
    HVD_BENCH_RESTORE=0 skips it."""
    bench = _load_bench()
    payload = {"metric": "resnet50_synthetic_img_sec_per_chip",
               "value": 2700.0, "unit": "images/sec/chip",
               "vs_baseline": 26.07}

    class FakeProc:
        def __init__(self, line):
            self.returncode = 0
            self.stdout = "RESULT " + line + "\n"
            self.stderr = ""

    calls = []

    def fake_run(cmd, *a, **k):
        calls.append(cmd)
        if "--child-restore" in cmd:
            return FakeProc(json.dumps(
                {"restore_ckpt_stall_us": 8.4, "restore_p99_ms": 312.0,
                 "restore_p50_ms": 120.0, "restore_steps_lost": 4,
                 "restore_snapshot_interval": 5,
                 "restore_drained": True}))
        return FakeProc(json.dumps(payload))

    for leg in ("_autotune_delta", "_compression_delta"):
        monkeypatch.setattr(bench, leg, lambda v: {})
    for leg in ("_serving_leg", "_projection_leg", "_control_leg",
                "_watch_leg"):
        monkeypatch.setattr(bench, leg, lambda: {})
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("HVD_BENCH_RESTORE", raising=False)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["restore_ckpt_stall_us"] == 8.4
    assert out["restore_p99_ms"] == 312.0
    assert out["restore_steps_lost"] == 4
    assert any("--child-restore" in c for c in calls)

    # a hung restore child degrades to nulls, never costs the number
    def raise_for_leg(cmd, *a, **k):
        if "--child-restore" in cmd:
            raise bench.subprocess.TimeoutExpired(cmd="x", timeout=1)
        return FakeProc(json.dumps(payload))

    monkeypatch.setattr(bench.subprocess, "run", raise_for_leg)
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert out["value"] == 2700.0
    assert out["restore_p99_ms"] is None
    assert out["restore_ckpt_stall_us"] is None
    assert "timeout" in out["restore_error"]

    # HVD_BENCH_RESTORE=0: no child run, no tail fields
    calls.clear()
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setenv("HVD_BENCH_RESTORE", "0")
    bench.main()
    out = json.loads(capsys.readouterr().out.strip())
    assert "restore_p99_ms" not in out
    assert not any("--child-restore" in c for c in calls)
