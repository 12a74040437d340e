"""What the step path tells a tracer about itself: the one host-span helper
and its two sinks, ``hvd_step_compiles_total`` and its flight-recorder
event, the bucket list the Recorder writes beside the gradient manifest.
No test here reads a clock."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import metrics
from horovod_tpu.observe import events as events_mod
from horovod_tpu.ops.fusion import FusionPlan, bucket_scope, tree_leaf_names
from horovod_tpu.timeline.recorder import Recorder
from horovod_tpu.timeline.timeline import host_span, timeline
from horovod_tpu.training import (
    init_train_state, make_train_step, shard_batch,
)


class _Linear:
    """The least a flax-style model needs."""

    def init(self, rng, x):
        return {"params": {"w": jnp.zeros((x.shape[-1], 2)),
                           "b": jnp.zeros((2,))}}

    def apply(self, variables, x):
        p = variables["params"]
        return x @ p["w"] + p["b"]


def _mse(logits, y):
    return jnp.mean((logits - y) ** 2)


def _toy_step(**kw):
    model = _Linear()
    opt = optax.sgd(0.1)
    state = init_train_state(model, opt, jnp.zeros((8, 3)))
    step = make_train_step(apply_fn=lambda v, x: model.apply(v, x),
                           loss_fn=_mse, optimizer=opt, **kw)
    return state, step


def _batch(rows, rng):
    return (shard_batch(rng.normal(size=(rows, 3)).astype(np.float32)),
            shard_batch(rng.normal(size=(rows, 2)).astype(np.float32)))


@pytest.fixture()
def compiles(monkeypatch):
    """Reads ``hvd_step_compiles_total`` since the fixture was made."""
    monkeypatch.setattr(metrics.registry, "enabled", True)
    monkeypatch.delenv("HVD_METRICS_KV_ADDR", raising=False)
    monkeypatch.delenv("HVD_METRICS_KV_PORT", raising=False)
    events_mod._reset_for_tests()

    def total():
        samples = metrics.registry.snapshot()["metrics"].get(
            "hvd_step_compiles_total", {}).get("samples", [])
        return sum(s["value"] for s in samples)

    base = total()
    yield lambda: total() - base
    events_mod._reset_for_tests()


def _compile_events():
    return [e for e in events_mod.recorder().drain()
            if e["kind"] == "step.compile"]


def test_compiles_counter_rises_with_the_batch_shape_and_not_otherwise(
        hvd_init, compiles, rng):
    state, step = _toy_step()
    assert compiles() == 0
    for expected, rows in ((1, 8), (1, 8), (1, 8), (2, 16), (2, 16),
                           (2, 8)):
        state, _ = step(state, *_batch(rows, rng))
        assert compiles() == expected
    found = _compile_events()
    assert [e["payload"]["step"] for e in found] == [1, 4]
    assert [e["payload"]["programs"] for e in found] == [1, 2]
    assert found[1]["payload"]["args"] == ["float32[16, 3]",
                                           "float32[16, 2]"]


def test_a_call_under_a_trace_counts_no_step_and_no_compile(
        hvd_init, compiles, rng):
    state, step = _toy_step()
    x, y = _batch(8, rng)
    jax.make_jaxpr(step)(state, x, y)
    assert compiles() == 0 and not _compile_events()
    state, _ = step(state, x, y)
    assert [e["payload"]["step"] for e in _compile_events()] == [1]


def test_compiles_are_recorded_with_the_metrics_plane_off(
        hvd_init, compiles, monkeypatch, rng):
    """The event needs no registry: "which step recompiled" has an answer
    in a job that exports no metrics."""
    monkeypatch.setattr(metrics.registry, "enabled", False)
    state, step = _toy_step()
    state, _ = step(state, *_batch(8, rng))
    assert compiles() == 0
    assert len(_compile_events()) == 1


def _chrome_events(tmp_path):
    with open(tmp_path / "0" / "comm.json") as f:
        return json.load(f)


@pytest.fixture()
def global_timeline(tmp_path, monkeypatch):
    """The process-wide timeline writing under ``tmp_path``, closed and
    its step counter's owner forgotten afterwards."""
    monkeypatch.setenv("HVD_TIMELINE_PYTHON", "1")
    tl = timeline
    tl.shutdown()
    tl.initialize(str(tmp_path))
    yield tl
    tl.shutdown()


def test_host_span_emits_the_chrome_event_timeline_span_emits(
        hvd_init, global_timeline, tmp_path):
    with host_span("call", step_num=3):
        pass
    with host_span("loader_h2d", cat="loader", epoch=1,
                                batch=0):
        pass
    global_timeline.shutdown()
    events = _chrome_events(tmp_path)
    assert [(e["name"], e["cat"], e["tid"], e["ph"]) for e in events] == [
        ("CALL", "train_step", "train_step", "X"),
        ("LOADER_H2D", "loader", "loader", "X")]


def test_host_span_without_a_timeline_emits_nothing_and_runs_the_body(
        hvd_init):
    timeline.shutdown()
    ran = []
    with host_span("call", step_num=1):
        ran.append(1)
    assert ran == [1] and not timeline.enabled


def test_host_span_lets_the_bodys_exception_through(hvd_init):
    with pytest.raises(KeyError):
        with host_span("call", step_num=1):
            raise KeyError("boom")


def test_the_steps_chrome_spans_nest_under_one_step_span(
        hvd_init, global_timeline, tmp_path, rng):
    """The replay engine's STEP window (``name`` STEP on the ``train_step``
    row) now comes from the helper, with the step's parts on the same
    row."""
    state, step = _toy_step()
    for _ in range(2):
        state, _ = step(state, *_batch(8, rng))
    global_timeline.shutdown()
    rows = [e for e in _chrome_events(tmp_path)
            if e.get("cat") == "train_step"]
    names = [e["name"] for e in rows]
    assert names.count("STEP") == 2
    for part in ("PREFLIGHT", "CALL", "GUARD"):
        assert names.count(part) == 2
    steps = [e for e in rows if e["name"] == "STEP"]
    # the first build was at make_train_step, before any step
    assert names.count("REBUILD") == 1
    for part in (e for e in rows if e["name"] not in ("STEP", "REBUILD")):
        assert any(s["ts"] <= part["ts"]
                   and part["ts"] + part["dur"] <= s["ts"] + s["dur"] + 1e-3
                   for s in steps)


def test_recorder_writes_the_bucket_list_beside_the_manifest(hvd_init,
                                                             tmp_path):
    grads = {"dense": {"kernel": np.zeros((300, 40), np.float32),
                       "bias": np.zeros((40,), np.float32)},
             "embed": np.zeros((5000, 40), np.float32),
             "scale": np.zeros((40,), jnp.bfloat16)}
    Recorder(str(tmp_path)).register_gradients(grads)
    buckets = json.loads(
        (tmp_path / "0" / "gradient_buckets.json").read_text())
    leaves = jax.tree_util.tree_leaves(grads)
    assert buckets == FusionPlan(leaves).describe(
        leaves, tree_leaf_names(grads))
    assert [b["bucket"] for b in buckets] == list(range(len(buckets)))
    assert [b["scope"] for b in buckets] == [
        bucket_scope(k) for k in range(len(buckets))]
    by_dtype = {b["dtype"]: b for b in buckets}
    assert by_dtype["bfloat16"]["leaves"] == ["scale"]
    assert by_dtype["bfloat16"]["bytes"] == 80
    assert by_dtype["float32"]["leaves"] == [
        "dense/bias", "dense/kernel", "embed"]
    assert by_dtype["float32"]["bytes"] == 4 * (40 + 12000 + 200000)
    manifest = json.loads(
        (tmp_path / "0" / "gradient_name_list.json").read_text())
    assert sorted(manifest) == sorted(
        "gradients/" + n for b in buckets for n in b["leaves"])


def test_describe_follows_an_explicit_plan(hvd_init):
    leaves = [np.zeros((4,), np.float32), np.zeros((6,), np.float32),
              np.zeros((2,), np.float32)]
    plan = FusionPlan(leaves, explicit_buckets=[[2, 0]])
    assert plan.describe(leaves, ["a", "b", "c"]) == [
        {"bucket": 0, "scope": "hvd_bucket_0", "leaves": ["c", "a"],
         "dtype": "float32", "bytes": 24},
        {"bucket": 1, "scope": "hvd_bucket_1", "leaves": ["b"],
         "dtype": "float32", "bytes": 24}]
