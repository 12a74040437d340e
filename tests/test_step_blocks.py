"""``make_train_step`` builds one program, and that program's blocks name
themselves: on every branch of the gradient exchange that the builder keeps,
each collective sits under ``hvd_grad_allreduce`` or ``hvd_loss_allreduce``
and ``hvd_forward``, ``hvd_loss``, ``hvd_optimizer_update`` hold ops of the
compiled step.  The device scopes are the only account of a step's blocks
(a ``jax.profiler`` trace reads them, docs/profiling.md): there is no
second, decomposed step, no ``profile`` keyword and no ``compute.json``."""

import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

import horovod_tpu as hvd
from horovod_tpu.models.mlp import MLP
from horovod_tpu.observe import events as events_mod
from horovod_tpu.ops.compression import (
    Compression, ErrorFeedback, Int8Compressor,
)
from horovod_tpu.timeline.timeline import timeline
from horovod_tpu.training import (
    init_train_state, make_train_step, shard_batch,
)

LEAVES = 6  # three Dense layers: a kernel and a bias each


class _BatchNormMLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        x = nn.Dense(16)(x)
        x = nn.BatchNorm(use_running_average=not train)(x)
        return nn.Dense(4)(nn.relu(x))


def _loss(logits, y):
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def _step(batch_stats=False, compression=None, **kw):
    """``(step, state, x, y)`` of a small classifier on the 8-device
    mesh (two simulated nodes of four)."""
    opt = optax.sgd(0.1)
    if batch_stats:
        model = _BatchNormMLP()

        def apply_fn(v, a, train=True, **mutable):
            return model.apply(v, a, train=train, **mutable)
    else:
        model = MLP(features=(16, 16, 4))

        def apply_fn(v, a, train=True):
            return model.apply(v, a)
    step = make_train_step(apply_fn=apply_fn, loss_fn=_loss, optimizer=opt,
                           has_batch_stats=batch_stats,
                           compression=compression, **kw)
    state = init_train_state(model, opt, jnp.zeros((2, 8)),
                             has_batch_stats=batch_stats,
                             compression=compression)
    x = shard_batch(np.zeros((16, 8), np.float32))
    y = shard_batch(np.zeros((16,), np.int32))
    return step, state, x, y


_COLLECTIVE = re.compile(
    r'stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|'
    r'collective_permute|collective_broadcast)"?[( ]')


def _collectives(lowered):
    """``[(kind, scope path, result type)]`` of every collective of a
    module lowered with debug info.  An op's type and location close the
    line that ends it: its own line, or for an op with a reduction region
    the line that closes the region; a location is a ``#locN`` of the table
    at the module's end."""
    text = lowered.as_text(debug_info=True)
    table = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
    lines = text.splitlines()
    found = []
    for i, line in enumerate(lines):
        op = _COLLECTIVE.search(line)
        if op is None:
            continue
        end = line if not line.rstrip().endswith("({") else next(
            ln for ln in lines[i:] if ln.lstrip().startswith("})"))
        ref = re.findall(r'loc\((#loc\d+|"[^"]*")', end)[-1]
        result = re.findall(r"-> \(?tensor<([^>]*)>", end)[-1]
        found.append((op.group(1), table.get(ref, ref.strip('"')), result))
    return found


#: branch of ``_reduce_grads`` -> (keywords, the kinds of collective under
#: ``hvd_grad_allreduce``, the dtype on the wire): the kinds say that the
#: branch asked for is the one that was built
BRANCHES = {
    "defaults": ({}, {"all_reduce"}, "f32"),
    "batch_stats": ({"batch_stats": True}, {"all_reduce"}, "f32"),
    "hierarchical": ({"hierarchical": True},
                     {"reduce_scatter", "all_reduce", "all_gather"}, "f32"),
    "two_level": ({"two_level": True},
                  {"reduce_scatter", "all_reduce", "all_gather"}, "f32"),
    "error_feedback": ({"compression": ErrorFeedback(Int8Compressor)},
                       {"all_reduce"}, "i8"),
    "in_graph_steps": ({"in_graph_steps": 2}, {"all_reduce"}, "f32"),
    # combinations: a stateless wire format, the residual carried through
    # the scan, the cross stage alone compressed, a sum per leaf
    "bf16_wire": ({"compression": Compression.bf16}, {"all_reduce"},
                  "bf16"),
    "error_feedback_in_graph_steps": (
        {"compression": ErrorFeedback(Int8Compressor), "in_graph_steps": 2},
        {"all_reduce"}, "i8"),
    "two_level_int8_cross": (
        {"two_level": True, "compression": Int8Compressor},
        {"reduce_scatter", "all_reduce", "all_gather"}, "i8"),
    "hierarchical_sum": ({"hierarchical": True, "op": hvd.Sum},
                         {"reduce_scatter", "all_reduce", "all_gather"},
                         "f32"),
    "sum": ({"op": hvd.Sum}, {"all_reduce"}, "f32"),
    "a_bucket_a_leaf": ({"threshold_bytes": 1}, {"all_reduce"}, "f32"),
}


@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_every_collective_and_block_of_the_step_is_under_its_scope(
        hvd_init, branch):
    """Read off the lowered module, where every collective still carries
    its own location (XLA's combiner later merges the loss's scalar into
    the first gradient all-reduce, which keeps one scope), and for the
    blocks off the compiled module, where the scopes outlive fusion."""
    kw, kinds, wire = BRANCHES[branch]
    step, state, x, y = _step(**kw)
    lowered = jax.jit(step).lower(state, x, y)
    found = _collectives(lowered)
    loss = [c for c in found if "hvd_loss_allreduce" in c[1]]
    grads = [c for c in found if "hvd_grad_allreduce" in c[1]]
    assert len(loss) + len(grads) == len(found), found
    # the loss: one scalar all-reduce; nothing of a gradient rides with it
    assert [(k, r) for k, _, r in loss] == [("all_reduce", "f32")], loss
    assert {k for k, _, _ in grads} == kinds, grads
    assert any(r.endswith(wire) and r != "f32" for _, _, r in grads), grads
    if branch == "a_bucket_a_leaf":
        assert sorted(re.search(r"hvd_bucket_(\d+)/reduce/", s).group(1)
                      for _, s, _ in grads) == [
                          str(k) for k in range(LEAVES)]
    if branch == "two_level_int8_cross":
        # the local stages stay float32; the cross stage alone is int8
        assert {k for k, _, r in grads if r.endswith("i8")} == {
            "all_reduce"}, grads
        assert {r.rsplit("x", 1)[-1] for k, _, r in grads
                if k != "all_reduce"} == {"f32"}, grads
    if branch.startswith("error_feedback"):
        # the scales' exchange is the exchange's too
        assert any(s.endswith("pmax") for _, s, _ in grads), grads
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    for scope in ("hvd_forward", "hvd_loss/", "hvd_optimizer_update",
                  "hvd_grad_allreduce"):
        assert any(scope in n for n in names), scope
    assert any("transpose(jvp(hvd_forward))" in n for n in names)


def test_profile_is_no_keyword_of_make_train_step(hvd_init):
    with pytest.raises(TypeError, match="profile"):
        _step(profile=True)


def test_a_step_function_carries_no_compute_profiler(hvd_init):
    for autotune in (False, True):
        step, _, _, _ = _step(autotune=autotune)
        assert not hasattr(step, "compute_profiler")
        assert hasattr(step, "loss_fetcher")


def test_six_steps_in_an_open_trace_window_run_one_program(
        hvd_init, tmp_path, monkeypatch):
    """The property the builder is held to: with the timeline on and its
    window open over every step, each step is the one compiled program
    (the jitted step's cache grows once, to one entry) and the trace
    directory gets ``comm.json`` and nothing of a second account."""
    monkeypatch.setenv("HVD_TIMELINE", str(tmp_path))
    monkeypatch.setenv("HVD_TIMELINE_PYTHON", "1")
    monkeypatch.setenv("HVD_TRACE_START_STEP", "1")
    monkeypatch.setenv("HVD_TRACE_END_STEP", "6")
    events_mod._reset_for_tests()
    timeline.shutdown()
    timeline.initialize()
    try:
        step, state, x, y = _step(donate=False)
        for _ in range(6):
            state, loss = step(state, x, y)
        assert np.isfinite(float(loss))
    finally:
        timeline.shutdown()
    compiles = [e["payload"] for e in events_mod.recorder().drain()
                if e["kind"] == "step.compile"]
    events_mod._reset_for_tests()
    assert [(c["step"], c["programs"]) for c in compiles] == [(1, 1)]
    written = sorted(p.name for p in pathlib.Path(tmp_path).rglob("*")
                     if p.is_file())
    assert "comm.json" in written and "compute.json" not in written
    with open(tmp_path / "0" / "comm.json") as f:
        calls = [e for e in json.load(f) if e.get("name") == "CALL"]
    assert len(calls) == 6
