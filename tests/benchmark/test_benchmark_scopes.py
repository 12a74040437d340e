"""The names the program gives the blocks of its compiled step, where the
device trace's readers find them: in the metadata of the *optimized*
module of the toy GPT step compiled for a described ``v5e:2x2`` (the three
Mosaic kernels, each bucket's all-reduce, the update's fusions), and in the
module as lowered, bucket by bucket against ``FusionPlan.describe``.

Where the topology cannot be described (no TPU compiler here, or another
process holds it) the same scopes are checked on the CPU lowering, so these
tests pass and count on every machine.  The topology is described inside a
fixture, never while a module is imported.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

CHIPS = 4
#: small enough that the toy model's gradients fill several buckets
THRESHOLD_BYTES = 64 * 1024


def _lower_toy_step(devices):
    """The toy GPT data-parallel step lowered for ``devices``; returns
    ``(lowered, params' shapes)``."""
    import benchmark_tiny
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.configs import gpt2_small

    cfg, mix = benchmark_tiny.GPT_TINY, benchmark_tiny.SEQ_TINY
    hvd.shutdown()
    try:
        # the state's shapes from a world of host devices: a described chip
        # holds no array
        hvd.init(devices=jax.devices("cpu")[:CHIPS])
        prog = gpt2_small.program(cfg, mix)
        state = jax.eval_shape(lambda: init_train_state(
            prog["model"], prog["optimizer"], prog["sample"]))
        hvd.shutdown()
        hvd.init(devices=list(devices))
        whole = NamedSharding(core.mesh(), P())
        rows = NamedSharding(core.mesh(), P(core.AXIS))
        prog = gpt2_small.program(cfg, mix)
        step = make_train_step(
            apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
            optimizer=prog["optimizer"], threshold_bytes=THRESHOLD_BYTES)
        ids = jax.ShapeDtypeStruct(
            (CHIPS * mix["rows_per_chip"], mix["items_per_row"]), jnp.int32,
            sharding=rows)
        lowered = jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"]((ids,)))
    finally:
        hvd.shutdown()
    return lowered, state.params


@pytest.fixture(scope="module")
def toy_step():
    """``(kind, text, lowered text, params)``: ``kind`` is ``"v5e"`` with
    the optimized module of the step compiled for four described chips, or
    ``"cpu"`` with the CPU lowering in its place."""
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:  # noqa: BLE001 — whatever stops it, use the CPU
        lowered, params = _lower_toy_step(jax.devices("cpu")[:CHIPS])
        text = lowered.as_text(debug_info=True)
        return "cpu", text, text, params
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered, params = _lower_toy_step(topo.devices)
        # the combiner would merge the toy's small buckets into one
        # all-reduce; the cells' 66-154 MB buckets stay apart without this
        text = lowered.compile(compiler_options={
            "xla_disable_hlo_passes": "all-reduce-combiner"}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()
    return "v5e", text, lowered.as_text(debug_info=True), params


def _plan(params):
    from horovod_tpu.ops.fusion import FusionPlan, tree_leaf_names

    leaves = jax.tree_util.tree_leaves(params)
    return FusionPlan(leaves, THRESHOLD_BYTES).describe(
        leaves, tree_leaf_names(params))


def _op_names(text, needle):
    """``op_name`` of every instruction of an optimized module whose line
    holds ``needle``."""
    found = []
    for line in text.splitlines():
        if needle in line:
            found += re.findall(r'op_name="([^"]*)"', line)
    return found


def _lowered_allreduces(lowered):
    """``[(location name, result bytes)]`` of the ``stablehlo.all_reduce``
    ops of a module lowered with debug info: the op's type and location
    are on the line that closes its region, the location a ``#locN`` of
    the table at the module's end."""
    table = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', lowered))
    lines = lowered.splitlines()
    found = []
    for i, line in enumerate(lines):
        if '"stablehlo.all_reduce"' not in line:
            continue
        close = next(ln for ln in lines[i:] if ln.lstrip().startswith("})"))
        dims, dtype = re.findall(r"-> tensor<((?:\d+x)*)(\w+)>", close)[-1]
        assert dtype == "f32", close
        ref = re.search(r'loc\((#loc\d+|"[^"]*")', close).group(1)
        found.append((table.get(ref, ref.strip('"')), 4 * math.prod(
            int(d) for d in dims.split("x") if d)))
    return found


@pytest.mark.parametrize("kernel", ["hvd_flash_fwd", "hvd_flash_dq",
                                    "hvd_flash_dkv"])
def test_each_flash_kernel_carries_its_name(toy_step, kernel):
    kind, text, lowered, _ = toy_step
    if kind == "v5e":
        names = _op_names(text, 'custom_call_target="tpu_custom_call"')
        assert names, "no Mosaic kernel in the optimized module"
        mine = [n for n in names if f"/{kernel}/" in n]
        # two layers, one call of each kernel a layer
        assert len(mine) == 2, names
        assert all("hvd_forward" in n for n in mine)
        assert all(("transpose(" in n) == (kernel != "hvd_flash_fwd")
                   for n in mine)
    else:
        # interpret mode has no custom call; the scope is on the kernel
        # body's ops
        assert re.search(rf'hvd_forward[^"]*/{kernel}/', lowered)


def test_every_gradient_allreduce_sits_under_its_bucket(toy_step):
    kind, text, _, params = toy_step
    plan = _plan(params)
    assert len(plan) > 2
    if kind == "cpu":
        names = [name for name, _ in _lowered_allreduces(text)]
    else:
        names = _op_names(text, " all-reduce(") \
            + _op_names(text, " all-reduce-start(")
    assert names
    buckets = set()
    for name in names:
        assert "hvd_loss_allreduce" in name or re.search(
            r"hvd_grad_allreduce/hvd_bucket_\d+/reduce/", name), name
        buckets.update(int(k) for k in re.findall(
            r"hvd_bucket_(\d+)/reduce/", name))
    assert buckets == {b["bucket"] for b in plan}
    assert any("hvd_loss_allreduce" in n for n in names)


def test_the_update_keeps_fusions_under_its_scope(toy_step):
    kind, text, lowered, _ = toy_step
    if kind == "v5e":
        assert any("hvd_optimizer_update" in n
                   for n in _op_names(text, " fusion("))
    else:
        assert "hvd_optimizer_update" in lowered


def test_the_loss_has_a_scope_inside_the_forward_block(toy_step):
    _, _, lowered, _ = toy_step
    assert re.search(r'hvd_forward\)?/hvd_loss/', lowered)


def test_bucket_list_matches_scopes_and_shapes_of_the_lowered_module(
        toy_step):
    """``FusionPlan.describe`` against the module as lowered: bucket ``k``
    of the list is the ``hvd_bucket_<k>`` whose all-reduce carries that
    many bytes, a bucket of several leaves has ``pack`` and ``unpack``
    beneath it and a bucket of one leaf ``reduce`` only, and the leaf
    names are the parameters' own."""
    from horovod_tpu.ops.fusion import tree_leaf_names

    _, _, lowered, params = toy_step
    plan = _plan(params)
    sizes = {}
    for name, nbytes in _lowered_allreduces(lowered):
        m = re.search(r"hvd_bucket_(\d+)/reduce/psum", name)
        if m:
            sizes[int(m.group(1))] = nbytes
    assert sizes == {b["bucket"]: b["bytes"] for b in plan}
    names = tree_leaf_names(params)
    assert [n for b in plan for n in b["leaves"]] == names
    assert sum(b["bytes"] for b in plan) == 4 * sum(
        math.prod(a.shape) for a in jax.tree_util.tree_leaves(params))
    for b in plan:
        scope = f"hvd_grad_allreduce/{b['scope']}/"
        assert b["scope"] == f"hvd_bucket_{b['bucket']}"
        assert b["dtype"] == "float32"
        assert (scope + "pack/" in lowered) == (len(b["leaves"]) > 1)
        assert (scope + "unpack/" in lowered) == (len(b["leaves"]) > 1)
        assert scope + "reduce/" in lowered
