"""``nemotron3-8k``'s step lowered and compiled for a described v5e at the
cell's own size: the model through ``init_train_state``'s shapes and
``make_train_step`` as ``run.py`` builds it, the state-space scan as XLA ops
under its scope, the one attention block's flash kernels compiled by
Mosaic.  No chip is attached and nothing runs: this counts the step's
Mosaic calls, holds the scan under ``hvd_ssm_scan`` and the step's memory
account before a chip call does.  And the steps two accepted expert cells
lower to, text for text what they were before ``routed_experts`` took an
expert's form."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import benchmark_tiny
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, topo)

BLOCKS = {9: 666_962_944, 7: 528_092_736}
CHIP_BYTES = 16 * 2 ** 30
#: sha256 of an accepted cell's training step as ``jax.jit(step).lower``
#: prints it for one described chip (StableHLO, no locations), with the
#: Mosaic kernels' serialized bodies taken out (they carry the checkout's
#: path; ``test_benchmark_sdar_v5e.py`` and its siblings hold the bodies
#: themselves), as the parent of PR 41 lowers them (read there by this
#: file's ``_lowered_step``).  ``kanana2-8k`` goes through
#: ``grouped_routed_experts`` under the sigmoid rule in two groups,
#: ``qwen3next-8k`` through ``routed_experts`` in one: the gated form's
#: loops are the ones they were, operation for operation.
LOWERED_BEFORE_THE_FORM = {
    "kanana2-8k":
        "89b7207c67bcf10c0b04163491f4a040bc461d3891ade0b686126740166a25fb",
    "qwen3next-8k":
        "d741fc362e95cd79aa1dd03a4b234969f58ff739352e2807ce9c2d62be461cf0",
}


def _lowered_step(topo, name):  # noqa: F811
    """``(cell, parameters, the cell's step lowered for one described
    chip)``."""
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell(name)
    cfg, mix, adapter = cell.cfg, cell.mix, cell.adapter
    hvd.shutdown()
    try:
        # the state's shapes from a world of host devices: a described chip
        # holds no array
        hvd.init(devices=jax.devices("cpu")[:1])
        prog = adapter.program(cfg, mix)
        state = jax.eval_shape(lambda: init_train_state(
            prog["model"], prog["optimizer"], prog["sample"]))
        parameters = sum(x.size for x in jax.tree_util.tree_leaves(
            state.params))
        hvd.shutdown()
        hvd.init(devices=[topo.devices[0]])
        whole = NamedSharding(core.mesh(), P())
        rows = NamedSharding(core.mesh(), P(core.AXIS))
        prog = adapter.program(cfg, mix)
        step = make_train_step(
            apply_fn=prog["apply_fn"], loss_fn=prog["loss_fn"],
            optimizer=prog["optimizer"])
        arrays = tuple(jax.ShapeDtypeStruct(
            (mix["rows_per_chip"], *a["shape"]), jnp.dtype(a["dtype"]),
            sharding=rows) for a in mix["arrays"])
        return cell, parameters, jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"](arrays))
    finally:
        hvd.shutdown()


@pytest.fixture(scope="module")
def nemotron_step(topo, no_compile_cache):  # noqa: F811
    """``(blocks, parameters, the cell's step compiled for one described
    chip)``."""
    cell, parameters, lowered = _lowered_step(topo, "nemotron3-8k")
    return cell.cfg["num_hidden_layers"], parameters, lowered.compile()


def test_the_scan_is_xla_under_its_scope_and_the_kernels_are_flashs(
        nemotron_step):
    """The one attention block calls each flash kernel once (a recomputed
    block keeps ``o`` and ``lse``) and nothing else of the step is a Mosaic
    call: the state-space scan is XLA ops, its loop over chunks and its
    products under ``hvd_ssm_scan``, forward and, without the recompute's
    mark, in the backward rule."""
    blocks, parameters, step = nemotron_step
    assert BLOCKS[blocks] == parameters
    text = step.as_text()
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)
    assert {k: calls.count(k) for k in set(calls)} == {
        "hvd_flash_fwd": 1, "hvd_flash_dq": 1, "hvd_flash_dkv": 1}
    paths = re.findall(r'op_name="([^"]+)"', text)
    scan = [p for p in paths if "/hvd_ssm/hvd_ssm_scan/" in p]
    assert any("/while" in p for p in scan)
    assert any(p.endswith("dot_general") for p in scan)
    assert any("transpose(jvp(" in p for p in scan)
    assert not any("rematted_computation" in p for p in scan)
    mixers = blocks // 2
    assert len({re.search(r"layers_(\d+)", p).group(1) for p in scan
                if "layers_" in p}) == mixers
    for scope in ("hvd_ssm_in", "hvd_ssm_conv", "hvd_ssm_out",
                  "hvd_attn_qkv", "hvd_attn_out", "hvd_flash_layout",
                  "hvd_moe_route", "hvd_moe_experts", "hvd_moe_shared",
                  "hvd_head", "hvd_loss/"):
        assert scope in text, scope
    # the recompute is marked and holds the state-space block's sides
    marked = [p for p in paths if "rematted_computation" in p]
    assert any("/hvd_ssm_in/" in p for p in marked)
    assert any("/hvd_ssm_conv/" in p for p in marked)
    # B and C go through the scan once a group: nothing repeats them to
    # the 64 heads ([.., 64, 128] of the sequence's length in bf16)
    assert "bf16[1,8192,64,128]" not in text


def test_the_step_fits_one_chip_beside_the_benchmarks_weights(
        nemotron_step):
    """``hbm_gb`` as a traced run will print it (arguments + temporaries):
    over the 4 GB a new cell has to fill, and room for the benchmark's
    float32 weights through the checked steps."""
    _, parameters, step = nemotron_step
    mem = step.memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"hbm {hbm} = arguments {mem.argument_size_in_bytes} + "
          f"temporaries {mem.temp_size_in_bytes}")
    assert mem.argument_size_in_bytes >= 12 * parameters
    assert 12.5 * parameters < hbm < 14.5 * parameters, hbm
    assert hbm > 0.25 * CHIP_BYTES
    assert hbm + 4 * parameters < 0.75 * CHIP_BYTES


@pytest.mark.parametrize("cell", sorted(LOWERED_BEFORE_THE_FORM))
def test_an_accepted_expert_cells_step_lowers_to_what_it_did(
        topo, no_compile_cache, cell):  # noqa: F811
    _, _, lowered = _lowered_step(topo, cell)
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", lowered.as_text())
    assert text.count("BODY") >= 3
    assert hashlib.sha256(text.encode()).hexdigest() \
        == LOWERED_BEFORE_THE_FORM[cell]
