"""The two cells that recompute their decoder layers (``sdar-bd4-8k``,
``qwen3next-8k``) compiled for a described v5e at the cells' own sizes, as
``run.py`` builds the step.  A recomputed layer keeps what the Pallas
forward kernels wrote for their backward kernels
(``models/qwen3_next.recomputed``), so the compiled step calls each forward
kernel once a layer, not twice.  No chip is attached and nothing runs.
(What the steps hold since the layers keep more than the kernels' outputs,
and which products still run a second time, is
``test_benchmark_keep_v5e.py``'s.)"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import benchmark_tiny
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)

LAYERS = 4
#: cell -> Mosaic calls of the step by kernel name
CELLS = {
    "sdar-bd4-8k": {"hvd_flash_fwd": LAYERS, "hvd_flash_dq": LAYERS,
                    "hvd_flash_dkv": LAYERS},
    # three DeltaNet layers and one of full attention
    "qwen3next-8k": {"hvd_flash_fwd": 1, "hvd_flash_dq": 1,
                     "hvd_flash_dkv": 1, "hvd_gdn_scan_fwd": 3,
                     "hvd_gdn_scan_bwd": 3},
}


def compile_step(cell_name, topo):  # noqa: F811
    """``cell_name``'s step compiled for one described chip."""
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell(cell_name)
    cfg, mix, adapter = cell.cfg, cell.mix, cell.adapter
    assert cfg["num_hidden_layers"] == LAYERS
    assert cfg["remat"] == "decoder_layer"
    hvd.shutdown()
    try:
        # the state's shapes from a world of host devices: a described chip
        # holds no array
        hvd.init(devices=jax.devices("cpu")[:1])
        prog = adapter.program(cfg, mix)
        state = jax.eval_shape(lambda: init_train_state(
            prog["model"], prog["optimizer"], prog["sample"]))
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
        return jax.jit(step).lower(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=whole), state),
            *prog["xy"](arrays)).compile()
    finally:
        hvd.shutdown()


@pytest.fixture(scope="module")
def steps(topo, no_compile_cache):  # noqa: F811
    return {name: compile_step(name, topo) for name in CELLS}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_layer_calls_each_forward_kernel_once(cell, steps):
    """What the parent ran twice a layer (the forward pass, then
    ``nn.remat``'s recompute) is in the compiled step once: as many forward
    calls as backward ones, and no Mosaic call besides."""
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        steps[cell].as_text())
    assert {k: calls.count(k) for k in set(calls)} == CELLS[cell]


@pytest.mark.parametrize("cell", ["qwen3next-8k"])
def test_the_layers_are_still_recomputed(cell, steps):
    """A product still runs again in the backward pass
    (``rematted_computation`` on its path), as the cell's configuration
    says (``remat``: ``decoder_layer``); no kernel does.  In ``sdar-bd4-8k``
    no product does any more (PR 37:
    ``test_benchmark_keep_v5e.py::test_which_parts_still_run_a_second_time``)."""
    text = steps[cell].as_text()
    again = [line for line in text.splitlines()
             if "rematted_computation" in line and "dot_general" in line]
    assert again, "no product is recomputed"
    assert not [line for line in text.splitlines()
                if "rematted_computation" in line
                and 'custom_call_target="tpu_custom_call"' in line]


