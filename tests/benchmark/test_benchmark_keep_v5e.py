"""The three cells that recompute their decoder layers (``qwen3next-8k``,
``sdar-bd4-8k``, ``kanana2-8k``) compiled for a described v5e at the cells'
own sizes, as ``run.py`` builds the step.  A recomputed layer now keeps, of
the outputs its second run made again, what ``models/recompute.py`` ranks
inside the byte budget it reckons from the shapes and the device's kind: the
compiled step still calls each forward kernel once a layer, the named
parts' products have no ``rematted_computation`` twin, and what that costs
is memory, held here against the rule the cells are held to.  No chip is
attached and nothing runs.  (A file of its own: the cases of
``test_benchmark_recompute_v5e.py`` and ``test_benchmark_moe_groups_v5e.py``
that hold the steps' ``hbm_gb`` with nothing kept, and the one that wants a
recomputed product in ``sdar-bd4-8k``, are pinned in ``tests/conftest.py``;
their assertions brought up to date are here.)"""

import re

import pytest

from test_benchmark_kanana2_v5e import kanana2_step  # noqa: F401 — fixture
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, topo)
from test_benchmark_recompute_v5e import compile_step

CHIP_BYTES = 16 * 2 ** 30
METRIC = "hvd_recompute_kept_bytes_traced_total"
#: cell -> (Mosaic calls of the step by kernel name, as
#: ``test_benchmark_recompute_v5e.py`` and ``test_benchmark_kanana2_v5e.py``
#: hold them; the band round the ``hbm_gb`` compiled before the chip
#: (PERF.md section 6, PR 37: 7.995, 7.891 and 9.043; with nothing kept
#: 6.584, 9.052 and 7.497); float32 parameters the benchmark keeps beside
#: the state; the names the budget keeps; the bytes it skips; the modules
#: whose products still run a second time).  ``sdar-bd4-8k`` holds *less*
#: than with nothing kept: with q kept as the kernels take it XLA no longer
#: holds the forward kernel's padded row statistics (268 MB each a layer)
#: from the forward pass to the backward.
CELLS = {
    "qwen3next-8k": (
        {"hvd_flash_fwd": 1, "hvd_flash_dq": 1, "hvd_flash_dkv": 1,
         "hvd_gdn_scan_fwd": 3, "hvd_gdn_scan_bwd": 3},
        (7.9e9, 8.1e9), 424_340_544,
        {"hvd_moe_routing", "hvd_keep_gdn_norm", "hvd_keep_out_proj",
         "hvd_keep_q_proj", "hvd_flash_q", "hvd_keep_mlp",
         "hvd_keep_gdn_in_proj", "hvd_keep_kv_proj", "hvd_gdn_scan_in",
         "hvd_keep_gdn_conv", "hvd_flash_k", "hvd_flash_v"}, 0,
        # under its sigmoid gate: the gate's derivative reads its output
        {"shared_down_proj", "shared_expert_gate"}),
    "sdar-bd4-8k": (
        {"hvd_flash_fwd": 4, "hvd_flash_dq": 4, "hvd_flash_dkv": 4},
        (7.8e9, 8.0e9), 456_346_624,
        {"hvd_moe_routing", "hvd_keep_out_proj", "hvd_keep_q_proj",
         "hvd_flash_q", "hvd_keep_kv_proj"},
        # k and v as the kernels take them, 32 heads: 2 x 4 x 134.2 MB
        2 * 4 * 16384 * 4096 * 2, set()),
    "kanana2-8k": (
        {"hvd_flash_fwd": 5, "hvd_flash_dq": 5, "hvd_flash_dkv": 5},
        (8.95e9, 9.15e9), 424_960_512,
        {"hvd_moe_routing", "hvd_keep_out_proj", "hvd_keep_q_proj",
         "hvd_flash_q", "hvd_keep_mlp", "hvd_keep_kv_proj", "hvd_flash_k",
         "hvd_flash_v"}, 0, set()),
}


def _kept_bytes():
    from horovod_tpu import metrics

    return {s["labels"]["name"]: s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                METRIC, {}).get("samples", [])}


@pytest.fixture(scope="module")
def steps(topo, no_compile_cache, kanana2_step):  # noqa: F811
    """``{cell: (the compiled step, what the counter read while it was
    traced)}``; ``kanana2-8k``'s step is the other file's fixture, traced
    before this one reads the counter."""
    from horovod_tpu import metrics

    got = {"kanana2-8k": (kanana2_step, None)}
    enabled = metrics.registry.enabled
    metrics.registry.enabled = True
    try:
        for cell in ("qwen3next-8k", "sdar-bd4-8k"):
            before = _kept_bytes()
            step = compile_step(cell, topo)
            got[cell] = (step, {k: v - before.get(k, 0)
                                for k, v in _kept_bytes().items()})
    finally:
        metrics.registry.enabled = enabled
    return got


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_layer_still_calls_each_forward_kernel_once(cell, steps):
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        steps[cell][0].as_text())
    assert {k: calls.count(k) for k in set(calls)} == CELLS[cell][0]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_what_is_kept_fits_beside_the_benchmarks_weights(cell, steps):
    """``hbm_gb`` as a traced run prints it (arguments + temporaries)
    inside the band round the value compiled before the chip, and the rule
    the budget is reckoned against: room for the benchmark's float32
    weights through the checked steps under three quarters of the chip."""
    _, (low, high), parameters = CELLS[cell][:3]
    mem = steps[cell][0].memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert low < hbm < high, hbm
    assert hbm + 4 * parameters < 0.75 * CHIP_BYTES


@pytest.mark.parametrize("cell", ["qwen3next-8k", "sdar-bd4-8k"])
def test_the_counter_says_what_was_kept_and_what_the_budget_refused(
        cell, steps):
    """One trace of the model a compile: the bytes by name, and under
    ``skipped`` what did not fit (``sdar-bd4-8k`` is the cell that cannot
    keep everything: the rank decides what goes)."""
    _, _, _, names, skipped, _ = CELLS[cell]
    read = {k: v for k, v in steps[cell][1].items() if v}
    assert set(read) - {"skipped"} == names
    assert read.get("skipped", 0) == skipped


def _recomputed(step):
    """The module paths (after ``layers_<i>/``) of the compiled step's ops
    with JAX's mark of a recomputed op, products apart from the rest."""
    products, others = set(), set()
    for path in re.findall(r'op_name="([^"]+)"', step.as_text()):
        after = re.search(r"rematted_computation/layers_\d+/(.*)", path)
        if after:
            (products if after.group(1).endswith("dot_general")
             else others).add(after.group(1))
    return products, others


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_which_parts_still_run_a_second_time(cell, steps):
    """The layers are still recomputed (``remat``: ``decoder_layer``): the
    two norms of a layer, the elementwise ends of the named parts.  No
    product is, but the ones no name reaches; no kernel is."""
    products, others = _recomputed(steps[cell][0])
    assert others, "nothing is recomputed"
    assert {p.split("/")[-2] for p in products} == CELLS[cell][5], products
    text = steps[cell][0].as_text()
    assert not [line for line in text.splitlines()
                if "rematted_computation" in line
                and 'custom_call_target="tpu_custom_call"' in line]
    for op in ("top_k", "sort"):
        assert not [p for p in others if re.search(
            rf"hvd_moe_route/(.*/)?{op}$", p)], op
