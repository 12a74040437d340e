"""Test harness: 8 virtual CPU devices stand in for an 8-chip slice.

The reference simulates "multi-node" as N processes on localhost under
``mpirun -np 2 -H localhost:2`` (reference docker-compose.test.yml:52,
.buildkite/gen-pipeline.sh:110-113).  The TPU-native analog (SURVEY §4) is
a single process with ``--xla_force_host_platform_device_count=8``: eight
XLA CPU devices form the mesh, and SPMD programs over it exercise the same
collective logic that runs over ICI on a real slice.
"""

import os

# Must be set before jax initializes its backends.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long compile-heavy drives excluded from the tier-1 budget "
        "(run explicitly or without -m 'not slow')",
    )


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, (
        "tests need --xla_force_host_platform_device_count=8"
    )
    return devs[:8]


@pytest.fixture()
def hvd_init(cpu_devices):
    """Fresh 8-rank world per test (2 simulated nodes x 4 local ranks)."""
    hvd.shutdown()
    hvd.init(devices=cpu_devices, local_size=4)
    yield hvd
    hvd.shutdown()


@pytest.fixture()
def rng():
    return np.random.default_rng(1234)


#: Tests of ``tests/benchmark`` that pin the benchmark to what it held when
#: they were written: the four cells before ``qwen3next-8k`` (PR 26), and
#: PR 26's eight metrics as the last of ``per_layer`` (PR 27 appends
#: ``loss_ms``).  Those files are the benchmark's own (``BENCHMARK.json``
#: lists the directory under ``paths``) and a PR that changes the program
#: may not edit them; the same assertions brought up to date are at the end
#: of ``test_benchmark_qwen3_next.py`` and in ``test_benchmark_loss.py``.
#: PR 30 appends two cells (``sdar-bd4-8k``, ``gpt2s-4k``) and three metrics:
#: three more tests pin the five cells and the lists of PR 27, and
#: ``test_benchmark_sdar.py`` ends with the same assertions brought up to
#: date.  PR 33 keeps the flash forward kernel's output and row statistics
#: across the layer recompute: ``sdar-bd4-8k``'s compiled step calls the
#: forward kernel four times, not eight, and holds 9.66 GB, not under 8.0;
#: ``test_benchmark_recompute_v5e.py`` holds both as they are now.  PR 34
#: appends the eighth cell (``kanana2-8k``) and seven metrics: two tests of
#: ``test_benchmark_sdar.py`` pin the seven cells and the lists of PR 30
#: (``test_benchmark_kanana2.py`` ends with the same assertions brought up
#: to date), and with eight cells a second four-chip cell is no fault of
#: form any more, which one case of ``test_benchmark_form.py`` expects.  PR 35
#: carries one accumulator of the experts' gradients through a layer's groups:
#: ``sdar-bd4-8k``'s compiled step holds 9.055 GB, two 302 MB temporaries
#: under the band PR 33 predicted; ``test_benchmark_moe_groups_v5e.py`` holds
#: the new value and what the loops carry.  PR 36 appends nine readers of the
#: decoder layers' part scopes: three tests pin what ``sdar-bd4-8k`` and
#: ``kanana2-8k`` list and PR 34's seven as the last entries
#: (``test_benchmark_part_scopes.py`` ends with the same assertions brought up
#: to date, and asserts its own entries by name).  PR 37 lets the
#: recomputed layers keep what fits the chip: the compiled steps hold 7.995
#: (``qwen3next-8k``), 9.043 (``kanana2-8k``) and 7.891 GB (``sdar-bd4-8k``:
#: less than before, so PR 33's pin of ``test_benchmark_sdar_v5e.py``'s band
#: under 8.0 is lifted), and no product of ``sdar-bd4-8k`` runs a second
#: time; ``test_benchmark_keep_v5e.py`` holds the four assertions brought up
#: to date.  PR 38 appends the ninth cell (``mellum2-16k``) and eight
#: metrics, and the cell to eighteen accepted lists: one test of
#: ``test_benchmark_kanana2.py`` pins the eight cells and two of
#: ``test_benchmark_part_scopes.py`` the lists of PR 36 and its nine readers
#: as the last entries (``test_benchmark_mellum2.py`` ends with the same
#: assertions brought up to date).  Strict,
#: so that the `benchmark` PR which brings the pins up to date has to take
#: this list out with them.
PINNED_TO_AN_EARLIER_BENCHMARK = {
    "test_benchmark_form.py::test_the_tiny_benchmark_keeps_the_form":
        "7 cells allowed one four-chip cell; with 8 the toy one is no fault",
    "test_benchmark_parts.py::test_new_metrics_are_entries_with_files":
        "flash_*_ms, flash_ms and grad_pack_ms now list qwen3next-8k too",
    "test_benchmark_harness.py::"
    "test_every_cell_of_the_real_benchmark_finds_its_files":
        "the expected cells lack qwen3next-8k",
    "test_benchmark_qwen3_next.py::"
    "test_which_cells_list_the_flash_parts_the_pack_and_the_update":
        "PR 26's eight metrics are no longer the last: loss_ms follows",
    "test_benchmark_loss.py::"
    "test_loss_ms_is_the_last_entry_after_pr_26s_eight":
        "loss_ms lists the two new cells and PR 30's three readers follow it",
    "test_benchmark_qwen3_next.py::"
    "test_qwen_metrics_are_entries_of_their_one_cell":
        "moe_ms, moe_route_ms and moe_tiles now list sdar-bd4-8k too",
    "test_benchmark_qwen3_next.py::"
    "test_every_cell_of_the_benchmark_finds_its_files_the_fifth_too":
        "the expected cells lack sdar-bd4-8k and gpt2s-4k",
    "test_benchmark_sdar_v5e.py::"
    "test_the_step_has_three_kernels_and_four_calls_a_layer":
        "a recomputed layer keeps o and lse: four forward calls, not eight",
    "test_benchmark_sdar.py::"
    "test_every_cell_of_the_benchmark_finds_its_files_all_seven":
        "the expected cells lack kanana2-8k",
    "test_benchmark_sdar.py::"
    "test_which_cells_list_which_metrics_after_pr_30":
        "the scope and kernel readers list kanana2-8k too, and PR 34's "
        "seven readers follow PR 30's six",
    "test_benchmark_form.py::"
    "test_a_fault_of_form_is_named[<lambda>-too many four-chip cells]":
        "with eight cells two may take four chips: a second is no fault",
    "test_benchmark_recompute_v5e.py::"
    "test_what_is_kept_fits_beside_the_benchmarks_weights[sdar-bd4-8k]":
        "one accumulator a layer, not one a group: hbm_gb 9.055, under the "
        "band round 9.665",
    "test_benchmark_sdar.py::test_what_the_two_new_cells_report":
        "sdar-bd4-8k lists five of PR 36's readers of the part scopes too",
    "test_benchmark_kanana2.py::"
    "test_which_cells_list_which_metrics_after_pr_34":
        "PR 34's seven readers are no longer the last: PR 36's nine follow",
    "test_benchmark_kanana2.py::test_what_the_new_cell_reports":
        "kanana2-8k lists six of PR 36's readers of the part scopes too",
    "test_benchmark_recompute_v5e.py::"
    "test_what_is_kept_fits_beside_the_benchmarks_weights[qwen3next-8k]":
        "every named output of a layer is kept: hbm_gb 7.995, over the "
        "band round 6.582",
    "test_benchmark_recompute_v5e.py::"
    "test_the_layers_are_still_recomputed[sdar-bd4-8k]":
        "the projections' outputs are kept: what is recomputed there "
        "holds no product any more",
    "test_benchmark_moe_groups_v5e.py::"
    "test_the_step_holds_no_more_than_it_did[sdar-bd4-8k]":
        "hbm_gb 7.891, under the band round 9.055: with q kept XLA no "
        "longer holds the forward kernel's padded row statistics",
    "test_benchmark_moe_groups_v5e.py::"
    "test_the_step_holds_no_more_than_it_did[kanana2-8k]":
        "every named output of a layer is kept: hbm_gb 9.043, over the "
        "band round 7.497",
    "test_benchmark_kanana2.py::"
    "test_every_cell_of_the_benchmark_finds_its_files_all_eight":
        "the expected cells lack mellum2-16k",
    "test_benchmark_part_scopes.py::"
    "test_the_nine_readers_are_entries_with_files_by_name":
        "seven of PR 36's nine readers list mellum2-16k too",
    "test_benchmark_part_scopes.py::"
    "test_which_cells_list_which_metrics_after_pr_36":
        "PR 36's nine readers are no longer the last: PR 38's eight follow, "
        "and the scope and kernel readers list mellum2-16k too",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        for name, why in PINNED_TO_AN_EARLIER_BENCHMARK.items():
            if item.nodeid.endswith("benchmark/" + name):
                item.add_marker(pytest.mark.xfail(
                    reason=f"pinned to an earlier benchmark: {why}",
                    strict=True))
