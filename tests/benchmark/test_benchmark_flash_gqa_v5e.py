"""The flash kernels with k and v at their own head count (PR 46), through
Mosaic's compiler for a described v5e at the shapes the five grouped-query
cells call them with, and the compiled steps of ``sdar-bd4-8k``,
``mellum2-16k`` and ``lfm2-8k-b2``: forward and dq keep the grid over q's
heads, dkv's runs over the kv heads with ``group`` times the steps and
writes dk and dv at ``hk`` heads; the steps hold k, v, dk and dv at ``hk``
heads alone and no sum over a group; ``sdar-bd4-8k``'s budget keeps k and v
as the kernels take them; and the calls at equal head counts lower to the
bodies the accepted files pin.  The values here are the ones the pinned
cases this PR turned red should be re-pointed to (PERF.md section 7).  No
chip is attached and nothing runs (a file of its own: only a ``benchmark``
PR edits one that is there)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import benchmark_tiny
from test_benchmark_flash_grid_v5e import (
    BLOCK_DIFFUSION_KERNELS, WINDOW_KERNELS_BEFORE_THE_TABLE, _digest, _mask)
from test_benchmark_keep_v5e import _kept_bytes
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, one_chip, topo)
from test_benchmark_mellum2_v5e import _bodies, _grads
from test_benchmark_sdar_v5e import CAUSAL_KERNELS_BEFORE_THE_MASK

CHIP_BYTES = 16 * 2 ** 30

# call: ([b, s, h, head size], kv heads, mask, the blocks the model names)
CALLS = {
    "sdar-bd4-8k": ((1, 16384, 32, 128), 4, ("block_diffusion", 4, 8192), {}),
    "mellum2-16k-window": ((1, 16384, 32, 128), 4, ("sliding_window", 1024),
                           {}),
    "mellum2-16k-full": ((1, 16384, 32, 128), 4, ("causal",), {}),
    "lfm2-8k-b2": ((2, 8192, 32, 64), 8, ("causal",), {}),
    "qwen3next-8k": ((1, 8192, 16, 256), 2, ("causal",), {"block_q": 512}),
    "nemotron3-8k": ((1, 8192, 32, 128), 2, ("causal",), {}),
}


def _bounds(body):
    return [int(n) for n in re.search(
        r"iteration_bounds = array<i64: ([\d, ]+)>", body).group(1).split(",")]


@pytest.mark.parametrize("call", sorted(CALLS))
def test_the_cells_calls_compile_at_their_head_counts(
        call, one_chip, no_compile_cache, monkeypatch):  # noqa: F811
    from horovod_tpu.ops import flash_attention as fa

    (b, s, h, d), hk, mask, blocks = CALLS[call]
    mask, group = _mask(*mask), h // hk
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hk, d), jnp.bfloat16, sharding=one_chip)
    bodies, lowered = _bodies(monkeypatch, lambda: _grads(
        dict(mask=mask, **blocks)).lower(q, kv, kv))
    assert len(bodies) == 3
    default_q, default_k = fa.default_blocks(d, mask)
    tiles = (blocks.get("block_q", default_q), default_k)
    steps = fa.grid_census(s, s, *tiles, mask, group=group)
    assert steps == fa.grid_census(s, s, *tiles, mask)
    if mask.kind == "sliding_window":
        rows, _, _, kv_steps, _ = fa._kv_grid(s, s, *tiles, mask, (0, 0))
        _, _, keys, q_steps = fa._q_grid(s, s, *tiles, mask, (0, 0))
        want = [[b, h, s // rows, kv_steps]] * 2 + [
            [b, hk, s // keys, group * q_steps]]
        words = [2, 2, 2]
    else:
        # the flattened grid: forward and dq over q's heads, three words a
        # pair; dkv over the kv heads, its group's passes written out, four
        want = [[b, h, steps["fwd"]["live"]], [b, h, steps["dq"]["live"]],
                [b, hk, group * steps["dkv"]["live"]]]
        words = [2 + 3 * want[0][2], 2 + 3 * want[1][2], 2 + 4 * want[2][2]]
        assert all(steps[k]["launched"] == steps[k]["live"] for k in steps)
    for body, grid, n in zip(bodies, want, words):
        assert _bounds(body) == grid
        assert f"memref<{n}xi32, #tpu.memory_space<smem>>" in body
    lowered.compile()
    # dq as q; dk and dv as k and v came: the kv heads, summed in the kernel
    assert [i.shape for i in lowered.out_info] == [
        (b, s, h, d), (b, s, hk, d), (b, s, hk, d)]
    text = lowered.as_text()
    assert f"tensor<{b}x{h}x{s}x{d}xbf16>" in text
    assert f"tensor<{b}x{hk}x{s}x{d}xbf16>" in text
    # the dkv kernel's results: two arrays at the kv heads, and nothing at
    # q's heads for a sum to fold afterwards
    dkv = [line for line in text.splitlines()
           if "tpu_custom_call" in line and "hvd_flash_dkv" in line]
    assert len(dkv) == 1
    results = dkv[0].rsplit("->", 1)[1]
    assert results.count(f"tensor<{b}x{hk}x{s}x{d}xbf16>") == 2
    assert f"tensor<{b}x{h}x{s}x{d}" not in results


@pytest.mark.parametrize("shape,block_q", list(CAUSAL_KERNELS_BEFORE_THE_MASK))
def test_equal_head_counts_lower_to_the_pinned_causal_bodies(
        one_chip, no_compile_cache, monkeypatch, shape, block_q):  # noqa: F811
    """A group of 1 is the parent's program: the digests of
    ``test_benchmark_sdar_v5e.py``."""
    blocks = {} if block_q is None else {"block_q": block_q}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    bodies, _ = _bodies(monkeypatch, lambda: _grads(
        dict(causal=True, **blocks)).lower(x, x, x))
    assert _digest(bodies) == CAUSAL_KERNELS_BEFORE_THE_MASK[shape, block_q]


@pytest.mark.parametrize("mask,digest", [
    (("sliding_window", 1024), WINDOW_KERNELS_BEFORE_THE_TABLE),
    (("block_diffusion", 4, 8192), BLOCK_DIFFUSION_KERNELS[1]),
], ids=["window", "block_diffusion"])
def test_equal_head_counts_lower_to_the_pinned_masked_bodies(
        one_chip, no_compile_cache, monkeypatch, mask, digest):  # noqa: F811
    """And the digests of ``test_benchmark_flash_grid_v5e.py``, at ``[1,
    16384, 32, 128]``."""
    x = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    bodies, _ = _bodies(monkeypatch, lambda: _grads(
        dict(mask=_mask(*mask))).lower(x, x, x))
    assert _digest(bodies) == digest


# cell: (q heads, kv heads, rows a call, head size, flash layers, the band
# round ``hbm_gb`` as compiled here before the chip, float32 parameters the
# benchmark keeps beside the state)
STEPS = {
    "sdar-bd4-8k": (32, 4, (1, 16384), 128, 4, (9.5e9, 9.7e9), 456_346_624),
    "mellum2-16k": (32, 4, (1, 16384), 128, 4, (8.05e9, 8.25e9),
                    340_349_184),
    "lfm2-8k-b2": (32, 8, (2, 8192), 64, 2, (10.5e9, 10.9e9), None),
}


def _kv_groups():
    from horovod_tpu import metrics

    return {(s["labels"]["kernel"], s["labels"]["q_heads"],
             s["labels"]["kv_heads"]): s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                "hvd_flash_kv_group_traced_total", {}).get("samples", [])}


def _compile_step(cell_name, topo):  # noqa: F811
    """``cell_name``'s step compiled for one described chip, as ``run.py``
    builds it."""
    import horovod_tpu as hvd
    from horovod_tpu import core
    from horovod_tpu.training import init_train_state, make_train_step

    from benchmarks.harness.spec import Spec

    cell = Spec(benchmark_tiny.REPO).cell(cell_name)
    cfg, mix, adapter = cell.cfg, cell.mix, cell.adapter
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
    """``{cell: (the compiled step, what the two counters read while it
    was traced)}``."""
    from horovod_tpu import metrics

    got = {}
    enabled = metrics.registry.enabled
    metrics.registry.enabled = True
    try:
        for cell in STEPS:
            kept, groups = _kept_bytes(), _kv_groups()
            step = _compile_step(cell, topo)
            got[cell] = (
                step,
                {k: v - kept.get(k, 0) for k, v in _kept_bytes().items()},
                {k: v - groups.get(k, 0) for k, v in _kv_groups().items()
                 if v != groups.get(k, 0)})
    finally:
        metrics.registry.enabled = enabled
    return got


@pytest.mark.parametrize("cell", sorted(STEPS))
def test_the_step_holds_k_and_v_at_the_kv_heads_alone(cell, steps):
    """Every flash call of the compiled step takes k and v, and dkv gives dk
    and dv, as ``[b, hk, s, d]``; q, o, do and dq are the only arrays at
    q's heads a kernel touches; nothing in the step has the shape of a
    repeat or of its transpose's sum (``[.., hk, group, ..]``)."""
    h, hk, (b, s), d, layers, _, _ = STEPS[cell]
    text = steps[cell][0].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    at_q, at_kv = f"bf16[{b},{h},{s},{d}]", f"bf16[{b},{hk},{s},{d}]"
    # (arrays at q's heads, arrays at the kv heads) a call, results and
    # operands: fwd q, o | k, v; dq q, do, dq | k, v; dkv q, do | k, v, dk, dv
    want = {"hvd_flash_fwd": (2, 2), "hvd_flash_dq": (3, 2),
            "hvd_flash_dkv": (2, 4)}
    for kernel, counts in want.items():
        mine = [line for line in calls if f"%{kernel}" in line.split("=")[0]]
        assert len(mine) == layers, kernel
        for line in mine:
            assert (line.count(at_q), line.count(at_kv)) == counts, kernel
    group = h // hk
    grouped = re.compile(rf"\[(?:{b},)?{s},{hk},{group},{d}\]"
                         rf"|\[(?:{b},)?{hk},{group},{s},{d}\]")
    assert not grouped.search(text)


@pytest.mark.parametrize("cell", sorted(STEPS))
def test_every_kernel_call_of_the_step_was_grouped(cell, steps):
    """``hvd_flash_kv_group_traced_total``: the three kernels, at the cell's
    two head counts and no other."""
    h, hk = STEPS[cell][:2]
    read = steps[cell][2]
    assert set(read) == {(kernel, str(h), str(hk))
                         for kernel in ("fwd", "dq", "dkv")}
    assert all(n >= 1 for n in read.values())


def test_sdars_budget_keeps_k_and_v_as_the_kernels_take_them(steps):
    """At 4 heads the two names are 2 x 16.8 MB a layer, 134 MB over the
    four layers, inside the 141 MB the budget had left: every name kept,
    nothing skipped (``test_benchmark_keep_v5e.py`` pins the five names and
    1 073 741 824 skipped bytes of 32 heads)."""
    read = {k: v for k, v in steps["sdar-bd4-8k"][1].items() if v}
    assert set(read) == {
        "hvd_moe_routing", "hvd_keep_out_proj", "hvd_keep_q_proj",
        "hvd_flash_q", "hvd_keep_kv_proj", "hvd_flash_k", "hvd_flash_v"}
    assert read["hvd_flash_k"] == read["hvd_flash_v"] \
        == 4 * 16384 * 4 * 128 * 2
    assert read["hvd_flash_q"] == 4 * 16384 * 32 * 128 * 2


@pytest.mark.parametrize("cell", sorted(STEPS))
def test_the_steps_fit_beside_the_benchmarks_weights(cell, steps):
    """``hbm_gb`` as a traced run prints it (arguments + temporaries) in a
    band round the value compiled before the chip: ``sdar-bd4-8k`` 9.596
    (7.892 at the parent: with k and v kept too XLA holds the forward
    kernels' padded row statistics from the forward pass to the backward
    again, as it did with nothing kept, 9.052), ``mellum2-16k`` 8.140
    (9.217: the kept k and v are an eighth), ``lfm2-8k-b2`` as it was to 1%;
    over the quarter of the chip a cell must fill and, with the benchmark's
    float32 weights, under three quarters."""
    *_, (low, high), parameters = STEPS[cell]
    mem = steps[cell][0].memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{cell}: hbm {hbm}")
    assert low < hbm < high, hbm
    assert hbm > 0.25 * CHIP_BYTES
    if parameters is not None:
        assert hbm + 4 * parameters < 0.75 * CHIP_BYTES
