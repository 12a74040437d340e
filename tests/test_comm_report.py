"""Collective-traffic report — the scaling-efficiency stand-in
(reference docs/benchmarks.rst:12-13 headline metric, modeled
analytically on the virtual mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models.mlp import MLP
from horovod_tpu.timeline.comm_report import (
    collective_report, hlo_collectives,
)
from horovod_tpu.training import init_train_state, make_train_step, shard_batch
from test_bench import _load_bench


def test_hlo_parser_counts_and_bytes():
    txt = """
  %ar = f32[1024,8]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[64]{0} all-gather(%y), dimensions={0}
  %done = f32[4]{0} all-reduce-done(%h)
"""
    cols = hlo_collectives(txt)
    assert cols["all-reduce"] == {"count": 1, "bytes": 1024 * 8 * 4}
    assert cols["all-gather"] == {"count": 1, "bytes": 64 * 2}


def test_hlo_parser_tiled_tpu_layouts():
    """Regression: TPU optimized HLO carries tiled layouts whose parens
    ('{1,0:T(8,128)}') aborted the shape match and silently zeroed the
    collective report."""
    txt = """
  %ar = f32[128,256]{1,0:T(8,128)} all-reduce(%x), replica_groups={}
  %ag = bf16[64,8]{1,0:T(16,128)(2,1)} all-gather(%y), dimensions={0}
  %start = (f32[32]{0:T(256)}, f32[32]{0:T(256)}) all-reduce-start(%z)
"""
    cols = hlo_collectives(txt)
    assert cols["all-reduce"]["count"] == 2
    assert cols["all-reduce"]["bytes"] == 128 * 256 * 4 + 32 * 4
    assert cols["all-gather"] == {"count": 1, "bytes": 64 * 8 * 2}


def test_report_finds_gradient_allreduce(hvd_init, rng):
    model = MLP(features=(32, 10))
    opt = optax.sgd(0.1)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    step = make_train_step(
        apply_fn=lambda v, a, train=True: model.apply(v, a),
        loss_fn=loss_fn, optimizer=opt, donate=False,
    )
    state = init_train_state(model, opt, jnp.zeros((2, 16)))
    x = shard_batch(rng.normal(size=(64, 16)).astype(np.float32))
    y = shard_batch(rng.integers(0, 10, size=(64,)).astype(np.int32))

    # the CPU mesh has no peak of its own: name the modelled chip's
    report = collective_report(lambda s, a, b: step(s, a, b), state, x, y,
                               peak_flops=197e12)
    assert "all-reduce" in report["collectives"]
    param_bytes = 4 * sum(
        l.size for l in jax.tree_util.tree_leaves(state.params)
    )
    # fused gradient allreduce + scalar loss allreduce; XLA may fold both
    # into one instruction or keep two — bytes must cover the gradients
    total = report["total_collective_bytes"]
    assert param_bytes <= total <= param_bytes + 1024
    assert report["scaling_model"][8] is not None
    # a TOY model's t_compute is microseconds, so the α (latency) term
    # legitimately drives 64-chip efficiency toward 0 — only bounds and
    # monotonicity are meaningful here; realistic curves are asserted in
    # test_latency_term_separates_fused_from_per_tensor below
    assert 0 <= report["scaling_model"][64] <= 1
    assert report["modeled_comm_seconds"][64] > 0
    # more chips -> monotonically no-better efficiency in the ring model
    effs = [report["scaling_model"][n] for n in (8, 16, 32, 64)]
    assert all(a >= b for a, b in zip(effs, effs[1:]))


def test_hlo_parser_fp8_and_c128_dtypes():
    """Regression: fp8 (f8e4m3fn / f8e5m2) and c128 collectives were
    missing from _DTYPE_BYTES, so quantized-allreduce traffic silently
    counted as 0 bytes in the report."""
    txt = """
  %q = f8e4m3fn[4096,256]{1,0} all-reduce(%x), replica_groups={}
  %q2 = f8e5m2[1024]{0} all-gather(%y), dimensions={0}
  %c = c128[32,8]{1,0} all-reduce(%z), replica_groups={}
"""
    cols = hlo_collectives(txt)
    assert cols["all-reduce"]["count"] == 2
    assert cols["all-reduce"]["bytes"] == 4096 * 256 * 1 + 32 * 8 * 16
    assert cols["all-gather"] == {"count": 1, "bytes": 1024 * 1}


def test_hlo_parser_fp8_async_start():
    """fp8 payloads must also survive the async -start tuple path (the
    form the TPU scheduler actually emits)."""
    txt = """
  %ars = (f8e4m3fn[8192]{0}, f8e4m3fn[8192]{0}, u32[]) all-reduce-start(%a), ...
"""
    cols = hlo_collectives(txt)
    assert cols["all-reduce"] == {"count": 1, "bytes": 8192}


def test_hlo_parser_async_start_forms():
    """Async -start shapes carry the payload twice; -done is skipped;
    multi-operand nested-tuple starts must parse (real-TPU HLO form)."""
    txt = """
  %cps = (f32[1024]{0}, f32[1024]{0}, u32[], u32[]) collective-permute-start(%x), ...
  %ars = ((f32[100]{0}, f32[50]{0}), (f32[100]{0}, f32[50]{0})) all-reduce-start(%a, %b), ...
  %ard = (f32[100]{0}, f32[50]{0}) all-reduce-done(%ars)
"""
    cols = hlo_collectives(txt)
    assert cols["collective-permute"]["bytes"] == 1024 * 4
    assert cols["all-reduce"] == {"count": 1, "bytes": 150 * 4}


def test_hlo_parser_asymmetric_async_start():
    """all-gather-start carries (small operand, big result): the payload
    is the result, not half the tuple."""
    txt = """
  %ag = (f32[128]{0}, f32[1024]{0}) all-gather-start(%x), dimensions={0}
  %rs = (f32[1024]{0}, f32[128]{0}) reduce-scatter-start(%y), ...
"""
    cols = hlo_collectives(txt)
    assert cols["all-gather"]["bytes"] == 1024 * 4
    assert cols["reduce-scatter"]["bytes"] == 1024 * 4


def test_hlo_parser_multidim_async_start():
    """Commas inside [dims] and {layout} must not split tuple elements."""
    txt = """
  %cps = (f32[128,256]{1,0}, f32[128,256]{1,0}, u32[], u32[]) collective-permute-start(%x), ...
"""
    cols = hlo_collectives(txt)
    assert cols["collective-permute"]["bytes"] == 128 * 256 * 4


def test_per_tensor_table_predicted_vs_measured():
    """The per-tensor cost table: predicted from the same α–β model the
    scaling curves and the replay what-ifs use, measured joined by
    tensor name, error surfaced."""
    from horovod_tpu.timeline.comm_report import (
        per_tensor_table, predict_collective_us,
    )

    tensors = {
        "g0": {"op": "all-reduce", "bytes": 4 * 1024 * 1024, "calls": 1},
        "g1": {"op": "all-gather", "bytes": 1024, "calls": 2},
    }
    table = per_tensor_table(tensors, 8,
                             measured_us={"g0": 300.0})
    assert set(table) == {"g0", "g1"}
    want_g0 = predict_collective_us("all-reduce", 4 * 1024 * 1024, 8)
    assert table["g0"]["predicted_us"] == pytest.approx(want_g0, abs=1e-3)
    assert table["g0"]["measured_us"] == 300.0
    assert "model_error_pct" in table["g0"]
    # no measurement for g1 -> prediction only
    assert "measured_us" not in table["g1"]
    # the α term scales with calls
    one = per_tensor_table({"g": {"op": "all-gather", "bytes": 1024,
                                  "calls": 1}}, 8)["g"]["predicted_us"]
    assert table["g1"]["predicted_us"] > one


def test_predict_collective_us_matches_model_scaling():
    """predict_collective_us IS model_scaling's per-op term — the two
    must never drift (the replay engine relies on this equality)."""
    from horovod_tpu.timeline.comm_report import (
        model_scaling, predict_collective_us,
    )

    cols = {"all-reduce": {"count": 3, "bytes": 10_000_000}}
    comm_seconds, _ = model_scaling(cols, None, sizes=(8,))
    want_us = comm_seconds[8] * 1e6
    got_us = predict_collective_us("all-reduce", 10_000_000, 8, calls=3)
    # model_scaling rounds to whole µs (round(t, 6) in seconds)
    assert got_us == pytest.approx(want_us, abs=1.0)


def test_latency_term_separates_fused_from_per_tensor():
    """The α (per-collective latency) term: one fused 100 MB allreduce
    beats 160 per-tensor allreduces of the same total bytes — the
    reference's fusion-buffer rationale, now visible in the model
    (SURVEY §2.1; reference fusion_buffer docs)."""
    from horovod_tpu.timeline.comm_report import model_scaling

    t_compute = 0.05  # a ResNet-50-class 50 ms step
    fused = {"all-reduce": {"count": 1, "bytes": 100_000_000}}
    per_tensor = {"all-reduce": {"count": 160, "bytes": 100_000_000}}
    _, eff_fused = model_scaling(fused, t_compute)
    _, eff_split = model_scaling(per_tensor, t_compute)
    for n in (8, 16, 32, 64):
        assert eff_fused[n] > eff_split[n]
    # realistic fused ResNet-50 stays in the reference's published band
    assert eff_fused[64] > 0.85
    # β term alone is ~size-independent for a ring: t_comm grows with
    # (n-1)/n; the split curve must degrade faster with n than fused
    assert (eff_fused[8] - eff_fused[64]) < (eff_split[8] - eff_split[64])


# ---------------------------------------------------------------------------
# wire-efficiency tier: dtype byte table + compression/two-level pricing
# ---------------------------------------------------------------------------
def test_dtype_bytes_table_pinned():
    """SATELLITE pin: the compressed-wire dtypes must be billed at their
    real sizes — a missing entry counts the collective as 0 bytes and
    the traffic report under-models exactly the payloads compression
    shrinks (int8/uint8 = 1, fp8 families = 1, bf16 = 2, f32 = 4)."""
    from horovod_tpu.timeline.comm_report import _DTYPE_BYTES, _array_bytes

    expected = {"s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "f8e4m3": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
                "pred": 1, "c64": 8, "c128": 16}
    for dtype, size in expected.items():
        assert _DTYPE_BYTES[dtype] == size, dtype
        # 128-element payload of each dtype bills exactly 128*size
        assert _array_bytes(f"{dtype}[128]") == 128 * size, dtype
    # a quantized-allreduce HLO result shape bills at 1 byte/element
    assert _array_bytes("s8[1024,1024]") == 1 << 20
    assert _array_bytes("f8e4m3fn[1024,1024]") == 1 << 20


def test_predict_collective_us_compression_pinned():
    """Compression cost curves, hand-computed at world 8 / ICI defaults
    (186 GB/s, 1 µs hop; COMPRESSION_MODEL: int8 = ¼ wire bytes +
    1 µs/MiB qd + one scalar scale all-reduce's α = 14 hops):

    64 MiB f32 flat:  1.75·64 MiB/186e9 + 14        = 645.40 µs
    64 MiB int8:      ¼·β(157.85) + 14 + 64 + 14    = 249.85 µs  (2.6x)
    1 MiB int8:       ¼·β(2.466) + 14 + 1 + 14      =  31.47 µs
    1 MiB f32 flat:   β(9.866) + 14                 =  23.87 µs
    — compression LOSES on small payloads (the scale-exchange α
    dominates), which is why the planner chooses per bucket."""
    from horovod_tpu.timeline.comm_report import predict_collective_us

    MiB = 1 << 20
    assert predict_collective_us("all-reduce", 64 * MiB, 8) == \
        pytest.approx(645.40, abs=0.01)
    assert predict_collective_us(
        "all-reduce", 64 * MiB, 8, compression="int8") == \
        pytest.approx(249.85, abs=0.01)
    # bf16: ½·β(631.40) + 14 + 32 qd, no scale exchange = 361.70 µs
    big_bf16 = predict_collective_us("all-reduce", 64 * MiB, 8,
                                     compression="bf16")
    assert big_bf16 == pytest.approx(361.70, abs=0.01)
    # small payload: int8 costs MORE than shipping f32
    small_raw = predict_collective_us("all-reduce", MiB, 8)
    small_int8 = predict_collective_us("all-reduce", MiB, 8,
                                       compression="int8")
    assert small_int8 == pytest.approx(31.47, abs=0.01)
    assert small_raw == pytest.approx(23.87, abs=0.01)
    assert small_int8 > small_raw
    # already-narrow payloads never bill below 1x (ratio clamps at 1)
    assert predict_collective_us(
        "all-reduce", MiB, 8, compression="bf16", orig_itemsize=2) >= \
        small_raw


def test_predict_collective_us_two_level_pinned():
    """Two-level shape (64 MiB, 8 ranks = 4 local x 2 cross, DCN
    defaults 25 GB/s / 10 µs hop): local RS+AG move 2·(3/4)·64 MiB on
    ICI (+ 6 ICI hops), the cross all-reduce moves (1/2)·2·16 MiB shard
    on DCN (+ 2 DCN hops); int8 shrinks ONLY the cross/DCN stage."""
    from horovod_tpu.timeline.comm_report import predict_collective_us

    MiB = 1 << 20
    tl = predict_collective_us("all-reduce", 64 * MiB, 8,
                               two_level=True, local_size=4)
    assert tl == pytest.approx(1238.29, abs=0.01)
    tl_int8 = predict_collective_us("all-reduce", 64 * MiB, 8,
                                    two_level=True, local_size=4,
                                    compression="int8")
    assert tl_int8 == pytest.approx(770.97, abs=0.01)
    # vs the honest multi-host flat baseline (the whole ring at DCN
    # bandwidth): two-level + int8 wins big
    flat_dcn = predict_collective_us("all-reduce", 64 * MiB, 8,
                                     ici_bytes_per_sec=25e9)
    assert flat_dcn > 2 * tl_int8
    # un-decomposable topologies fall back to the flat shape — the
    # model mirrors two_level_allreduce's runtime degrade
    flat = predict_collective_us("all-reduce", 64 * MiB, 8)
    for bad_local in (None, 1, 3, 8):
        assert predict_collective_us(
            "all-reduce", 64 * MiB, 8, two_level=True,
            local_size=bad_local) == pytest.approx(flat)


def test_model_scaling_with_compression_improves_efficiency():
    """The SCALING.md story: the same collective profile, modeled with
    int8 gradients, keeps more efficiency at every world size."""
    from horovod_tpu.timeline.comm_report import model_scaling

    cols = {"all-reduce": {"count": 4, "bytes": 100 * (1 << 20)}}
    _, eff_raw = model_scaling(cols, 0.05)
    _, eff_c = model_scaling(cols, 0.05, compression="int8")
    for n in (8, 16, 32, 64):
        assert eff_c[n] > eff_raw[n]
        assert 0.0 < eff_raw[n] < 1.0


# ---------------------------------------------------------------------------
# the one peak table (utils/flops.py) every MFU number divides by
# ---------------------------------------------------------------------------
def test_peak_table_keyed_by_device_kind(monkeypatch, hvd_init):
    """Known kind -> its published figures; unknown kind -> no default
    (the CPU mesh included); HVD_PEAK_FLOPS names a peak explicitly."""
    from horovod_tpu.utils import flops

    monkeypatch.delenv("HVD_PEAK_FLOPS", raising=False)
    assert flops.peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert flops.hbm_bytes_per_sec("TPU v5 lite") == pytest.approx(819e9)
    assert flops.peak_flops("TPU v9 imaginary") is None
    # the mesh here is 8 CPU devices: no peak, no MFU, and an error for
    # the callers that publish one
    assert flops.peak_flops() is None
    assert flops.hbm_bytes_per_sec() is None
    assert flops.image_model_mfu(2677.0) is None
    assert flops.transformer_mfu(10.0, 124_000_000, 12, 768, 1024) is None
    with pytest.raises(RuntimeError, match="no peak FLOP/s.*'cpu'"):
        flops.require_peak_flops()
    monkeypatch.setenv("HVD_PEAK_FLOPS", "123e12")
    assert flops.peak_flops() == pytest.approx(123e12)
    assert flops.require_peak_flops() == pytest.approx(123e12)


def test_collective_report_peak_single_sourced(monkeypatch):
    monkeypatch.setenv("HVD_PEAK_FLOPS", "111e12")
    rep = collective_report(lambda x: x * 2.0, np.ones(4, np.float32))
    assert rep["assumptions"]["peak_flops"] == pytest.approx(111e12)


def test_bench_mfu_through_utils_flops(monkeypatch, hvd_init):
    from horovod_tpu.utils import flops

    bench = _load_bench()
    # the comm report and the bench number share one peak: an explicit
    # peak moves both
    monkeypatch.setenv("HVD_PEAK_FLOPS", "197e12")
    want = round(flops.image_model_mfu(2677.0), 4)
    assert bench._mfu(2677.0) == pytest.approx(want)
    assert want == pytest.approx(2677.0 * 24.30e9 / 197e12, abs=1e-4)
    monkeypatch.setenv("HVD_PEAK_FLOPS", "98.5e12")
    assert bench._mfu(2677.0) == pytest.approx(
        round(2677.0 * 24.30e9 / 98.5e12, 4))
    # a device that is not in the table is an error in bench.py, never
    # a null and never a v5e default (this mesh is CPU)
    monkeypatch.delenv("HVD_PEAK_FLOPS")
    with pytest.raises(RuntimeError, match="no peak FLOP/s"):
        bench._mfu(2677.0)
