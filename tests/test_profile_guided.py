"""Profile-guided tuning: the replay→autotune closed loop.

The pinned numbers come from the hand-computed autotune fixture
(horovod_tpu/timeline/replay/fixture.py AUTOTUNE_EXPECTED): a symmetric
2-rank step with three gradients whose two-thread replay puts the
optimal plan at exactly 2 buckets [[g0], [g1, g2]] and 300 µs (baseline
440 µs) — recovered by the bucket search, applied by the tuner, verified
against realized step times, and rolled back on an injected regression.
"""

import importlib.util as _ilu
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.optim.autotune import ParameterManager, TunableParams
from horovod_tpu.optim.profile_guided import (
    FusionPlanSpec,
    ProfileGuidedTuner,
    plan_from_summary,
    plan_from_trace,
    predicted_score_fn,
)
from horovod_tpu.ops.fusion import FusionPlan, tree_leaf_names
from horovod_tpu.run.http_client import get_autotune, put_autotune_plan
from horovod_tpu.run.http_server import RendezvousServer
from horovod_tpu.timeline.replay import analyze
from horovod_tpu.timeline.replay.fixture import (
    AUTOTUNE_EXPECTED, write_autotune_fixture_trace,
)
from horovod_tpu.timeline.replay.simulator import (
    CostModel, bucket_plan_search, bucketed_dag, comm_channel_order,
)
from horovod_tpu.timeline.replay.stitcher import stitch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def autotune_dir(tmp_path):
    write_autotune_fixture_trace(str(tmp_path))
    return str(tmp_path)


@pytest.fixture()
def fixture_cm():
    return CostModel(world=2,
                     hop_latency_us=AUTOTUNE_EXPECTED["hop_latency_us"])


@pytest.fixture()
def server():
    s = RendezvousServer()
    s.start()
    yield s
    s.stop()


# ---------------------------------------------------------------------------
# bucket search recovers the hand-computed optimum
# ---------------------------------------------------------------------------
def test_bucket_search_recovers_optimal_plan(autotune_dir, fixture_cm):
    _art, dags = stitch(autotune_dir)
    results = bucket_plan_search(dags[0], fixture_cm)
    by_k = {r["num_buckets"]: r for r in results}
    for k, us in AUTOTUNE_EXPECTED["bucket_search_us"].items():
        assert by_k[k]["predicted_step_us"] == pytest.approx(us, abs=1e-3)
    best = results[0]
    assert best["num_buckets"] == AUTOTUNE_EXPECTED["optimal_num_buckets"]
    assert best["buckets"] == AUTOTUNE_EXPECTED["optimal_buckets"]


def test_what_if_emits_machine_readable_plan(autotune_dir, fixture_cm):
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    wi = summary["steps"][0]["what_if"]
    assert wi["baseline_replay_us"] == pytest.approx(
        AUTOTUNE_EXPECTED["baseline_us"])
    by_name = {s["scenario"]: s for s in wi["scenarios"]}
    sc = by_name["fuse_buckets_2"]
    assert sc["predicted_step_us"] == pytest.approx(
        AUTOTUNE_EXPECTED["uncompressed_step_us"])
    assert sc["plan"]["buckets"] == AUTOTUNE_EXPECTED["optimal_buckets"]
    assert sc["plan"]["overlap"] is True
    # the staged wire-format choice on the winning partition — the plan
    # the closed loop applies (compression ranked against fusion on the
    # same scale)
    cc = by_name["fuse_buckets_2_compressed"]
    assert cc["predicted_step_us"] == pytest.approx(
        AUTOTUNE_EXPECTED["predicted_step_us"])
    assert cc["plan"]["buckets"] == AUTOTUNE_EXPECTED["optimal_buckets"]
    assert cc["plan"]["compression"] == \
        AUTOTUNE_EXPECTED["optimal_compression"]
    # whole-wire compression what-ifs, priced by predict_collective_us
    assert by_name["compress_int8"]["predicted_step_us"] == pytest.approx(
        AUTOTUNE_EXPECTED["compress_int8_us"])
    assert "compress_fp8" in by_name and "compress_bf16" in by_name
    # the serial fuse-all ceiling and the free-channel overlap bound
    assert by_name["fuse_all_comm"]["predicted_step_us"] == pytest.approx(
        AUTOTUNE_EXPECTED["fuse_all_us"])
    assert by_name["overlap_comm"]["predicted_step_us"] == pytest.approx(
        AUTOTUNE_EXPECTED["overlap_us"])


def test_analyze_plan_search_opt_out(autotune_dir, fixture_cm):
    """plan_search=False (hvd_replay --no-plan-search) skips the bucket
    search — the expensive what-if — while the diagnostic scenarios
    stay; last_steps=1 (the in-job path) replays only the newest step."""
    summary = analyze(autotune_dir, cost_model=fixture_cm,
                      plan_search=False).summary
    wi = summary["steps"][0]["what_if"]
    assert wi["bucket_search"] == []
    names = {s["scenario"] for s in wi["scenarios"]}
    assert not any(n.startswith("fuse_buckets_") for n in names)
    assert "overlap_comm" in names and "fuse_all_comm" in names
    latest = analyze(autotune_dir, cost_model=fixture_cm,
                     last_steps=1).summary
    all_steps = analyze(autotune_dir, cost_model=fixture_cm).summary
    assert len(latest["steps"]) == 1
    assert latest["steps"][0]["step"] == \
        max(s["step"] for s in all_steps["steps"])


def test_plan_from_trace_end_to_end(autotune_dir, fixture_cm):
    plan = plan_from_trace(autotune_dir, cost_model=fixture_cm)
    assert plan is not None
    assert plan.buckets == AUTOTUNE_EXPECTED["optimal_buckets"]
    assert plan.predicted_step_us == pytest.approx(
        AUTOTUNE_EXPECTED["predicted_step_us"])
    assert plan.baseline_step_us == pytest.approx(
        AUTOTUNE_EXPECTED["baseline_us"])
    assert plan.predicted_speedup_pct == pytest.approx(
        AUTOTUNE_EXPECTED["predicted_speedup_pct"], abs=0.05)
    # round-trips through the wire format
    assert FusionPlanSpec.from_dict(plan.to_dict()) == plan


def test_bucketed_dag_uncovered_comms_ride_as_singletons(autotune_dir,
                                                         fixture_cm):
    _art, dags = stitch(autotune_dir)
    dag = dags[0]
    order = comm_channel_order(dag)
    assert len(order) == 3
    # bucket only the first collective: the other two stay singleton
    bdag, bucket_ids, chain = bucketed_dag(dag, fixture_cm, [[order[0]]])
    assert len(bucket_ids) == 3
    comm_nodes = [n for n in bdag.nodes if n.kind == "comm"]
    assert len(comm_nodes) == 3
    # channel chain serializes them in dispatch order
    assert chain[bucket_ids[1]] == [bucket_ids[0]]
    assert chain[bucket_ids[2]] == [bucket_ids[1]]


# ---------------------------------------------------------------------------
# FusionPlan: explicit buckets + named-bucket matching
# ---------------------------------------------------------------------------
def test_fusion_plan_explicit_buckets():
    leaves = [jnp.zeros((4,), jnp.float32) for _ in range(5)]
    plan = FusionPlan(leaves, explicit_buckets=[[0, 2], [1]])
    # unclaimed leaves 3, 4 appended as singletons
    assert plan.buckets == [[0, 2], [1], [3], [4]]
    assert plan.explicit


def test_fusion_plan_explicit_splits_mixed_dtypes():
    leaves = [jnp.zeros((4,), jnp.float32), jnp.zeros((4,), jnp.bfloat16),
              jnp.zeros((4,), jnp.float32)]
    plan = FusionPlan(leaves, explicit_buckets=[[0, 1, 2]])
    # one concat per dtype: f32 pair together, bf16 alone
    assert sorted(map(sorted, plan.buckets)) == [[0, 2], [1]]


def test_fusion_plan_explicit_rejects_bad_indices():
    leaves = [jnp.zeros((4,), jnp.float32)] * 2
    with pytest.raises(ValueError, match="two buckets"):
        FusionPlan(leaves, explicit_buckets=[[0], [0]])
    with pytest.raises(ValueError, match="leaf 7"):
        FusionPlan(leaves, explicit_buckets=[[7]])


def test_fusion_plan_from_named_buckets_suffix_match():
    leaves = [jnp.zeros((4,), jnp.float32)] * 3
    names = ["dense/kernel", "dense/bias", "head/kernel"]
    # trace names are the trailing component; unknown names are ignored
    plan = FusionPlan.from_named_buckets(
        leaves, names, [["bias", "head/kernel"], ["no_such_tensor"]])
    assert plan.buckets == [[1, 2], [0]]


def test_fused_allreduce_rejects_under_covering_plan(hvd_init):
    """A stale plan built for fewer tensors than the call passes must
    fail loudly instead of returning None for the uncovered gradients."""
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.fusion import FusionPlan, fused_allreduce

    short = [jnp.zeros((4,), jnp.float32)] * 2
    stale = FusionPlan(short, explicit_buckets=[[0, 1]])

    @hvd.spmd(in_specs=P(hvd.AXIS), out_specs=P(hvd.AXIS))
    def step(t):
        tensors = [t[0], t[0] * 2, t[0] * 3]
        return fused_allreduce(tensors, plan=stale)[0][None]

    with pytest.raises(ValueError, match="covers 2 tensors"):
        step(np.zeros((8, 4), np.float32))


def test_tree_leaf_names_slash_paths():
    tree = {"a": {"w": jnp.zeros(2), "b": jnp.zeros(2)}, "c": jnp.zeros(2)}
    names = tree_leaf_names(tree)
    assert set(names) == {"a/w", "a/b", "c"}


def test_allreduce_pytree_named_buckets_matches_unfused(hvd_init, rng):
    """An explicit plan changes the bucketing, never the math."""
    import jax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.fusion import allreduce_pytree

    tree = {"w": rng.normal(size=(4, 4)).astype(np.float32),
            "b": rng.normal(size=(4,)).astype(np.float32),
            "v": rng.normal(size=(2,)).astype(np.float32)}
    stacked = jax.tree_util.tree_map(
        lambda leaf: np.stack([leaf * (r + 1) for r in range(8)]), tree)

    @hvd.spmd(in_specs=P(hvd.AXIS), out_specs=P(hvd.AXIS))
    def step(t):
        per_rank = jax.tree_util.tree_map(lambda a: a[0], t)
        out = allreduce_pytree(per_rank, op=hvd.Average,
                               named_buckets=[["b", "v"], ["w"]])
        return jax.tree_util.tree_map(lambda a: a[None], out)

    out = step(stacked)
    scale = np.mean([r + 1 for r in range(8)])
    for key in ("w", "b", "v"):
        got = np.asarray(jax.device_get(out[key]))[0]
        np.testing.assert_allclose(got, tree[key] * scale, rtol=1e-5)


# ---------------------------------------------------------------------------
# TunableParams: the categorical-per-GP split is explicit
# ---------------------------------------------------------------------------
def test_as_vector_excludes_categorical_dims():
    a = TunableParams(fusion_threshold_bytes=1 << 24,
                      hierarchical_allreduce=False)
    b = TunableParams(fusion_threshold_bytes=1 << 24,
                      hierarchical_allreduce=True)
    # the GP input is identical; the CATEGORY differs — a flipped flag
    # selects a different GP instead of silently sharing one
    np.testing.assert_array_equal(a.as_vector(), b.as_vector())
    assert a.category() != b.category()
    assert "hierarchical_allreduce" in TunableParams.CATEGORICAL_DIMS
    assert "hierarchical_allreduce" not in TunableParams.CONTINUOUS_DIMS


def test_observations_land_in_per_category_gps(monkeypatch):
    monkeypatch.setenv("HVD_AUTOTUNE_PYTHON", "1")
    pm = ParameterManager(enabled=True, warmup_samples=0,
                          steps_per_sample=1, max_samples=6)
    while not pm.frozen:
        # score favors hierarchical so both categories get visited
        s = 2e9 if pm.current.hierarchical_allreduce else 1e9
        pm.record_step(s, 1.0)
    counts = {cat: len(bo.xs) for cat, bo in pm._bo.items()}
    assert set(counts) == {(False,), (True,)}
    assert all(c > 0 for c in counts.values())
    assert sum(counts.values()) == 6
    # every observation in the (True,) GP scored the hierarchical surface
    assert all(y == pytest.approx(2e9) for y in pm._bo[(True,)].ys)
    assert all(y == pytest.approx(1e9) for y in pm._bo[(False,)].ys)


def test_initial_category_outside_tuned_set_gets_own_gp(monkeypatch):
    """tune_hierarchical=False pins the flag: the pinned category gets
    its own GP AND the proposal rotation must never flip the flag (it
    used to alternate hierarchical on/off every sample, re-jitting and
    overriding the caller's explicit pin)."""
    monkeypatch.setenv("HVD_AUTOTUNE_PYTHON", "1")
    pm = ParameterManager(enabled=True, tune_hierarchical=False,
                          warmup_samples=0, steps_per_sample=1,
                          max_samples=4,
                          initial=TunableParams(
                              hierarchical_allreduce=True))
    assert (True,) in pm._bo
    while not pm.frozen:
        assert pm.current.hierarchical_allreduce is True
        pm.record_step(1e9, 1.0)    # must not KeyError into a wrong GP
    assert pm.current.hierarchical_allreduce is True


# ---------------------------------------------------------------------------
# warm start: fewer observations to converge than cold
# ---------------------------------------------------------------------------
def _surface(p: TunableParams) -> float:
    x = np.log2(p.fusion_threshold_bytes)
    return 1e9 * np.exp(-0.5 * ((x - 24.0) / 1.5) ** 2)


def _observations_to_band(warm: bool) -> int:
    pm = ParameterManager(enabled=True, warmup_samples=0,
                          steps_per_sample=1, max_samples=12,
                          tune_hierarchical=False)
    if warm:
        assert pm.warm_start(_surface, n_points=8) == 8
    k = 0
    while not pm.frozen:
        k += 1
        pm.record_step(_surface(pm.current), 1.0)
        if abs(np.log2(pm.current.fusion_threshold_bytes) - 24.0) < 1.0:
            return k
    return k


def test_warm_start_converges_in_fewer_observations():
    """The satellite's pin: on the same synthetic cost surface the
    warm-started GP reaches the optimum band in strictly fewer real
    observations than the cold one (both deterministic, fixed seeds)."""
    cold = _observations_to_band(warm=False)
    warm = _observations_to_band(warm=True)
    assert warm < cold, (warm, cold)


def test_warm_start_does_not_consume_sample_budget():
    pm = ParameterManager(enabled=True, warmup_samples=0,
                          steps_per_sample=1, max_samples=3,
                          tune_hierarchical=False)
    pm.warm_start(_surface, n_points=8)
    assert pm._samples_seen == 0
    for _ in range(3):
        pm.record_step(_surface(pm.current), 1.0)
    assert pm.frozen  # exactly max_samples real observations


def test_warm_start_prior_cannot_outscale_live_observations():
    """The α–β prior predicts comm-only bytes/sec; live samples score
    whole-step bytes/sec — orders of magnitude apart.  The prior must be
    anchored into live units at the first real sample (contributing
    shape, not an unbeatable score): the frozen best can never be a raw
    model value that no measurement could ever exceed."""
    pm = ParameterManager(enabled=True, warmup_samples=0,
                          steps_per_sample=1, max_samples=4,
                          tune_hierarchical=False,
                          initial=TunableParams(
                              fusion_threshold_bytes=1 << 25))
    pm.warm_start(lambda p: 1000.0 * _surface(p), n_points=8)

    def live(p):                        # reality: 1000x smaller units
        return _surface(p) / 10.0

    while not pm.frozen:
        pm.record_step(live(pm.current), 1.0)
    bo = pm._bo[pm.current.category()]
    assert bo.prior_scale is not None   # anchored at the first sample
    _, best_y = bo.best()
    # anchored prior max = live-unit scale; the raw 1000x model value
    # (>= 1e11 at its peak) can no longer win the argmax by units alone
    assert best_y < 1e9
    # and the anchor preserves the shape: prior argmax is still at 2^24
    xs, ys = bo._merged()
    assert abs(float(bo._denorm(xs[int(np.argmax(ys))])[0]) - 24.0) < 2.0


def test_frozen_best_is_a_measured_point():
    """best() must argmax over LIVE observations: the prior scale anchors
    ONE point into live units, so elsewhere on the curve the scaled model
    can still out-score reality — _freeze would otherwise pin the knobs
    to a never-measured prediction that measurements contradicted."""
    from horovod_tpu.optim.autotune import BayesianOptimization

    bo = BayesianOptimization([(20.0, 28.0)])
    bo.observe_prior([28.0], 200.0)     # model over-predicts at 2^28
    bo.observe_prior([24.0], 100.0)
    bo.set_prior_scale(1.0)             # scaled priors still dwarf live
    bo.observe([24.0], 1.5)             # measured best
    bo.observe([28.0], 1.0)             # reality contradicts the model
    vec, y = bo.best()
    assert float(vec[0]) == pytest.approx(24.0)
    assert y == pytest.approx(1.5)
    # with no live observations at all, priors are the fallback
    cold = BayesianOptimization([(20.0, 28.0)])
    cold.observe_prior([28.0], 200.0)
    cold.observe_prior([24.0], 100.0)
    vec, _ = cold.best()
    assert float(vec[0]) == pytest.approx(28.0)


def test_predicted_score_fn_prior_shape():
    """The α–β prior: smaller thresholds pay more α (more buckets) —
    score must be monotone non-decreasing in threshold, finite, and
    positive (the GP can always fit it)."""
    fn = predicted_score_fn(256e6, world=8, ici_bytes_per_sec=186e9,
                            hop_latency_us=1.0)
    xs = [fn(TunableParams(fusion_threshold_bytes=1 << e))
          for e in range(20, 29)]
    assert all(np.isfinite(x) and x > 0 for x in xs)
    assert xs == sorted(xs)


# ---------------------------------------------------------------------------
# the closed loop: apply → verify / rollback
# ---------------------------------------------------------------------------
def _loop(summary, step_us_sequence, **kw):
    applied = []
    kw.setdefault("rollback", True)
    tuner = ProfileGuidedTuner(
        analyze_fn=lambda: summary, apply_fn=applied.append,
        window_steps=4, guard_band_pct=10.0, **kw)
    for us in step_us_sequence:
        tuner.on_step(us * 1e-6)
    return tuner, applied


def test_loop_converges_to_known_optimal_plan(autotune_dir, fixture_cm):
    """Acceptance pin: the synthetic-DAG job recovers the known-optimal
    fusion plan and realized speedup lands within the guard band of
    predicted."""
    from horovod_tpu import metrics

    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    best = AUTOTUNE_EXPECTED["predicted_step_us"]
    tuner, applied = _loop(summary, [base] * 4 + [best] * 4)
    assert isinstance(applied[0], FusionPlanSpec)
    assert applied[0].buckets == AUTOTUNE_EXPECTED["optimal_buckets"]
    assert tuner.history[-1]["outcome"] == "verified"
    realized = tuner.history[-1]["realized_speedup_pct"]
    predicted = AUTOTUNE_EXPECTED["predicted_speedup_pct"]
    assert abs(realized - predicted) <= 10.0
    assert metrics.AUTOTUNE_PREDICTED_SPEEDUP.get() == pytest.approx(
        predicted, abs=0.05)
    assert metrics.AUTOTUNE_REALIZED_SPEEDUP.get() == pytest.approx(
        realized, abs=0.05)
    assert not tuner.active  # loop settles after verification


def test_loop_rolls_back_injected_regression(autotune_dir, fixture_cm):
    from horovod_tpu import metrics

    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    before = metrics.AUTOTUNE_ROLLBACKS.get()
    # verify window realizes NO speedup: shortfall 31.8% > 10% band
    tuner, applied = _loop(summary, [base] * 8)
    assert tuner.history[-1]["outcome"] == "rolled_back"
    assert applied[-1] is None          # restored threshold bucketing
    assert tuner.plan is None
    assert metrics.AUTOTUNE_ROLLBACKS.get() == before + 1


def test_loop_keeps_regressed_plan_when_rollback_disabled(autotune_dir,
                                                          fixture_cm):
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    tuner, applied = _loop(summary, [base] * 8, rollback=False)
    assert tuner.history[-1]["outcome"] == "verified"
    assert applied[-1] is not None


def test_loop_verifies_despite_host_overhead_outside_the_dag(
        autotune_dir, fixture_cm):
    """The simulator's speedup is a fraction of the DAG replay makespan;
    the measured window also carries host time outside the DAG.  A plan
    that delivers its full predicted absolute saving must verify even
    when that overhead halves the realized percentage."""
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    saved = base - AUTOTUNE_EXPECTED["predicted_step_us"]
    overhead = base                     # measured step = 2x the DAG replay
    tuner, applied = _loop(
        summary,
        [base + overhead] * 4 + [base + overhead - saved] * 4)
    assert tuner.history[-1]["outcome"] == "verified"
    assert applied[-1] is not None      # no spurious rollback
    # the record shows both the raw realized pct and what was expected
    rec = tuner.history[-1]
    assert rec["expected_realized_pct"] == pytest.approx(
        saved / (base + overhead) * 100.0, abs=0.05)
    assert rec["realized_speedup_pct"] == pytest.approx(
        rec["expected_realized_pct"], abs=0.1)


def test_loop_replans_on_cycle_flush_cadence(autotune_dir, fixture_cm):
    """cycle_flush_steps > 0: a verified plan stays pinned for its
    cadence, then the loop re-measures and re-plans instead of freezing
    (the compiled-world analog of the reference's cycle time).  A
    re-plan that lands on the plan already running is RETAINED without
    a re-jit and without re-verifying — the new baseline was measured
    with the plan applied, so verifying against the stale trace's
    prediction would read as a false regression and roll back a
    verified-good plan."""
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    best = AUTOTUNE_EXPECTED["predicted_step_us"]
    tuner, applied = _loop(
        summary,
        [base] * 4 + [best] * 4  # plan 1: baseline, verify → steady
        + [best] * 3             # pinned for the flush cadence
        + [best] * 4,            # cycle 2: fresh baseline → re-plan
        cycle_flush_steps=3)
    assert applied[0].cycle_flush_steps == 3
    assert [r["outcome"] for r in tuner.history] == \
        ["applied", "verified", "retained"]
    assert len(applied) == 1                # retained: no second re-jit
    assert tuner.plan.plan_id == 1 and tuner.phase == tuner.PHASE_STEADY
    assert tuner.active                     # the cycle keeps going
    # default cadence 0 keeps the old freeze-after-verify behavior
    frozen, _ = _loop(summary, [base] * 4 + [best] * 4 + [best] * 8)
    assert not frozen.active


def test_loop_sync_hooks_make_ranks_agree(autotune_dir, fixture_cm):
    """Multi-process safety: the window measurement is reduced to a
    process mean and the plan decision is taken from process 0 — a rank
    whose trace flushed late (analyze -> None) must still apply process
    0's plan instead of bucketing differently from its peers."""
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    best = AUTOTUNE_EXPECTED["predicted_step_us"]
    rank0_plan = plan_from_summary(summary)
    synced_windows = []

    def window_sync(us):
        synced_windows.append(us)
        return us + 1.0                 # process mean differs from local

    applied = []
    tuner = ProfileGuidedTuner(
        analyze_fn=lambda: None,        # this rank's trace isn't ready
        apply_fn=applied.append, window_steps=2, guard_band_pct=10.0,
        window_sync=window_sync,
        plan_sync=lambda d: rank0_plan.to_dict())   # process 0's choice
    for us in [base] * 2 + [best] * 2:
        tuner.on_step(us * 1e-6)
    assert applied and applied[0].buckets == \
        AUTOTUNE_EXPECTED["optimal_buckets"]
    assert len(synced_windows) == 2     # every window boundary synced
    assert tuner.baseline_us == pytest.approx(base + 1.0)


def test_loop_non_root_skips_analyze(autotune_dir, fixture_cm):
    """When the plan decision is process 0's broadcast, non-root ranks
    must not stitch the trace or run the bucket search — the result
    would be discarded, at seconds of CPU per window on large traces."""
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    rank0_plan = plan_from_summary(summary)
    calls = []

    def analyze_fn():
        calls.append(1)
        return summary

    applied = []
    tuner = ProfileGuidedTuner(
        analyze_fn=analyze_fn, apply_fn=applied.append, window_steps=2,
        guard_band_pct=10.0, plan_root=False,
        plan_sync=lambda d: rank0_plan.to_dict())
    for us in [AUTOTUNE_EXPECTED["baseline_us"]] * 2:
        tuner.on_step(us * 1e-6)
    assert not calls                    # broadcast only, no local analyze
    assert applied and applied[0].buckets == \
        AUTOTUNE_EXPECTED["optimal_buckets"]


def test_loop_retries_when_trace_not_ready():
    calls = []

    def flaky_analyze():
        calls.append(1)
        return None

    tuner = ProfileGuidedTuner(analyze_fn=flaky_analyze,
                               apply_fn=lambda p: None, window_steps=2)
    for _ in range(6):
        tuner.on_step(1e-3)
    assert len(calls) == 3              # one probe per window, still active
    assert tuner.active


def test_loop_freezes_after_planless_windows():
    """A job whose trace can never yield a plan (e.g. fully compiled
    plane, no per-tensor comm spans) must stop re-stitching after
    max_plan_attempts windows instead of probing forever."""
    tuner = ProfileGuidedTuner(analyze_fn=lambda: None,
                               apply_fn=lambda p: None, window_steps=2,
                               max_plan_attempts=3)
    for _ in range(10):
        tuner.on_step(1e-3)
    assert not tuner.active
    assert tuner.history[-1]["outcome"] == "no_plan_available"
    assert tuner.history[-1]["windows_tried"] == 3


def _one_plan_summary(buckets, predicted_us, baseline_us):
    """An ``analyze()`` summary whose only plannable scenario is
    ``buckets`` at ``predicted_us``."""
    return {"steps": [{"step": 1, "what_if": {
        "baseline_replay_us": baseline_us,
        "scenarios": [{
            "scenario": f"fuse_buckets_{len(buckets)}",
            "predicted_step_us": predicted_us,
            "speedup_pct": (baseline_us - predicted_us) / baseline_us * 100,
            "plan": {"buckets": buckets}}]}}]}


def test_loop_rolls_back_to_the_last_verified_plan():
    """A re-plan that regresses falls back to the plan the loop had
    verified before it, not to threshold bucketing: what an earlier window
    proved is not thrown away with what a later one disproved."""
    first = [["g0"], ["g1", "g2"]]
    second = [["g0", "g1"], ["g2"]]
    summaries = iter([_one_plan_summary(first, 700.0, 1000.0),
                      _one_plan_summary(second, 560.0, 700.0)])
    applied = []
    tuner = ProfileGuidedTuner(
        analyze_fn=lambda: next(summaries), apply_fn=applied.append,
        window_steps=2, guard_band_pct=10.0, cycle_flush_steps=2)
    for us in ([1000] * 2 + [700] * 2     # first plan: applied, verified
               + [700] * 2                # pinned for its flush cadence
               + [700] * 2                # fresh baseline: second plan
               + [700] * 2):              # which buys nothing
        tuner.on_step(us * 1e-6)
    assert [r["outcome"] for r in tuner.history] == \
        ["applied", "verified", "applied", "rolled_back"]
    assert [p.buckets for p in applied] == [first, second, first]
    assert tuner.plan.buckets == first and tuner.plan.plan_id == 1


def test_ranks_leave_a_verify_window_together():
    """What follows a verify window is decided from the synced window
    alone: a rank whose own steps say "keep" and one whose own steps say
    "roll back" take the same way out, or one of them would stop joining
    the window's collectives."""
    summary = _one_plan_summary([["g0"], ["g1", "g2"]], 700.0, 1000.0)
    local_verify_us = {"fast": 700.0, "slow": 1000.0}
    mean = sum(local_verify_us.values()) / 2
    ways_out = {}
    for rank, us in local_verify_us.items():
        synced = iter([1000.0, mean])   # the baseline, then the verify
        tuner = ProfileGuidedTuner(
            analyze_fn=lambda: summary, apply_fn=lambda p: None,
            window_steps=2, guard_band_pct=10.0, plan_root=rank == "fast",
            window_sync=lambda w: next(synced),
            plan_sync=lambda d: plan_from_summary(summary).to_dict())
        for step_us in [1000.0] * 2 + [us] * 2:
            tuner.on_step(step_us * 1e-6)
        ways_out[rank] = (tuner.history[-1]["outcome"], tuner.phase,
                          tuner.plan)
    # realized 15% of an expected 30%: past the 10% band for both
    assert ways_out["fast"] == ways_out["slow"] == \
        ("rolled_back", ProfileGuidedTuner.PHASE_FROZEN, None)


def test_a_persisted_plan_with_a_compute_key_is_read(server):
    """Outside input: a plan record that a run before PR 28 left in the
    KV store or the tuner's log carries a ``compute`` entry (PR 12's
    knobs).  Reading it ignores the entry; the bucket plan still applies."""
    record = {"buckets": [["g0"], ["g1", "g2"]], "overlap": True,
              "compression": None,
              "compute": {"fused_optimizer": True, "remat_policy": "dots",
                          "loss_fetch_steps": 4},
              "cycle_flush_steps": 0, "predicted_step_us": 300.0,
              "baseline_step_us": 440.0, "predicted_speedup_pct": 31.82,
              "source_step": 3, "plan_id": 1, "outcome": "verified"}
    put_autotune_plan("127.0.0.1", server.port, 1, record)
    stored = get_autotune("127.0.0.1", server.port)["current"]
    plan = FusionPlanSpec.from_dict(stored)
    assert plan.buckets == record["buckets"] and plan.plan_id == 1
    assert not hasattr(plan, "compute") and "compute" not in plan.to_dict()
    updates = []
    pm = ParameterManager(enabled=True, on_update=updates.append)
    pm.apply_plan(plan)
    assert updates[-1].fusion_plan is plan


def test_parameter_manager_rotates_threshold_and_hierarchical_only(
        monkeypatch):
    """The GP moves the two knobs of the gradient exchange and nothing
    of the step's compute."""
    import dataclasses

    assert [f.name for f in dataclasses.fields(TunableParams)] == [
        "fusion_threshold_bytes", "hierarchical_allreduce", "fusion_plan"]
    assert TunableParams.CATEGORICAL_DIMS == ("hierarchical_allreduce",)
    for gone in ("tune_fused_optimizer", "tune_remat"):
        with pytest.raises(TypeError, match=gone):
            ParameterManager(enabled=True, **{gone: True})
    monkeypatch.setenv("HVD_AUTOTUNE_PYTHON", "1")
    seen = []
    pm = ParameterManager(enabled=True, warmup_samples=0,
                          steps_per_sample=1, max_samples=8,
                          on_update=seen.append)
    while not pm.frozen:
        pm.record_step(1e9, 1.0)
    assert pm._category_knobs == [{"hierarchical_allreduce": False},
                                  {"hierarchical_allreduce": True}]
    assert {p.hierarchical_allreduce for p in seen} == {False, True}
    assert len({p.fusion_threshold_bytes for p in seen}) > 1


def test_parameter_manager_plan_pinning_fires_rejit_seam():
    updates = []
    pm = ParameterManager(enabled=True, on_update=updates.append)
    plan = FusionPlanSpec(buckets=[["g0"], ["g1", "g2"]])
    pm.apply_plan(plan)
    assert pm.frozen and pm.current.fusion_plan is plan
    assert updates and updates[-1].fusion_plan is plan
    pm.clear_plan()
    assert pm.current.fusion_plan is None
    assert updates[-1].fusion_plan is None
    assert not pm.frozen                # exploration resumes


# ---------------------------------------------------------------------------
# GET /autotune: the per-plan table the loop publishes
# ---------------------------------------------------------------------------
def test_autotune_scope_roundtrip(server):
    rec1 = {"plan_id": 1, "outcome": "applied",
            "predicted_speedup_pct": 31.82, "buckets": [["g0"]]}
    rec2 = {"plan_id": 1, "outcome": "verified",
            "predicted_speedup_pct": 31.82, "realized_speedup_pct": 30.9}
    put_autotune_plan("127.0.0.1", server.port, 1, rec1)
    put_autotune_plan("127.0.0.1", server.port, 2, rec2)
    report = get_autotune("127.0.0.1", server.port)
    assert [p["seq"] for p in report["plans"]] == [1, 2]
    assert report["current"] == rec2
    assert report["outcome"] == "verified"
    assert report["predicted_speedup_pct"] == 31.82
    assert report["realized_speedup_pct"] == 30.9
    # in-process view agrees with the HTTP view
    assert server.autotune_report() == report


def test_tuner_pushes_plan_records(server, autotune_dir, fixture_cm):
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    base = AUTOTUNE_EXPECTED["baseline_us"]
    best = AUTOTUNE_EXPECTED["predicted_step_us"]
    tuner = ProfileGuidedTuner(
        analyze_fn=lambda: summary, apply_fn=lambda p: None,
        window_steps=2, push_target=("127.0.0.1", server.port, None))
    for us in [base] * 2 + [best] * 2:
        tuner.on_step(us * 1e-6)
    report = get_autotune("127.0.0.1", server.port)
    assert report["outcome"] == "verified"
    assert report["current"]["buckets"] == \
        AUTOTUNE_EXPECTED["optimal_buckets"]


def test_autotune_report_empty(server):
    report = get_autotune("127.0.0.1", server.port)
    assert report == {"plans": [], "current": None}


# ---------------------------------------------------------------------------
# CLI: tier-1 --check + plan output
# ---------------------------------------------------------------------------
def _load_cli():
    spec = _ilu.spec_from_file_location(
        "hvd_autotune", os.path.join(REPO, "scripts", "hvd_autotune.py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_check_smoke():
    """The tier-1 closed-loop smoke the ISSUE pins: --check exits 0."""
    cli = _load_cli()
    with pytest.raises(SystemExit) as e:
        cli.main(["--check"])
    assert e.value.code == 0


def test_cli_plan_output_and_push(autotune_dir, server, tmp_path, capsys):
    cli = _load_cli()
    out = tmp_path / "plan.json"
    record = cli.main([autotune_dir,
                       "--hop-us", str(AUTOTUNE_EXPECTED["hop_latency_us"]),
                       "--json", "--out", str(out),
                       "--push", f"127.0.0.1:{server.port}"])
    assert record["buckets"] == AUTOTUNE_EXPECTED["optimal_buckets"]
    assert json.loads(out.read_text()) == record
    assert json.loads(capsys.readouterr().out) == record
    served = get_autotune("127.0.0.1", server.port)
    assert served["current"]["buckets"] == \
        AUTOTUNE_EXPECTED["optimal_buckets"]
    # repeated offline pushes accumulate instead of overwriting one slot
    cli.main([autotune_dir,
              "--hop-us", str(AUTOTUNE_EXPECTED["hop_latency_us"]),
              "--push", f"127.0.0.1:{server.port}"])
    capsys.readouterr()
    assert len(get_autotune("127.0.0.1", server.port)["plans"]) == 2


# ---------------------------------------------------------------------------
# tpurun wiring: --profile-guided flag → worker env
# ---------------------------------------------------------------------------
def test_tpurun_profile_guided_env_translation():
    import argparse

    from horovod_tpu.run.config_parser import env_from_args

    ns = argparse.Namespace(profile_guided=True, autotune_window_steps=8,
                            autotune_guard_band_pct=5.0)
    env = env_from_args(ns)
    assert env["HVD_AUTOTUNE_PROFILE_GUIDED"] == "1"
    assert env["HVD_AUTOTUNE_WINDOW_STEPS"] == "8"
    assert env["HVD_AUTOTUNE_GUARD_BAND_PCT"] == "5.0"
    # off by default: the knob must not leak into every worker env
    assert "HVD_AUTOTUNE_PROFILE_GUIDED" not in env_from_args(
        argparse.Namespace(profile_guided=False))


# ---------------------------------------------------------------------------
# make_train_step integration: the loop rides the re-jit seam
# ---------------------------------------------------------------------------
def test_warm_start_survives_traced_first_call(hvd_init, monkeypatch, rng):
    """Recorder.record_step_function traces the step before the first
    real dispatch (HVD_TIMELINE jobs — exactly the profile-guided
    configuration).  The traced call caches grad_bytes from tracer
    leaves but must not burn the only warm-start opportunity: the first
    eager call still seeds the GP."""
    import jax
    import optax

    import horovod_tpu.optim.profile_guided as pg
    from horovod_tpu.models.mlp import MLP
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    seeded = []
    monkeypatch.setattr(
        pg, "warm_start_manager",
        lambda pm, grad_bytes, **kw: seeded.append(grad_bytes) or 0)
    model = MLP(features=(8, 4))
    opt = optax.sgd(0.05)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    step = make_train_step(apply_fn=model.apply, loss_fn=loss_fn,
                           optimizer=opt, autotune=True, donate=False)
    state = init_train_state(model, opt, jnp.zeros((2, 8)))
    x = shard_batch(rng.normal(size=(16, 8)).astype(np.float32))
    y = shard_batch(rng.integers(0, 4, size=(16,)).astype(np.int32))

    jax.make_jaxpr(lambda s, a, b: step(s, a, b))(state, x, y)
    assert seeded == []                 # tracers must not seed the GP
    step(state, x, y)
    assert len(seeded) == 1 and seeded[0] > 0
    step(state, x, y)
    assert len(seeded) == 1             # once per job, not per step


def test_step_sync_symmetric_while_tuner_active(hvd_init, monkeypatch, rng):
    """While the PG loop measures, the step wrapper must block on the
    result even on the pm-frozen/pm-None path — otherwise the baseline
    window (GP active, synced) and the verify window (GP frozen,
    pipelined) measure different things and any plan 'verifies'.  Once
    the loop settles the sync must disappear from the hot path."""
    import jax
    import optax

    import horovod_tpu.training as training
    from horovod_tpu.models.mlp import MLP

    model = MLP(features=(8, 4))
    opt = optax.sgd(0.05)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    step = training.make_train_step(
        apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
        profile_guided=True, donate=False)
    tuner = step.profile_guided_tuner
    state = training.init_train_state(model, opt, jnp.zeros((2, 8)))
    x = training.shard_batch(rng.normal(size=(16, 8)).astype(np.float32))
    y = training.shard_batch(rng.integers(0, 4, size=(16,)).astype(np.int32))
    state, _ = step(state, x, y)        # compile outside the counter

    gets = []
    real_device_get = jax.device_get
    monkeypatch.setattr(
        training.jax, "device_get",
        lambda v: gets.append(1) or real_device_get(v))
    state, _ = step(state, x, y)
    assert len(gets) >= 1               # measuring: sync per step
    tuner.phase = tuner.PHASE_STEADY    # plan pinned, only counting
    tuner._steady_left = 100
    gets.clear()
    state, _ = step(state, x, y)
    assert gets == []                   # steady: pipeline kept async
    tuner.phase = tuner.PHASE_FROZEN    # loop settles
    gets.clear()
    state, _ = step(state, x, y)
    assert gets == []                   # hot path: no sync once frozen


def test_profile_guided_drives_train_step(hvd_init, monkeypatch, tmp_path,
                                          rng, autotune_dir, fixture_cm):
    """End to end through training.py: the tuner analyzes a trace and
    applies the plan through the rebuild seam (explicit named buckets)
    while real steps dispatch; an injected verify-window regression then
    rolls it back through the same seam, and training keeps working on
    both sides of the rollback."""
    import optax

    import horovod_tpu as hvd  # noqa: F401
    from horovod_tpu.models.mlp import MLP
    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    monkeypatch.setenv("HVD_AUTOTUNE_WINDOW_STEPS", "3")
    model = MLP(features=(16, 4))
    opt = optax.sgd(0.05)

    def loss_fn(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    step = make_train_step(
        apply_fn=model.apply, loss_fn=loss_fn, optimizer=opt,
        profile_guided=True, donate=False,
    )
    tuner = step.profile_guided_tuner
    assert tuner is not None and tuner.active
    assert step.parameter_manager is None
    summary = analyze(autotune_dir, cost_model=fixture_cm).summary
    tuner.analyze_fn = lambda: summary

    state = init_train_state(model, opt, jnp.zeros((2, 8)))
    x = shard_batch(rng.normal(size=(16, 8)).astype(np.float32))
    y = shard_batch(rng.integers(0, 4, size=(16,)).astype(np.int32))

    # drive real steps until the baseline window closes and the plan is
    # applied through the rebuild seam (re-jit with named buckets)
    for _ in range(12):
        state, loss = step(state, x, y)
        if tuner.phase == tuner.PHASE_VERIFY:
            break
    assert tuner.plan is not None
    assert tuner.plan.buckets == AUTOTUNE_EXPECTED["optimal_buckets"]
    assert [r.get("outcome") for r in tuner.history] == ["applied"]
    assert np.isfinite(float(np.asarray(loss)))

    # deterministic regression injection: the verify window realizes a
    # 50% SLOWDOWN over the measured baseline — far past the guard band
    # however the fixture's predicted saving normalizes onto real CPU
    # step time — so the plan must roll back (wall-clock-independent;
    # real CPU step intervals are too noisy to pin an outcome on)
    base_s = tuner.baseline_us * 1e-6
    for _ in range(tuner.window_steps):
        tuner.on_step(base_s * 1.5)
    assert tuner.history[-1]["outcome"] == "rolled_back"
    assert tuner.plan is None and not tuner.active

    # the rolled-back (threshold-bucketed) step still trains
    state, loss = step(state, x, y)
    assert np.isfinite(float(np.asarray(loss)))


# ---------------------------------------------------------------------------
# wire-efficiency tier: compression + two-level what-ifs
# ---------------------------------------------------------------------------
def test_compression_choice_search_recovers_fixture_optimum(autotune_dir,
                                                            fixture_cm):
    """The staged per-bucket wire-format search on the hand-computed
    partition: int8 on the 4 MiB bucket (β/4 beats its qd + scale α),
    cast-only bf16 on the 0.5 MiB bucket (the scale α wouldn't pay)."""
    from horovod_tpu.timeline.replay.simulator import (
        bucket_plan_search, compression_choice_search,
    )

    _art, dags = stitch(autotune_dir)
    results = bucket_plan_search(dags[0], fixture_cm)
    best = results[0]
    comp, makespan = compression_choice_search(
        dags[0], fixture_cm, best["node_partition"])
    # node_partition is in search order; map through _bucket_plan's wire
    # ordering via the emitted plan instead of assuming it
    from horovod_tpu.timeline.replay.simulator import _bucket_plan

    plan = _bucket_plan(dags[0], best["node_partition"], makespan,
                        compression=comp)
    assert plan["compression"] == AUTOTUNE_EXPECTED["optimal_compression"]
    assert makespan == pytest.approx(
        AUTOTUNE_EXPECTED["predicted_step_us"], abs=1e-3)


def test_two_level_comm_scenario_priced_by_cost_model(autotune_dir):
    """two_level_comm appears when the cost model carries a hierarchy
    (local_size > 1 dividing the world) and prices every all-reduce with
    predict_collective_us(two_level=True) — absent on flat models."""
    from horovod_tpu.timeline.comm_report import predict_collective_us
    from horovod_tpu.timeline.replay.simulator import what_if

    _art, dags = stitch(autotune_dir)
    dag = dags[0]
    flat_cm = CostModel(world=2, hop_latency_us=10.0)
    names = {s["scenario"] for s in what_if(dag, flat_cm)["scenarios"]}
    assert "two_level_comm" not in names        # no hierarchy to exploit

    cm = CostModel(world=8, hop_latency_us=10.0, local_size=4)
    wi = what_if(dag, cm)
    by_name = {s["scenario"]: s for s in wi["scenarios"]}
    assert "two_level_comm" in by_name
    # the scenario's durations are exactly the shared cost model's
    comm = [n for n in dag.nodes if n.kind == "comm"]
    expected = sum(predict_collective_us(
        "all-reduce", n.nbytes, 8,
        ici_hop_latency=10e-6,
        two_level=True, local_size=4,
        dcn_bytes_per_sec=cm.dcn_bytes_per_sec,
        dcn_hop_latency=cm.dcn_hop_latency_us * 1e-6) for n in comm)
    computes = sum(n.dur_us for n in dag.nodes
                   if n.kind == "compute") / len(dag.chains)
    assert by_name["two_level_comm"]["predicted_step_us"] == \
        pytest.approx(computes + expected, abs=1e-3)


def test_compress_scenarios_present_and_ranked(autotune_dir, fixture_cm):
    """compress_<dtype> what-ifs exist for every registered candidate
    and land on the same predicted-µs scale as the fusion scenarios."""
    from horovod_tpu.timeline.replay.simulator import (
        COMPRESSION_CANDIDATES, what_if,
    )

    _art, dags = stitch(autotune_dir)
    wi = what_if(dags[0], fixture_cm)
    names = [s["scenario"] for s in wi["scenarios"]]
    for comp in COMPRESSION_CANDIDATES:
        assert f"compress_{comp}" in names
    # ranked list is sorted by predicted step time (shared scale)
    times = [s["predicted_step_us"] for s in wi["scenarios"]]
    assert times == sorted(times)


def test_applied_plan_carries_compression_through_train_step(hvd_init,
                                                             monkeypatch):
    """A FusionPlanSpec with per-bucket compression applies through
    make_train_step's rebuild seam: training proceeds and the lazily
    initialized error-feedback residual appears in the state."""
    import optax

    from horovod_tpu.training import (
        init_train_state, make_train_step, shard_batch,
    )

    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(8)(x)
            return nn.Dense(4)(x)

    model, opt = MLP(), optax.sgd(0.05)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    step = make_train_step(apply_fn=lambda v, x: model.apply(v, x),
                           loss_fn=loss_fn, optimizer=opt,
                           autotune=True)
    state = init_train_state(model, opt, jnp.zeros((2, 6)))
    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 6)).astype(np.float32)
    Y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    x, y = shard_batch(X), shard_batch(Y)
    state, _ = step(state, x, y)

    names = ["Dense_0/bias", "Dense_0/kernel", "Dense_1/bias",
             "Dense_1/kernel"]
    plan = FusionPlanSpec(buckets=[names[:2], names[2:]],
                          compression=["int8", "bf16"])
    step.parameter_manager.apply_plan(plan)
    import jax as _jax

    for _ in range(3):
        state, loss = step(state, x, y)
    assert np.isfinite(float(loss))
    assert _jax.tree_util.tree_leaves(state.residual)  # EF came up
    step.parameter_manager.clear_plan()                # rollback path
    state, loss = step(state, x, y)
    assert np.isfinite(float(loss))
