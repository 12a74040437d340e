"""Rank/size/topology sanity — analog of the reference's rank/size tests
(reference test/test_torch.py:99-128 test_horovod_rank / test_horovod_size
reading MPI env via test/common.py:27-59)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from jax.sharding import PartitionSpec as P


def test_size_and_local(hvd_init):
    assert hvd.size() == 8
    assert hvd.local_size() == 4
    assert hvd.cross_size() == 2
    assert hvd.is_initialized()
    assert hvd.is_homogeneous()


def test_uninitialized_raises():
    hvd.shutdown()
    with pytest.raises(RuntimeError):
        hvd.size()


def test_double_init_is_noop(hvd_init, cpu_devices):
    hvd.init(devices=cpu_devices[:4])  # ignored: already initialized
    assert hvd.size() == 8


def test_rank_inside_spmd(hvd_init):
    @hvd.spmd(in_specs=P(hvd.AXIS), out_specs=P(hvd.AXIS))
    def get_rank(x):
        return (x[0] + hvd.rank())[None]

    out = get_rank(jnp.zeros((8,), jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))


def test_local_and_cross_rank_inside_spmd(hvd_init):
    @hvd.spmd(in_specs=P(hvd.AXIS), out_specs=P(hvd.AXIS))
    def get(x):
        return jnp.stack(
            [x[0, 0] + hvd.local_rank(), x[0, 0] + hvd.cross_rank()]
        )[None]

    out = np.asarray(get(jnp.zeros((8, 2), jnp.int32)))
    np.testing.assert_array_equal(out[:, 0], [0, 1, 2, 3, 0, 1, 2, 3])
    np.testing.assert_array_equal(out[:, 1], [0, 0, 0, 0, 1, 1, 1, 1])


def test_hierarchical_rank_model(hvd_init):
    @hvd.spmd(hierarchical=True, in_specs=P(hvd.CROSS_AXIS),
              out_specs=P(hvd.CROSS_AXIS))
    def get(x):
        return jnp.stack([
            x[0, 0] + hvd.rank(),
            x[0, 0] + hvd.local_rank(),
            x[0, 0] + hvd.cross_rank(),
        ])[None]

    # hierarchical mesh is (cross=2, local=4); shard input over cross only
    out = np.asarray(get(jnp.zeros((2, 3), jnp.int32)))
    # with local axis unsharded in in_specs, each (cross,local) device sees
    # the same row; ranks must still enumerate 0..7
    assert out.shape == (2, 3)


def test_capability_probes(hvd_init):
    assert hvd.xla_built()
    assert not hvd.mpi_enabled()
    assert not hvd.nccl_built()
    assert not hvd.gloo_built()
    assert not hvd.cuda_built()


def test_process_rank(hvd_init):
    assert hvd.process_rank() == 0
    assert hvd.process_size() == 1
    assert hvd.rank() == 0  # outside SPMD: controller index
    assert hvd.local_rank() == 0


def test_mesh_sum_accumulates_half_precision_in_f32(hvd_init):
    """The process-mesh reduction must match the native host plane's
    numerics (csrc reduces in double): bf16/f16 rows accumulate in f32,
    int rows keep their exact dtype (advisor round-4, eager.py)."""
    from jax.sharding import Mesh

    from horovod_tpu import eager

    devs = np.array(jax.devices("cpu")[:4], dtype=object)
    pmesh = Mesh(devs, ("proc",))

    # 4 bf16 rows of 0.1: a bf16-accumulated sum of many 0.1s drifts;
    # f32 accumulation keeps the partial sums exact to f32
    rows = jnp.full((4, 256), 0.1, jnp.bfloat16)
    out = eager._sum_rows_fn(pmesh)(rows)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out),
        4 * np.full((256,), np.float32(jnp.bfloat16(0.1))),
        rtol=1e-6,
    )

    iout = eager._sum_rows_fn(pmesh)(jnp.full((4, 8), 2**24 + 1, jnp.int32))
    assert iout.dtype == jnp.int32  # widening to f32 would lose exactness
    assert int(np.asarray(iout)[0]) == 4 * (2**24 + 1)


# ---------------------------------------------------------------------------
# the persistent compile cache is placed from outside, or at a fixed path
# ---------------------------------------------------------------------------
def _record_config_updates(monkeypatch):
    from horovod_tpu import core

    calls = []
    monkeypatch.setattr(core.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return core, calls


_CACHE_EVERYTHING = ("jax_persistent_cache_min_compile_time_secs", 0.0)


def test_compile_cache_env_placement_sets_no_directory(monkeypatch,
                                                       tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the code sets no
    directory of its own.  It still asks for every program to be cached,
    unless the environment chose that threshold too."""
    core, calls = _record_config_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    core._place_compile_cache("tpu")
    assert calls == [_CACHE_EVERYTHING]
    assert core.compile_cache_dir() == str(tmp_path)
    calls.clear()
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    core._place_compile_cache("tpu")
    assert calls == []


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    """Unset: <checkout>/.jax_cache — the path is part of the cache key,
    so it must be the same in every process (never a temp dir, a pid or
    a clock).  The CPU test mesh stays uncached."""
    import os
    import subprocess
    import sys

    core, calls = _record_config_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    core._place_compile_cache("cpu")
    assert calls == []
    core._place_compile_cache("tpu")
    assert calls == [_CACHE_EVERYTHING,
                     ("jax_compilation_cache_dir", want)]
    assert core.compile_cache_dir() == want
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = repo
    code = ("from horovod_tpu import core; "
            "print(core.compile_cache_dir())")
    seen = {subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120,
                           check=True).stdout.strip()
            for cwd in (repo, os.path.join(repo, "tests"))}
    assert seen == {want}

