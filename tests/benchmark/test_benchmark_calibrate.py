"""``benchmarks/calibrate.py`` on the CPU at the tiny preset: the builder's
tool that reads, in one process, what ``correct`` compares for a cell's
program over many seeds and for its float8 control over a few.  (A file of
its own: it builds the tiny benchmark and two states, and the files beside
it are long already.)"""

import jax
import pytest

import benchmark_tiny
from benchmarks import calibrate
from benchmarks.configs import gpt2_small
from benchmarks.harness.spec import Spec
from test_benchmark_harness import world  # noqa: F401 — a fixture


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return benchmark_tiny.make(str(tmp_path_factory.mktemp("bench")))


def test_calibrate_reads_a_state_a_seed(tiny_root, world, capsys):  # noqa: F811
    """``calibrate.py`` builds one state a seed, as ``run_cell`` does
    (``seeded_state``), and keeps nothing of a seed for the next: two seeds
    in one process, the program's numbers far inside the limits (the toy
    computes in float32), the float8 control outside them."""
    out = calibrate.readings(Spec(tiny_root), "tiny-gpt", [3, 4], [4],
                             devices=jax.devices("cpu")[:1])
    assert sorted(out["program"]) == [3, 4] and list(out["control"]) == [4]
    for numbers in out["program"].values():
        assert all(v <= gpt2_small.LIMITS[k] / 10 for k, v in numbers.items())
    assert out["program"][3] != out["program"][4]
    control = out["control"][4]
    assert control["grad_sketch_gap"] > gpt2_small.LIMITS["grad_sketch_gap"]
    assert set(calibrate.summary(out)) == set(control)
    assert capsys.readouterr().out.count("calibrate: seed") == 5

