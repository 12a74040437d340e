"""``nemotron3-8k``'s step compiled for a described v5e with the
state-space scan as Pallas kernels (``ops/ssd.py``, PR 42): the flash three
and the scan's three are the step's only Mosaic calls, each scan kernel once
a state-space block and pass under ``/hvd_ssm/hvd_ssm_scan/``, none of them
with the recompute's mark, no loop left under the scan's scope and nothing
of a chunk's algebra (``L``, the masked scores: chunk x chunk float32 a
head and chunk) anywhere in the step.  No chip is attached and nothing
runs."""

import math
import re

import pytest

from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, topo)
from test_benchmark_nemotron_h_v5e import _lowered_step


@pytest.fixture(scope="module")
def step_text(topo, no_compile_cache):  # noqa: F811
    cell, _, lowered = _lowered_step(topo, "nemotron3-8k")
    return cell, lowered.compile().as_text()


def test_the_scan_is_three_kernels_a_block_under_its_scope(step_text):
    from horovod_tpu.ops import ssd

    cell, text = step_text
    mixers = cell.cfg["num_hidden_layers"] // 2
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*op_name=\"([^\"]+)\"", text)
    names = [name for name, _ in calls]
    assert {k: names.count(k) for k in set(names)} == {
        "hvd_flash_fwd": 1, "hvd_flash_dq": 1, "hvd_flash_dkv": 1,
        ssd.FWD_KERNEL: mixers, ssd.STATES_KERNEL: mixers,
        ssd.BWD_KERNEL: mixers}
    for name, path in calls:
        if name.startswith(ssd.SCAN_SCOPE):
            assert f"/hvd_ssm/{ssd.SCAN_SCOPE}/" in path, path
            assert f"/{name}/pallas_call" in path, path
            # a recomputed block keeps the scan's output and operands by
            # name: no kernel runs a second time
            assert "rematted_computation" not in path, path
    paths = re.findall(r'op_name="([^"]+)"', text)
    scan = [p for p in paths if f"/hvd_ssm/{ssd.SCAN_SCOPE}/" in p]
    assert not any("/while" in p for p in scan)
    assert not any("rematted_computation" in p for p in scan)
    assert len({re.search(r"layers_(\d+)", p).group(1) for p in scan
                if "layers_" in p}) == mixers


def test_nothing_of_a_chunks_algebra_crosses_hbm(step_text):
    """``L`` and the masked scores were ``f32[1,64,128,128,8,8]`` in the
    step before the kernels: no float32 array of chunk x chunk elements a
    head and chunk is left, in any order of its dimensions."""
    cell, text = step_text
    cfg, mix = cell.cfg, cell.mix
    chunk = cfg["chunk_size"]
    seq = mix["arrays"][0]["shape"][0]
    whole = mix["rows_per_chip"] * cfg["mamba_num_heads"] * seq * chunk
    assert "f32[1,64,128,128,8,8]" not in text
    for shape in set(re.findall(r"f32\[([\d,]+)\]", text)):
        dims = [int(d) for d in shape.split(",")]
        assert not (math.prod(dims) == whole and dims.count(chunk) >= 2), \
            shape
