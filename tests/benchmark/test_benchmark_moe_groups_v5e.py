"""The two cells whose expert layers take their rows in groups
(``sdar-bd4-8k``: four groups of 4096 rows a layer, ``kanana2-8k``: two)
compiled for a described v5e at the cells' own sizes, as ``run.py`` builds
the step.  The backward pass of ``parallel/moe.routed_experts`` carries one
float32 accumulator of each of the experts' matrices through a layer's
groups: in the compiled step the loop over the groups hands the three to
the loop over a group's tiles and takes them back, and neither adds, copies
nor zeroes an array of their shapes (the parent summed a group's
``[held, d, f]`` gradients into the running cotangent, 1.2 GB of traffic a
group-layer).  No chip is attached and nothing runs.  (What the steps
hold, ``hbm_gb``, is ``test_benchmark_keep_v5e.py``'s.)"""

import collections
import re

import pytest

from test_benchmark_kanana2_v5e import kanana2_step  # noqa: F401 — fixture
from test_benchmark_kernels_v5e import (  # noqa: F401 — fixtures
    no_compile_cache, topo)
from test_benchmark_recompute_v5e import compile_step

#: cell -> (float32 shapes of the held experts' matrices, expert layers,
#: Mosaic calls of the step by kernel name as
#: ``test_benchmark_recompute_v5e.py`` and ``test_benchmark_kanana2_v5e.py``
#: hold them)
CELLS = {
    "sdar-bd4-8k": (
        r"f32\[16,(?:2048,768|768,2048)\]", 4,
        {"hvd_flash_fwd": 4, "hvd_flash_dq": 4, "hvd_flash_dkv": 4}),
    "kanana2-8k": (
        r"f32\[8,(?:2048,768|768,2048)\]", 4,
        {"hvd_flash_fwd": 5, "hvd_flash_dq": 5, "hvd_flash_dkv": 5}),
}
EXPERTS_SCOPE = "hvd_moe_experts"


@pytest.fixture(scope="module")
def steps(topo, no_compile_cache, kanana2_step):  # noqa: F811
    return {"sdar-bd4-8k": compile_step("sdar-bd4-8k", topo),
            "kanana2-8k": kanana2_step}


def _computations(text):
    """``{name: its instruction lines}`` of an optimized module's text, and
    ``{name: (the computation that calls it, as what)}``: ``body`` and
    ``condition`` of a ``while``, ``calls`` of a fusion, ``to_apply``."""
    lines, called, name = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            lines[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            lines[name].append(line)
            for how, callee in re.findall(
                    r"(body|condition|calls|to_apply)=%?([\w.\-]+)", line):
                called[callee] = (name, how)
    return lines, called


def _loops_round(name, called):
    """How many loop bodies ``name`` sits in."""
    depth = 0
    while name in called:
        name, how = called[name]
        depth += how == "body"
    return depth


def _produced(line, shape):
    """The opcode of an instruction whose result is one array of ``shape``
    (a tuple that holds one is a loop's state, not a new array)."""
    made = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
    if made and not made.group(1).startswith("(") \
            and re.match(shape, made.group(1)):
        return made.group(2)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_group_loop_hands_the_accumulators_on_and_touches_none(
        cell, steps):
    shape, layers, _ = CELLS[cell]
    lines, called = _computations(steps[cell].as_text())
    carrying = {}    # body -> the computation its ``while`` sits in
    for name, body in lines.items():
        for line in body:
            state = line.split(" while(")[0] if " while(" in line else ""
            if len(re.findall(shape, state)) == 3:
                carrying[re.search(r"body=%?([\w.\-]+)",
                                   line).group(1)] = name
    over_groups = [b for b in carrying if b in carrying.values()]
    over_tiles = [b for b in carrying if carrying[b] in over_groups]
    assert len(over_groups) == len(over_tiles) == layers
    for body in over_groups:
        made = collections.Counter(
            _produced(line, shape) for line in lines[body])
        del made[None]
        # three out of the loop's own state, three out of the tile loop's
        assert made == {"get-tuple-element": 6}, (body, made)
    for body in over_tiles:
        made = collections.Counter(
            _produced(line, shape) for line in lines[body])
        del made[None]
        # an expert's slice updated in place, nothing else
        assert made == {"get-tuple-element": 3, "fusion": 3}, (body, made)
        assert _loops_round(body, called) == 2


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_what_runs_under_the_experts_scope_runs_once_a_tile(cell, steps):
    """``moe_tiles`` is the mean number of runs of the instructions under
    ``hvd_moe_experts`` and the experts' roofline shares divide by their
    time: every one of them sits in a tile loop's body (a loop over the
    groups, then one over a group's tiles), none beside the loops, where
    the accumulators are zeroed once a layer."""
    lines, called = _computations(steps[cell].as_text())
    depths = collections.Counter()
    for name, body in lines.items():
        if called.get(name, ("", ""))[1] in ("calls", "to_apply"):
            continue    # a fusion's inside: the fusion itself is counted
        for line in body:
            if re.search(rf'op_name="[^"]*{EXPERTS_SCOPE}', line):
                depths[_loops_round(name, called)] += 1
    assert set(depths) == {2}, depths


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_mosaic_calls_are_the_ones_the_step_had(cell, steps):
    calls = re.findall(
        r"%(\S+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        steps[cell].as_text())
    assert {k: calls.count(k) for k in set(calls)} == CELLS[cell][2]


