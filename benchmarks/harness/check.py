"""The comparison that decides ``correct``.

The timed step's first three steps are held against the float32 reference
training the same three batches from the same seeded weights; the window's
losses are held to being finite and to where they should have got.  Every
number is printed beside its limit in every run, and one over its limit
makes the run not correct.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence, Tuple

import jax

from benchmarks.references import common


def replace_leaves(tree, by_name: Dict[str, object]):
    """``tree`` with every leaf replaced by the one of its name."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = list(common.flatten(tree))
    missing = sorted(set(names) ^ set(by_name))
    if missing:
        raise RuntimeError(
            "the reference's weights and the program's parameters differ "
            f"in leaves: {missing[:6]}{' ...' if len(missing) > 6 else ''}")
    new = [by_name[n] for n in names]
    for (_, old), leaf, n in zip(leaves, new, names):
        if old.shape != leaf.shape:
            raise RuntimeError(f"leaf {n}: the reference makes "
                               f"{leaf.shape}, the program holds "
                               f"{old.shape}")
    return jax.tree_util.tree_unflatten(treedef, new)


#: A leaf whose first gradient is under this share of the median leaf's is
#: "all but zero" (a key bias, which softmax cancels; a block that starts
#: as the identity).  Its *update* is not compared: Adam divides the
#: gradient by its own magnitude, so what such a leaf moves by is the sign
#: of rounding noise, in the program and in the reference alike.  Nor does
#: its sketch enter the mean: there is nothing there to differ.
NEGLIGIBLE_GRADIENT = 1e-2


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   leaves: Sequence[str] = ()) -> Tuple[float, str]:
    """The largest gap between the program's norm of a leaf and the
    reference's — of the norms, not the norm of the difference — against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  ``leaves`` restricts the
    search; the median is always over every leaf."""
    if set(program) != set(reference):
        raise RuntimeError("program and reference name different leaves")
    median = statistics.median(reference.values())
    worst, where = 0.0, ""
    for name in (leaves or reference):
        ref = reference[name]
        scale = max(ref, median)
        # a leaf whose gradient is exactly zero in both (a block that starts
        # as the identity) agrees; one that is zero only in the reference
        # does not
        gap = 0.0 if program[name] == ref else (
            abs(program[name] - ref) / scale if scale > 0 else math.inf)
        if not math.isfinite(gap):
            return math.inf, name
        if gap > worst:
            worst, where = gap, name
    return worst, where


def sketch_gap(program: Dict[str, Sequence[float]],
               reference: Dict[str, Sequence[float]],
               norms: Dict[str, float], leaves: Sequence[str]) -> float:
    """Root mean square, over ``leaves``, of the difference between the
    program's and the reference's sketches of a leaf
    (``common.leaf_sketches``) against the reference's norm of that leaf or
    of the median leaf.  A leaf's term estimates the norm of the two
    gradients' *difference*, which rounding noise moves and a gap between
    two norms hardly does; the mean over leaves, not the worst, because it
    is what reads alike from seed to seed (PERF.md section 2)."""
    median = statistics.median(norms.values())
    total = 0.0
    for name in leaves:
        diff2 = sum((p - r) ** 2 for p, r in
                    zip(program[name], reference[name])) / len(
                        reference[name])
        total += diff2 / max(norms[name], median) ** 2
    gap = math.sqrt(total / len(leaves))
    return gap if math.isfinite(gap) else math.inf


def first_steps_numbers(program: dict, reference: dict) -> Dict[str, float]:
    """``program`` and ``reference``: ``losses`` (one per step),
    ``grad_norms``, ``grad_sketches`` and ``update_norms`` (by leaf)."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(program["losses"], reference["losses"]))
    grads = reference["grad_norms"]
    grad_gap, grad_leaf = worst_leaf_gap(program["grad_norms"], grads)
    floor = NEGLIGIBLE_GRADIENT * statistics.median(grads.values())
    moving = [k for k, v in grads.items() if v > floor]
    upd_gap, upd_leaf = worst_leaf_gap(program["update_norms"],
                                       reference["update_norms"], moving)
    print(f"check: worst leaves: gradient norm {grad_leaf}, update "
          f"{upd_leaf} (of the {len(moving)} leaves in {len(grads)} whose "
          f"first gradient is not negligible)", flush=True)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "grad_sketch_gap": sketch_gap(
                program["grad_sketches"], reference["grad_sketches"], grads,
                moving),
            "update_norm_gap": upd_gap}


def window_numbers(losses: Sequence[float]) -> Dict[str, float]:
    finite = [x for x in losses if math.isfinite(x)]
    tail = finite[-10:]
    return {"nonfinite_losses": float(len(losses) - len(finite)),
            "final_loss": sum(tail) / len(tail) if tail else math.inf}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, List[str]]:
    """Every number needs a limit, and holds it when ``number <= limit``
    (``nan`` never does)."""
    lines, ok = [], True
    for name, value in numbers.items():
        if name not in limits:
            raise RuntimeError(f"no limit for the compared number {name!r}")
        held = value <= limits[name]
        ok = ok and held
        lines.append(f"check: {name} = {value!r}  limit {limits[name]!r}  "
                     f"{'ok' if held else 'OVER'}")
    return ok, lines
