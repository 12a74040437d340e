"""What a recomputed decoder layer keeps: ranked names inside a byte budget.

``remat`` on the decoders (``qwen3_next``, ``sdar``, ``kanana2``,
``mellum2``, ``nemotron_h``, ``lfm2``) recomputes each layer in the backward
pass from the layer's input.  What a layer always keeps is what the Pallas forward
kernels, and the state-space scan, wrote for their backward rules
(:data:`KERNEL_RESIDUALS`), so a layer calls each of them once.
Everything else the second run makes again costs time and buys memory, and a
chip that has the memory need not pay: the models name those outputs
(``checkpoint_name``; ``models/scopes.py``, ``ops/flash_attention.py``,
``ops/gated_delta.py``, ``ops/ssd.py``, ``parallel/moe.py``), :data:`RANK`
orders the names
by the recompute time a kept byte saves (measured part by part on a v5e:
PERF.md section 5), and :func:`recomputed` keeps, in that order, what fits
the bytes :func:`keep_budget` finds free — the device's memory less what the
model reckons, from the shapes it is applied to, that the step holds
whatever is kept.  A device of unknown memory (the CPU mesh) has no budget:
only the kernels' residuals are kept there.  A part kept has no op under its
scope with ``rematted_computation`` on its path; counter
``hvd_recompute_kept_bytes_traced_total{name}`` says what was kept and what
the budget refused (``name="skipped"``).
"""

from __future__ import annotations

from typing import Mapping, Sequence, Tuple

import jax
from flax import linen as nn

from .. import metrics
from ..ops.flash_attention import (FLASH_K, FLASH_LSE, FLASH_OUT, FLASH_Q,
                                   FLASH_V)
from ..ops.gated_delta import GDN_IN, GDN_INVERSES, GDN_OUT, GDN_STATES
from ..ops.ssd import SSD_IN, SSD_OUT
from ..parallel.moe import ROUTING
from ..utils import flops
from . import scopes

#: what the forward kernels wrote and the backward kernels read, and the
#: state-space scan's output: kept whatever the budget (a kernel call, or
#: the scan, to make again, PR 33)
KERNEL_RESIDUALS = (FLASH_OUT, FLASH_LSE, GDN_OUT, GDN_STATES, GDN_INVERSES,
                    SSD_OUT)

#: The names a layer may keep besides, by the recompute time a kept byte
#: saves, most first (ms a GB on a v5e, PERF.md section 5, PR 36's table by
#: part): the router's logits, picks, order and sizes (over 50: the product,
#: the top-k and the sort for 5-18 MB a layer); the gated norm's output
#: (31); ``out_proj`` / ``o_proj``'s output (22-24: with it the second half
#: of a layer starts from an add); ``q_proj`` (11-14); q as the flash
#: kernels take it (11-15: norms, rotary and the swap); a SwiGLU's gate and
#: up (11-12); ``in_proj_qkvz`` (11); the k / v side's projections (11);
#: the scan's operands (11); the convolution's output (10); k and v as the
#: flash kernels take them (4-6: latent attention's assembly a q head and
#: the swap; grouped-query attention's are k's norm and rotary and the swap
#: at the kv heads' count, not measured apart).
#: The state-space mixer's names (``nemotron_h``) stand beside the gated
#: DeltaNet's, part for part (the same work on the same bytes: its norm, its
#: ``in_proj``, the scan's operands, its convolution; not measured apart).
#: So do the gated short convolution's two (``lfm2``): ``out_proj``'s
#: operand ``C * z`` beside the gated norms, ``in_proj``'s output beside
#: the other ``in_proj``s (not measured apart either: its taps and gates
#: between the two are three elementwise passes and have no name).
RANK = (ROUTING, scopes.KEEP_GDN_NORM, scopes.KEEP_SSM_NORM,
        scopes.KEEP_SCONV_GATE, scopes.KEEP_OUT_PROJ, scopes.KEEP_Q_PROJ,
        FLASH_Q, scopes.KEEP_MLP, scopes.KEEP_GDN_IN_PROJ,
        scopes.KEEP_SSM_IN_PROJ, scopes.KEEP_SCONV_IN_PROJ,
        scopes.KEEP_KV_PROJ, GDN_IN, SSD_IN, scopes.KEEP_GDN_CONV,
        scopes.KEEP_SSM_CONV, FLASH_K, FLASH_V)

#: The share of a device's memory a step may fill with its arguments, its
#: temporaries and one more float32 copy of the parameters beside it (a
#: caller's reference weights, a checkpoint on its way out): the 0.75 that
#: ``tests/benchmark/test_benchmark_recompute_v5e.py`` holds the cells to,
#: less 0.02 for what the reckoning of ``held`` misses (against the steps
#: compiled for a v5e with nothing kept it reads 8 MB over in
#: ``sdar-bd4-8k``, 22 MB under in ``kanana2-8k``, 674 MB over in
#: ``qwen3next-8k``: PERF.md section 6, PR 37).
FILL = 0.73


def training_state_bytes(module: nn.Module) -> int:
    """What training ``module`` holds beside its activations, from the
    shapes of the parameters it is applied with: the parameters, two
    moments of each (Adam's: the step's arguments) and the one more copy
    :data:`FILL` leaves room beside (a gradient is whole only late in the
    backward pass, when the layers' residuals are gone)."""
    return 4 * sum(x.size * x.dtype.itemsize for x in
                   jax.tree_util.tree_leaves(
                       module.variables.get("params", {})))


def ranked(parts: Mapping[str, int]) -> list:
    """``parts`` (``{name: bytes}``) as ``(name, bytes)`` in :data:`RANK`'s
    order, without the names a model has no layer for."""
    return [(name, parts[name]) for name in RANK if parts.get(name)]


def keep_within(parts: Sequence[Tuple[str, int]], budget: int) -> tuple:
    """The names to keep of ``parts``, ``(name, bytes over all the layers
    that have it)`` in rank order, inside ``budget`` bytes: greedy, each in
    its turn if it still fits, else skipped (a later, smaller one may
    fit)."""
    kept, left = [], budget
    for name, nbytes in parts:
        if nbytes <= left:
            kept.append(name)
            left -= nbytes
    return tuple(kept)


def keep_budget(held: int) -> int:
    """Bytes free for what :func:`recomputed` may keep: :data:`FILL` of the
    mesh devices' memory (by device kind, ``utils/flops.DEVICE_PEAKS``: a
    described device has no allocator to ask, and must get the budget the
    chip gets) less ``held``, what the step holds whatever is kept.  0 on a
    device whose memory is not known."""
    hbm = flops.hbm_bytes()
    return 0 if hbm is None else max(0, int(FILL * hbm) - held)


def recomputed(layer_cls, model: nn.Module, parts: Mapping[str, int],
               held: int):
    """``layer_cls`` recomputed in the backward pass (``nn.remat``), keeping
    the kernels' residuals and, of ``parts`` (``{name: bytes over all of
    ``model``'s layers}``), what :func:`keep_within` fits in :data:`RANK`'s
    order into :func:`keep_budget` of what the step holds anyway: ``held``
    (the activations ``model`` reckons) and ``model``'s training state.
    Called once a trace of the model, so the choice is part of the one
    program the step compiles to; an ``init`` has no backward pass and
    nothing to choose or count."""
    names = ()
    if not model.is_initializing():
        names = keep_within(
            ranked(parts), keep_budget(held + training_state_bytes(model)))
        metrics.record_recompute_kept(
            {name: parts[name] for name in names},
            sum(n for name, n in parts.items() if name not in names))
    return nn.remat(
        layer_cls, policy=jax.checkpoint_policies.save_only_these_names(
            *KERNEL_RESIDUALS, *names))
