"""What a recomputed decoder layer keeps (``models/recompute.py``): always
the residuals the Pallas forward kernels name for their backward kernels
(``ops/flash_attention.FLASH_OUT`` / ``FLASH_LSE``, ``ops/gated_delta.GDN_OUT``
/ ``GDN_STATES`` / ``GDN_INVERSES``), and of the other named outputs what
fits a byte budget, in rank order.  The ranked choice as a pure function at
the benchmark's three cells' shapes; then at toy size on the CPU mesh (no
budget there: tests set one), the kernels in interpreter mode: the gradients
are those of the layers kept whole and of a recompute that keeps nothing,
whatever is kept; the gradient's program calls each forward kernel once a
layer; a part kept is not computed again and a part skipped is; a name
inside a loop over groups is kept; a caller without a checkpoint lowers to
the program it had; the counters read the bytes the benchmark's cells
keep."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from horovod_tpu import metrics
from horovod_tpu.models import (gpt, kanana2, qwen3_next, recompute, scopes,
                                sdar)
from horovod_tpu.models.gpt import next_token_loss
from horovod_tpu.ops import flash_attention as flash
from horovod_tpu.ops import gated_delta as gdn
from horovod_tpu.ops import ssd
from horovod_tpu.parallel import moe

TOKENS = 64
GB = 10 ** 9
#: the benchmark's three cells as their adapters build them (the published
#: widths are the models' defaults) and the ``[b, s]`` a layer sees
CELLS = {
    "qwen3next-8k": (qwen3_next.Qwen3Next(
        num_layers=4, num_experts=16, vocab_size=18992), (1, 8192)),
    "sdar-bd4-8k": (sdar.SDAR(
        num_layers=4, num_experts=16, vocab_size=18992), (1, 16384)),
    "kanana2-8k": (kanana2.Kanana2(
        num_layers=5, num_experts=8, vocab_size=16032), (1, 8192)),
}
_MIXER_OUT = (moe.ROUTING, scopes.KEEP_OUT_PROJ)
#: cell -> {budget in GB: the names kept}; each is the one before and more
KEPT = {
    "qwen3next-8k": {
        0: (),
        1: (moe.ROUTING, scopes.KEEP_GDN_NORM, scopes.KEEP_OUT_PROJ,
            scopes.KEEP_Q_PROJ, flash.FLASH_Q, scopes.KEEP_MLP,
            scopes.KEEP_KV_PROJ, flash.FLASH_K, flash.FLASH_V),
        2: (moe.ROUTING, scopes.KEEP_GDN_NORM, scopes.KEEP_OUT_PROJ,
            scopes.KEEP_Q_PROJ, flash.FLASH_Q, scopes.KEEP_MLP,
            scopes.KEEP_GDN_IN_PROJ, scopes.KEEP_KV_PROJ, gdn.GDN_IN,
            flash.FLASH_K, flash.FLASH_V),      # the convolution's: 403 MB
        5: recompute.RANK},
    "sdar-bd4-8k": {
        0: (),
        1: (*_MIXER_OUT, scopes.KEEP_Q_PROJ, scopes.KEEP_KV_PROJ),
        # k and v at their own 4 heads: 67 MB each over the layers
        2: (*_MIXER_OUT, scopes.KEEP_Q_PROJ, flash.FLASH_Q,
            scopes.KEEP_KV_PROJ, flash.FLASH_K, flash.FLASH_V),
        5: (*_MIXER_OUT, scopes.KEEP_Q_PROJ, flash.FLASH_Q,
            scopes.KEEP_KV_PROJ, flash.FLASH_K, flash.FLASH_V)},
    "kanana2-8k": {
        0: (),
        1: (*_MIXER_OUT, scopes.KEEP_Q_PROJ, scopes.KEEP_KV_PROJ),
        2: (*_MIXER_OUT, scopes.KEEP_Q_PROJ, flash.FLASH_Q,
            scopes.KEEP_MLP, scopes.KEEP_KV_PROJ, flash.FLASH_V),
        5: (*_MIXER_OUT, scopes.KEEP_Q_PROJ, flash.FLASH_Q,
            scopes.KEEP_MLP, scopes.KEEP_KV_PROJ, flash.FLASH_K,
            flash.FLASH_V)},
}


def _ranked(cell):
    model, shape = CELLS[cell]
    parts, _ = model.recompute_parts(*shape)
    assert set(parts) <= set(recompute.RANK)
    return recompute.ranked(parts)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_ranked_choice_at_the_cells_shapes(cell):
    """Greedy in rank order inside the budget; at these budgets each choice
    holds the smaller budget's (a name that no longer fits is skipped, so a
    budget between two of these may keep a smaller, lower name instead)."""
    ranked = _ranked(cell)
    sizes = dict(ranked)
    before = ()
    for gb, names in sorted(KEPT[cell].items()):
        kept = recompute.keep_within(ranked, gb * GB)
        assert kept == tuple(n for n in names if n in sizes), (gb, kept)
        assert sum(sizes[n] for n in kept) <= gb * GB
        assert set(before) <= set(kept)
        before = kept
    assert recompute.keep_within(ranked, sum(sizes.values())) == tuple(sizes)


def test_the_choice_never_passes_its_budget_and_skips_what_does_not_fit():
    parts = [("a", 3), ("b", 2), ("c", 2), ("d", 1)]
    assert recompute.keep_within(parts, 0) == ()
    assert recompute.keep_within(parts, 2) == ("b",)
    assert recompute.keep_within(parts, 4) == ("a", "d")
    assert recompute.keep_within(parts, 5) == ("a", "b")
    assert recompute.keep_within(parts, 8) == ("a", "b", "c", "d")
    for budget in range(10):
        kept = recompute.keep_within(parts, budget)
        assert sum(dict(parts)[n] for n in kept) <= budget


def test_at_the_published_depth_the_same_function_keeps_what_has_room():
    """48 layers of the published widths and 3 GB: the routers' residuals
    (17.8 MB a layer) and the output projections' outputs (33.6 MB a
    layer), not the gated norms' (67.1 MB a DeltaNet layer: 2.4 GB) nor
    anything wider; of the twelve attention layers, k's and v's projections
    and k and v as the kernels take them, at their own two heads, still
    fit."""
    model = qwen3_next.Qwen3Next()
    parts, _ = model.recompute_parts(1, 8192)
    assert parts[scopes.KEEP_OUT_PROJ] == 48 * 8192 * 2048 * 2
    assert recompute.keep_within(recompute.ranked(parts), 3 * GB) == (
        moe.ROUTING, scopes.KEEP_OUT_PROJ, scopes.KEEP_KV_PROJ,
        flash.FLASH_K, flash.FLASH_V)
    assert parts[flash.FLASH_K] == parts[flash.FLASH_V] \
        == 12 * 8192 * 2 * 256 * 2


def test_no_budget_where_the_devices_memory_is_not_known():
    """The CPU mesh (or no mesh at all) has no entry in the peaks' table:
    only the kernels' residuals are kept there."""
    assert recompute.keep_budget(0) == 0
    assert recompute.KERNEL_RESIDUALS == (
        flash.FLASH_OUT, flash.FLASH_LSE, gdn.GDN_OUT, gdn.GDN_STATES,
        gdn.GDN_INVERSES, ssd.SSD_OUT)


def _ids(seed, vocab=256):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (1, TOKENS)), jnp.int32)


def _qwen(interval):
    """(model, a sample, loss of a model and its parameters, layers by the
    forward kernel they call); ``interval`` 1 is full attention in both
    layers, 3 the gated delta rule in both, 2 one of each."""
    model = qwen3_next.qwen3_next_tiny(
        num_layers=2, full_attention_interval=interval, dtype=jnp.float32)
    ids = _ids(0)
    full = sum((i + 1) % interval == 0 for i in range(2))
    return model, ids, lambda m, p: next_token_loss(
        m.apply({"params": p}, ids), ids), {"flash": full, "scan": 2 - full}


def _sdar():
    model = sdar.sdar_tiny(dtype=jnp.float32, qk_norm_init=2.0)
    rng = np.random.default_rng(1)
    batch = (_ids(1, 255),
             jnp.asarray(rng.integers(4096, 65537, (1, TOKENS // 4)),
                         jnp.int32),
             jnp.asarray(rng.integers(0, 65536, (1, TOKENS)), jnp.int32))
    return model, batch, lambda m, p: sdar.block_diffusion_loss(
        m.apply({"params": p}, batch), batch), {"flash": 2, "scan": 0}


def _kanana2():
    """A dense layer and two expert layers whose rows are taken in two
    groups: the routing runs inside ``parallel/moe``'s loop over them."""
    model = kanana2.kanana2_tiny(dtype=jnp.float32, moe_group_rows=32,
                                 moe_capacity_factor=2.0)
    ids = _ids(3)
    return model, ids, lambda m, p: next_token_loss(
        m.apply({"params": p}, ids), ids), {"flash": 3, "scan": 0}


MODELS = {
    "qwen3next-full": lambda: _qwen(1),
    "qwen3next-linear": lambda: _qwen(3),
    "qwen3next-hybrid": lambda: _qwen(2),
    "sdar": _sdar,
    "kanana2": _kanana2,
}
#: the variants that recompute with a policy -> the budget they are given:
#: none (the CPU mesh's own), all the room there is, and half of what every
#: name together would take (some kept, some skipped)
BUDGETS = {"kept": 0, "every": 1 << 40, "half": None}


def _launcher_calls(jaxpr):
    """Calls of the jitted kernel launchers in a jaxpr's text."""
    text = str(jaxpr)
    return {kernel: len(re.findall(rf"name={name}\b", text))
            for kernel, name in [
                ("fwd", "_fwd_call"), ("flash_dq", "_dq_call"),
                ("flash_dkv", "_dkv_call"), ("scan_bwd", "_bwd_call")]}


def _expected_calls(forward_calls, forward_passes):
    flash_layers, scan_layers = forward_calls["flash"], forward_calls["scan"]
    # both kernels' forward launchers are named ``_fwd_call``
    return {"fwd": forward_passes * (flash_layers + scan_layers),
            "flash_dq": flash_layers, "flash_dkv": flash_layers,
            "scan_bwd": scan_layers}


keep_within = recompute.keep_within      # the fixture below wraps it


@pytest.fixture(scope="module", params=sorted(MODELS))
def case(request):
    """``(forward launcher calls a pass, {variant: (loss, gradients, the
    gradient's jaxpr, the names kept)})`` of one model with its layers
    recomputed under each of :data:`BUDGETS`, kept ``whole``
    (``remat=False``) and recomputed by ``nn.remat`` with no policy
    (``nothing``)."""
    model, sample, loss, forward_calls = MODELS[request.param]()
    assert model.remat
    params = model.init(jax.random.PRNGKey(0), sample)["params"]
    parts, _ = model.recompute_parts(
        *(sample[0].shape if isinstance(sample, tuple) else sample.shape))

    def read(m, names=()):
        fn = jax.value_and_grad(lambda p: loss(m, p))
        return (*fn(params), jax.make_jaxpr(fn)(params), names)

    got = {"whole": read(model.clone(remat=False))}
    with pytest.MonkeyPatch.context() as patch:
        for variant, budget in BUDGETS.items():
            if budget is None:
                budget = sum(parts.values()) // 2
            kept = []
            patch.setattr(recompute, "keep_budget", lambda held: budget)
            patch.setattr(recompute, "keep_within", lambda *a: kept.append(
                keep_within(*a)) or kept[-1])
            got[variant] = read(model, kept)
        for module in (qwen3_next, sdar, kanana2):
            patch.setattr(module, "recomputed", lambda cls, *_: nn.remat(cls))
        got["nothing"] = read(model)
    return forward_calls, got


def test_the_budgets_keep_nothing_something_and_everything(case):
    _, got = case
    none, half, every = (set(got[v][3][-1]) for v in ("kept", "half",
                                                       "every"))
    assert not none and none < half < every <= set(recompute.RANK)
    assert {moe.ROUTING, scopes.KEEP_OUT_PROJ} <= half


@pytest.mark.parametrize("kept", sorted(BUDGETS))
@pytest.mark.parametrize("other", ["whole", "nothing"])
def test_the_gradients_are_those_of_the_other_two_ways(case, kept, other):
    _, got = case
    assert float(got[kept][0]) == float(got[other][0])
    for a, b in zip(jax.tree_util.tree_leaves(got[kept][1]),
                    jax.tree_util.tree_leaves(got[other][1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)
    assert min(float(jnp.linalg.norm(g))
               for g in jax.tree_util.tree_leaves(got[kept][1])) > 0.0


@pytest.mark.parametrize("variant,forward_passes", [
    ("kept", 1), ("half", 1), ("every", 1), ("whole", 1), ("nothing", 2)])
def test_a_layer_calls_each_forward_kernel_once(case, variant,
                                                forward_passes):
    """As many forward launchers in the gradient's program as with the
    layers kept whole: the recompute's are dead code once the residuals
    are saved.  Without the policy every forward kernel runs in the forward
    pass and again in the recompute."""
    forward_calls, got = case
    assert _launcher_calls(got[variant][2]) == _expected_calls(
        forward_calls, forward_passes)


def test_everything_but_the_kernels_is_recomputed(case):
    """Without a budget the kernels' names are the only thing that leaves a
    layer's forward pass beside its input: the projections' products are in
    the gradient's program once more than in that of layers kept whole (a
    recompute with no policy has the forward kernels' own products
    besides).  Each name kept takes products out again, down to the few a
    name does not reach (a shared expert's ``down_proj`` under its gate)."""
    _, got = case
    products = {k: str(v[2]).count("dot_general") for k, v in got.items()}
    assert products["nothing"] >= products["kept"] > products["half"] \
        > products["every"] >= products["whole"]


#: a name -> the module whose product makes the value it keeps
PRODUCT_OF = {
    moe.ROUTING: rf"{moe.ROUTE_SCOPE}/dot_general",
    scopes.KEEP_OUT_PROJ: r"/(o_proj|out_proj)/dot_general",
    scopes.KEEP_Q_PROJ: r"/q_proj/dot_general",
    scopes.KEEP_KV_PROJ: r"/(k_proj|kv_a_proj_with_mqa)/dot_general",
    scopes.KEEP_MLP: r"/(shared_|shared_experts_)?gate_proj/dot_general",
    scopes.KEEP_GDN_IN_PROJ: r"/in_proj_qkvz/dot_general",
}


def _run_again(model, loss, params):
    """The paths (``op_name``) of the compiled gradient's ops with JAX's
    mark of a recomputed op on them."""
    text = jax.jit(jax.grad(lambda p: loss(model, p))).lower(
        params).compile().as_text()
    marked = [path for path in re.findall(r'op_name="([^"]+)"', text)
              if f"checkpoint/{qwen3_next.REMAT_MARK}/" in path]
    assert marked
    return marked


@pytest.mark.parametrize("name", ["qwen3next-hybrid", "kanana2"])
def test_a_part_kept_is_not_computed_again_and_a_part_skipped_is(
        monkeypatch, name):
    """In the compiled gradient a kept name's product has no
    ``rematted_computation`` twin and a skipped name's still has: with no
    budget every product of a layer runs again, with half the bytes the
    names that fit are gone, with all of them none is left.  ``kanana2``'s
    toy routes inside ``parallel/moe``'s loop over two groups of rows: JAX
    (0.9.0) hands the checkpoint's policy into a scan's body, so the name
    there is kept as a stacked output of the loop; an upgrade that stops
    doing so fails here, by name.  The routing's top-k and sort go with its
    product: naming the weights alone would not do that (the backward pass
    reads the logits and the picks, so both are named, the picks by the
    top-k's own ``fwd`` rule where a derivative is taken through it:
    ``lax.top_k``'s derivative wants the output of the call it
    differentiates)."""
    model, sample, loss, _ = MODELS[name]()
    params = model.init(jax.random.PRNGKey(0), sample)["params"]
    parts = dict(recompute.ranked(model.recompute_parts(*sample.shape)[0]))
    have = set(PRODUCT_OF) & set(parts)
    assert len(have) >= 5
    for budget in (0, sum(parts.values()) // 2, sum(parts.values())):
        kept = set(recompute.keep_within(recompute.ranked(parts), budget))
        monkeypatch.setattr(recompute, "keep_budget", lambda held: budget)
        flash._flash_fn.cache_clear()
        marked = _run_again(model, loss, params)
        assert {n for n, product in PRODUCT_OF.items() if any(
            re.search(product, path) for path in marked)} == have - kept, \
            budget
        for op in ("top_k", "sort"):
            assert any(re.search(rf"{moe.ROUTE_SCOPE}/(.*/)?{op}\b", path)
                       for path in marked) == (moe.ROUTING not in kept), op
    assert kept >= have and moe.ROUTING in kept


def _named_bytes(jaxpr, found, times=1):
    """``{name: bytes}`` of every ``checkpoint_name`` in ``jaxpr`` and the
    jaxprs its equations hold, a scan's body once a turn of the loop."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            aval = eqn.outvars[0].aval
            found[eqn.params["name"]] = found.get(eqn.params["name"], 0) \
                + times * aval.size * aval.dtype.itemsize
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _named_bytes(sub, found, times * eqn.params.get("length", 1)
                         if eqn.primitive.name == "scan" else times)
    return found


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_bytes_a_model_reckons_are_the_bytes_its_names_hold(name):
    """``recompute_parts`` is arithmetic on shapes; the forward pass's own
    ``checkpoint_name``s say what it has to come to (the names inside the
    kernels' ``fwd`` rules are their inputs' sizes and show only under
    differentiation; the held experts' sizes, a few integers a group, are
    named and not reckoned)."""
    model, sample, _, _ = MODELS[name]()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            sample)["params"]
    found = _named_bytes(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, sample))(params).jaxpr, {})
    rows = sample[0] if isinstance(sample, tuple) else sample
    b, s = rows.shape
    if isinstance(sample, tuple):
        s *= 2                          # both copies
    parts = dict(recompute.ranked(model.recompute_parts(b, s)[0]))
    in_kernels = {flash.FLASH_Q, flash.FLASH_K, flash.FLASH_V, gdn.GDN_IN}
    assert set(found) == set(parts) - in_kernels
    # the softmax rule's top-k names its values and picks in a ``fwd`` rule
    # too (``moe._top_k``); the sigmoid rule's are in the open
    in_top_k = 0 if name == "kanana2" else (
        2 * 4 * b * s * model.num_experts_per_tok * model.num_layers)
    for key, nbytes in found.items():
        if key == moe.ROUTING:
            assert 0 < nbytes + in_top_k - parts[key] < 64 * model.num_layers
        else:
            assert nbytes == parts[key], key


def _normalised(text):
    """StableHLO without the counter the symbol table appends to private
    functions' names (a ``name`` equation moves it by one)."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1_N", text)


def test_a_caller_without_a_checkpoint_lowers_to_the_program_it_had(
        monkeypatch):
    """``gpt2_small``'s family keeps its layers whole: with the names in
    the forward rule and without them its gradient is the same program."""
    model = gpt.gpt_tiny(dtype=jnp.float32)
    ids = _ids(2)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]

    def lowered():
        flash._flash_fn.cache_clear()
        return jax.jit(jax.grad(lambda p: next_token_loss(
            model.apply({"params": p}, ids), ids))).lower(params).as_text()

    with_names = lowered()
    assert "_fwd_call" in with_names
    monkeypatch.setattr(flash, "checkpoint_name", lambda x, name: x)
    without = lowered()
    flash._flash_fn.cache_clear()
    assert _normalised(with_names) == _normalised(without)
    assert flash.FLASH_OUT not in with_names


def _counter(metric, label):
    return {s["labels"][label]: s["value"]
            for s in metrics.registry.snapshot()["metrics"].get(
                metric, {}).get("samples", [])}


def _residual_bytes():
    return _counter("hvd_kernel_residual_bytes_traced_total", "kernel")


@pytest.mark.parametrize("cell,budget,kept,skipped", [
    # every name of the cell, by hand from its shapes (bfloat16; float32
    # logits and int32 picks, weights, order)
    ("qwen3next-8k", 4 * GB, {
        moe.ROUTING: 4 * 4 * 8192 * (512 + 3 * 10),
        scopes.KEEP_GDN_NORM: 3 * 8192 * 4096 * 2,
        scopes.KEEP_OUT_PROJ: 4 * 8192 * 2048 * 2,
        scopes.KEEP_Q_PROJ: 8192 * 8192 * 2,
        flash.FLASH_Q: 8192 * 4096 * 2,
        scopes.KEEP_MLP: 4 * 2 * 8192 * 512 * 2,
        scopes.KEEP_GDN_IN_PROJ: 3 * 8192 * (12288 + 64) * 2,
        scopes.KEEP_KV_PROJ: 2 * 8192 * 512 * 2,
        gdn.GDN_IN: 3 * 8192 * (8192 * 2 + 2 * 32 * 4),
        scopes.KEEP_GDN_CONV: 3 * 8192 * 8192 * 2,
        flash.FLASH_K: 8192 * 512 * 2,
        flash.FLASH_V: 8192 * 512 * 2}, 0),
    # what 1.4 GB leave of the cell whose k and v come at 4 heads: not k's
    # and v's projections (134 MB), nor k and v as the kernels take them
    ("sdar-bd4-8k", 14 * GB // 10, {
        moe.ROUTING: 4 * 4 * 16384 * (128 + 3 * 8),
        scopes.KEEP_OUT_PROJ: 4 * 16384 * 2048 * 2,
        scopes.KEEP_Q_PROJ: 4 * 16384 * 4096 * 2,
        flash.FLASH_Q: 4 * 16384 * 4096 * 2},
     4 * 2 * 16384 * 512 * 2 + 2 * 4 * 16384 * 512 * 2),
    # and the 2 GB that refused 1.07 GB of k and v at 32 heads keep it all
    ("sdar-bd4-8k", 2 * GB, {
        moe.ROUTING: 4 * 4 * 16384 * (128 + 3 * 8),
        scopes.KEEP_OUT_PROJ: 4 * 16384 * 2048 * 2,
        scopes.KEEP_Q_PROJ: 4 * 16384 * 4096 * 2,
        flash.FLASH_Q: 4 * 16384 * 4096 * 2,
        scopes.KEEP_KV_PROJ: 4 * 2 * 16384 * 512 * 2,
        flash.FLASH_K: 4 * 16384 * 512 * 2,
        flash.FLASH_V: 4 * 16384 * 512 * 2}, 0),
    ("kanana2-8k", 4 * GB, {
        moe.ROUTING: 4 * 4 * 8192 * (128 + 3 * 6),
        scopes.KEEP_OUT_PROJ: 5 * 8192 * 2048 * 2,
        scopes.KEEP_Q_PROJ: 5 * 8192 * 6144 * 2,
        flash.FLASH_Q: 5 * 8192 * 6144 * 2,
        scopes.KEEP_MLP: 2 * 8192 * (6144 + 4 * 1536) * 2,
        scopes.KEEP_KV_PROJ: 5 * 8192 * 576 * 2,
        flash.FLASH_K: 5 * 8192 * 6144 * 2,
        flash.FLASH_V: 5 * 8192 * 4096 * 2}, 0),
])
def test_the_counter_reads_what_the_cells_keep_and_what_they_skip(
        monkeypatch, cell, budget, kept, skipped):
    """``hvd_recompute_kept_bytes_traced_total{name}``: the bytes kept
    under each name over all the layers, and under ``skipped`` what the
    budget refused, once a trace."""
    monkeypatch.setattr(metrics.registry, "enabled", True)
    monkeypatch.setattr(recompute, "keep_budget", lambda held: budget)
    model, shape = CELLS[cell]
    metric = "hvd_recompute_kept_bytes_traced_total"
    before = _counter(metric, "name")
    applied = nn.Dense(1).bind({"params": {}})
    recompute.recomputed(nn.Dense, applied, *model.recompute_parts(*shape))
    after = _counter(metric, "name")
    assert {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)} == {
                **kept, **({"skipped": skipped} if skipped else {})}


@pytest.mark.parametrize("kernel,nbytes", [
    # sdar-bd4-8k: o bfloat16 [1, 32, 16384, 128] + lse float32 [1, 32, 16384]
    ("flash", 134_217_728 + 2_097_152),
    # qwen3next-8k: o bfloat16 [1, 8192, 32 x 128], states float32
    # [1, 32, 128, 128, 128], inverses float32 [1, 16, 128, 64, 128]
    ("gdn_scan", 67_108_864 + 268_435_456 + 67_108_864)])
def test_the_counter_reads_what_a_layer_of_the_benchmarks_cells_keeps(
        monkeypatch, kernel, nbytes):
    monkeypatch.setattr(metrics.registry, "enabled", True)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype)

    if kernel == "flash":
        x = shape(1, 16384, 32, 128)
        fn, args = (lambda q, k, v: flash.flash_attention(
            q, k, v, mask=flash.block_diffusion_mask(4, 8192), interpret=True),
            (x, x, x))
    else:
        qk, v = shape(1, 8192, 16, 128), shape(1, 8192, 32, 128)
        gb = shape(1, 8192, 32, dtype=jnp.float32)
        fn, args = (lambda *a: gdn.gated_delta_rule(*a, interpret=True),
                    (qk, qk, v, gb, gb))
    before = _residual_bytes().get(kernel, 0)
    jax.make_jaxpr(fn)(*args)  # the plain call keeps nothing
    assert _residual_bytes().get(kernel, 0) == before
    jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        fn(*a).astype(jnp.float32)), argnums=(0, 1, 2)))(*args)
    assert _residual_bytes()[kernel] - before == nbytes
