"""The device scopes of the decoders (``models/scopes.py``), from the
``op_name`` metadata of a tiny model's gradient compiled for the CPU (the
kernels in interpreter mode): every matrix product, convolution and custom
call of a decoder layer and of the head is under exactly one documented
part; no part's name shows outside the module it belongs to; the ops JAX
marks as recomputed (``qwen3_next.REMAT_MARK``) hold every part a recompute
runs and none of the kept kernels; GPT names its head and nothing else; a
scope adds nothing to the lowered program; ``docs/profiling.md`` lists the
scopes the program emits."""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import (gpt, kanana2, lfm2, mellum2, nemotron_h,
                                qwen3_next, scopes, sdar)
from horovod_tpu.models.gpt import next_token_loss
from horovod_tpu.ops import flash_attention as flash
from horovod_tpu.ops import gated_delta as gdn
from horovod_tpu.ops import ssd
from horovod_tpu.parallel import moe

TOKENS = 64
#: HLO opcodes of what the chip gives matrix or kernel time: on the CPU the
#: Pallas kernels are interpreted, so a kernel is many ops under its name
PRODUCTS = ("dot", "convolution", "custom-call")
#: the step's own scopes (``training.py``), which these gradients are put
#: under as ``make_train_step`` puts a model
FORWARD, LOSS = "hvd_forward", "hvd_loss"
#: what a recomputed layer (``qwen3_next.recomputed``) does not run a second
#: time: the forward kernels, whose residuals it keeps (of the scan's scope
#: the recompute holds the transposes that lay ``g`` and ``beta`` out, not
#: the kernel); the backward kernels, which only the backward pass calls;
#: the experts' tile products, which ``parallel/moe``'s own backward rule
#: runs again inside its loops (the recompute needs the routing, not the
#: layer's output); and the state-space scan, whose output a block keeps
#: and whose backward rule runs the chunk algebra again itself, from
#: operands that are named as they arrive (nothing under the scan's scope
#: makes them)
NOT_RECOMPUTED = {*scopes.FLASH_KERNELS, gdn.FWD_KERNEL, gdn.BWD_KERNEL,
                  moe.EXPERTS_SCOPE, ssd.SCAN_SCOPE}
#: {a kernel name nested in a documented part: the part}
NESTED = {kernel: part for part, kernels in scopes.NESTED.items()
          for kernel in kernels}


def _ids(seed, vocab=256):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, vocab, (1, TOKENS)), jnp.int32)


def _causal(model):
    ids = _ids(0)
    return model, ids, lambda logits: next_token_loss(logits, ids)


def _sdar():
    rng = np.random.default_rng(1)
    batch = (_ids(1, 255),
             jnp.asarray(rng.integers(4096, 65537, (1, TOKENS // 4)),
                         jnp.int32),
             jnp.asarray(rng.integers(0, 65536, (1, TOKENS)), jnp.int32))
    return (sdar.sdar_tiny(num_layers=1), batch,
            lambda logits: sdar.block_diffusion_loss(logits, batch))


_MOE = (moe.ROUTE_SCOPE, moe.EXPERTS_SCOPE)
#: {model: (its builder, {module of a layer, or "" for outside the layers:
#: {its blocks: the parts it emits}})}
MODELS = {
    "qwen3_next_tiny": (
        lambda: _causal(qwen3_next.qwen3_next_tiny(num_layers=2)),
        {"self_attn": {scopes.ATTN: scopes.PARTS[scopes.ATTN]},
         "linear_attn": {scopes.GDN: scopes.PARTS[scopes.GDN]},
         "mlp": {scopes.MOE: scopes.PARTS[scopes.MOE]},
         "": {scopes.HEAD: ()}}),
    "sdar_tiny": (
        _sdar,
        {"self_attn": {scopes.ATTN: scopes.PARTS[scopes.ATTN]},
         "mlp": {scopes.MOE: _MOE},          # no shared expert
         "": {scopes.HEAD: (), scopes.BD_NOISE: (),
              scopes.BD_HEAD_ROWS: ()}}),
    "kanana2_tiny": (
        lambda: _causal(kanana2.kanana2_tiny(num_layers=2)),
        {"self_attn": {scopes.MLA: scopes.PARTS[scopes.MLA]},
         "mlp": {scopes.DENSE_MLP: (), scopes.MOE: scopes.PARTS[scopes.MOE]},
         "": {scopes.HEAD: ()}}),
    # one period: three window layers and a full one; the kind of a layer
    # is a scope between ``hvd_attn`` and its parts (``scopes.KINDS``)
    "mellum2_tiny": (
        lambda: _causal(mellum2.mellum2_tiny()),
        {"self_attn": {scopes.ATTN: (*scopes.KINDS[scopes.ATTN],
                                     *scopes.PARTS[scopes.ATTN])},
         "mlp": {scopes.MOE: _MOE},          # no shared expert
         "": {scopes.HEAD: (), scopes.ROTARY_TABLES: ()}}),
    # blocks of one part: a state-space mixer, relu^2 experts beside a
    # shared one, or attention, all three under the module name ``mixer``
    "nemotron_h_tiny": (
        lambda: _causal(nemotron_h.nemotron_h_tiny()),
        {"mixer": {scopes.SSM: scopes.PARTS[scopes.SSM],
                   scopes.ATTN: scopes.PARTS[scopes.ATTN],
                   scopes.MOE: scopes.PARTS[scopes.MOE]},
         "": {scopes.HEAD: ()}}),
    # two operator kinds times two feed-forward kinds: a dense convolution
    # layer, an attention layer and a convolution layer with experts
    "lfm2_tiny": (
        lambda: _causal(lfm2.lfm2_tiny(layer_types=lfm2.LAYER_TYPES[1:4])),
        {"conv": {scopes.SCONV: scopes.PARTS[scopes.SCONV]},
         "self_attn": {scopes.ATTN: scopes.PARTS[scopes.ATTN]},
         "feed_forward": {scopes.DENSE_MLP: (), scopes.MOE: _MOE},
         "": {scopes.HEAD: ()}}),
    "gpt_tiny": (lambda: _causal(gpt.gpt_tiny(vocab_size=256)), {}),
}
DECODERS = sorted(set(MODELS) - {"gpt_tiny"})
#: {model: the parts its recompute never runs}: where a block is one part
#: (``nemotron_h``), a block's last projection feeds nothing the block
#: computes again, and ``hvd_attn_out`` holds ``o_proj`` alone (the gated
#: norm before ``out_proj`` and the shared expert's ``up`` are read by their
#: products' weight gradients, so ``hvd_ssm_out`` and ``hvd_moe_shared`` do
#: run again)
LAST_OF_A_BLOCK = {"nemotron_h_tiny": {scopes.ATTN_OUT}}


def _lowered(name):
    """The gradient of ``name``'s loss under the step's scopes, lowered."""
    flash._flash_fn.cache_clear()
    model, sample, loss = MODELS[name][0]()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            sample)["params"]

    def forward(p):
        with jax.named_scope(FORWARD):
            logits = model.apply({"params": p}, sample)
            with jax.named_scope(LOSS):
                return loss(logits)

    return jax.jit(jax.grad(forward)).lower(params)


@pytest.fixture(scope="module")
def lowered_of():
    """``name -> the lowered gradient``, lowered once a model."""
    got = {}
    return lambda name: got.get(name) or got.setdefault(name, _lowered(name))


@pytest.fixture(scope="module")
def ops_of(lowered_of):
    """``name -> [(opcode, path)]`` of every instruction under ``hvd_forward``
    of the gradient compiled for the CPU (``op_name`` as a device trace
    shows it as ``tf_op``: XLA has inlined the jitted launchers and the
    loops' bodies, so a path is whole), compiled once a model."""
    got = {}

    def read(name):
        if name not in got:
            text = lowered_of(name).compile().as_text()
            # an instruction XLA made of several keeps each one's path,
            # joined by ";" (two reshapes merged): each is a path
            got[name] = [
                (op, path) for op, paths in re.findall(
                    r"^\s*(?:ROOT )?\S+ = .*? ([a-z][a-z-]*)\(.*"
                    r"op_name=\"([^\"]+)\"", text, re.M)
                for path in paths.split(";") if FORWARD in path]
        return got[name]
    return read


def _hoisted(path):
    """Loop constants (an iota, a fill) that JAX hoists out of the loop over
    a call's groups when it splits the recompute off: they keep the
    innermost scope and lose the module's path."""
    return qwen3_next.REMAT_MARK in path and "/layers_" not in path


def _hvd(path):
    """The program's scopes on a path, outermost first, the step's own
    apart (``jvp(hvd_forward)`` is a transform's wrapper, no component)."""
    return [c for c in path.split("/") if c.startswith("hvd_")]


def _all_leaves(name):
    """The parts ``name``'s products may sit under, a block with none being
    its own."""
    return {part for blocks in MODELS[name][1].values()
            for block, parts in blocks.items() if block in scopes.PARTS
            for part in (parts or (block,))
            if part not in scopes.KINDS.get(block, ())}


@pytest.mark.parametrize("name", DECODERS)
def test_every_product_is_under_exactly_one_part(ops_of, name):
    """Layers and head alike: nothing the chip spends matrix or kernel
    time on is left to ``hvd_forward/dot_general``."""
    leaves = _all_leaves(name)
    products = [(op, path) for op, path in ops_of(name) if op in PRODUCTS]
    assert len(products) > 20
    for op, path in products:
        # a kernel's name is on its path twice: its scope and its ``name=``
        under = {c for c in _hvd(path) if c in leaves}
        assert len(under) == 1, (op, path)
    assert any("layers_" not in path for _, path in products)  # the head's


@pytest.mark.parametrize("name", DECODERS)
def test_no_part_shows_outside_its_module_and_none_is_undocumented(
        ops_of, name):
    modules = MODELS[name][1]
    seen = set()
    for _, path in ops_of(name):
        names = [c for c in _hvd(path) if c != LOSS]
        seen.update(names)
        if not names:
            continue
        layer = re.search(r"/layers_\d+/(\w+)/", path)
        if _hoisted(path):
            assert names == [moe.ROUTE_SCOPE], path
            continue
        blocks = modules[layer.group(1) if layer else ""]
        # the outermost is the module's block, the rest are its parts (a
        # kernel's own name inside its part's)
        assert names[0] in blocks, path
        allowed = set(scopes.PARTS.get(names[0], ())) | set(NESTED)
        kinds = scopes.KINDS.get(names[0], ())
        if kinds and set(kinds) & set(blocks[names[0]]):
            # a layer's kind comes next, once, and its parts inside it
            assert names[1] in kinds and not set(names[2:]) & set(kinds), \
                path
            names = [names[0], *names[2:]]
        for inner in names[1:]:
            assert inner in allowed, path
            assert NESTED.get(inner, names[0]) in names, path
    assert seen <= set(scopes.documented())
    assert seen - set(NESTED) == {
        n for blocks in modules.values() for block, parts in blocks.items()
        for n in (block, *parts)}


@pytest.mark.parametrize("name", DECODERS)
def test_the_recompute_is_marked_and_holds_every_part_it_runs(ops_of, name):
    """JAX's own mark: ``.../checkpoint/rematted_computation/layers_<i>/
    <scopes>/<primitive>``.  A JAX that renames it fails here, not in a
    metric that reads nothing."""
    assert qwen3_next.REMAT_MARK == "rematted_computation"
    marked = [path for _, path in ops_of(name)
              if f"checkpoint/{qwen3_next.REMAT_MARK}/" in path]
    assert marked
    for path in marked:
        assert _hoisted(path) or re.search(
            rf"checkpoint/{qwen3_next.REMAT_MARK}/layers_\d+/", path), path
        assert path.startswith(f"jit(forward)/transpose(jvp({FORWARD}))/")
    held = {c for path in marked for c in _hvd(path)}
    layer_leaves = _all_leaves(name) - {scopes.HEAD}
    want = layer_leaves - NOT_RECOMPUTED - LAST_OF_A_BLOCK.get(name, set())
    # the recompute's layout swaps: the CPU's compiler folds them into the
    # interpreted kernels' slices, the chip's runs them
    assert want - {flash.LAYOUT_SCOPE} <= held & layer_leaves <= want
    assert not held & NOT_RECOMPUTED
    # the first run and the transposed ops carry no mark, and both are there
    first = [p for _, p in ops_of(name) if f"/jvp({FORWARD})/" in p
             and "transpose(" not in p and "/layers_" in p]
    assert first and not any(qwen3_next.REMAT_MARK in p for p in first)
    assert any("transpose(" in p and qwen3_next.REMAT_MARK not in p
               and scopes.MOE in p for _, p in ops_of(name))


def test_gpt_names_its_head_and_nothing_else(ops_of):
    """The blocks are the dense zoo's ``EncoderLayer``: one family of
    products already, and no recompute."""
    seen = set()
    head_products = 0
    for op, path in ops_of("gpt_tiny"):
        names = [c for c in _hvd(path) if c != LOSS]
        seen.update(names)
        if op == "dot" and "wte.attend" in path:
            assert names == [scopes.HEAD], path
            head_products += 1
        if scopes.HEAD in names:
            assert "EncoderLayer" not in path, path
            assert re.search(r"/hvd_head/(LayerNorm_0|wte\.attend|\w+$)",
                             path), path
    assert head_products == 3  # forward, and two transposes
    assert seen == {scopes.HEAD, *scopes.FLASH}
    assert not any(qwen3_next.REMAT_MARK in p for _, p in ops_of("gpt_tiny"))


def _normalised(text):
    """As ``tests/test_recompute.py``: without the symbol table's
    counters."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1_N", text)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_scope_adds_nothing_to_the_lowered_program(monkeypatch, lowered_of,
                                                     name):
    """Scopes are metadata: with ``jax.named_scope`` a no-op the gradient
    lowers to the same text (locations are not printed)."""
    with_scopes = lowered_of(name).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _lowered(name).as_text()
    flash._flash_fn.cache_clear()
    assert _normalised(with_scopes) == _normalised(without)
    assert "hvd_" not in re.sub(r"hvd_(flash|gdn_scan)_\w+", "", without)


def test_the_docs_table_lists_the_scopes_the_program_emits():
    """``docs/profiling.md``'s scope table against ``models/scopes.py``'s
    list, the kernels' names and the step's own scopes."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "docs",
                        "profiling.md")
    with open(path) as fh:
        text = fh.read()
    table = text[text.index("| scope | what runs under it |"):]
    table = table[:table.index("\n\n")]
    listed = set()
    for row in table.splitlines()[2:]:
        first = row.split("|")[1]
        if "counter, not a scope" in first:
            continue
        listed.update(re.findall(r"hvd_[a-z_]+", first))
    step = {"hvd_forward", "hvd_loss", "hvd_grad_allreduce", "hvd_bucket_",
            "hvd_loss_allreduce", "hvd_optimizer_update"}
    assert listed == set(scopes.documented()) | step
    assert qwen3_next.REMAT_MARK in text


def _compiled_names(scope, cache_dir):
    """The ``op_name``s of a small program under ``scope``, compiled through
    the persistent cache at ``cache_dir``."""
    def f(x):
        with jax.named_scope(scope):
            return jnp.dot(x, x) + 1.0

    text = jax.jit(f).lower(jnp.ones((16, 16))).compile().as_text()
    return set(re.findall(r'op_name="jit\(f\)/(\w+)/', text))


def test_the_compile_cache_is_keyed_by_the_scope_names(monkeypatch,
                                                       tmp_path):
    """JAX keys a cached program without its metadata: a program that
    differs in its scopes alone loads the executable, and the names, of
    whoever compiled first.  ``core`` folds the list of names into the key
    (``cache_key.custom_hook``, JAX 0.9.0): another list, another entry."""
    from jax._src import cache_key
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu import core

    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(cache_key, "custom_hook", cache_key.custom_hook)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(core, "_COMPILE_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        core._place_compile_cache("tpu")
        compilation_cache.reset_cache()
        assert cache_key.custom_hook().startswith("hvd_scopes:")
        assert _compiled_names("hvd_first", tmp_path) == {"hvd_first"}
        # the fault: the same program under another name is the first's
        assert _compiled_names("hvd_second", tmp_path) == {"hvd_first"}
        # the cure: another list of names is another key
        monkeypatch.setattr(scopes, "OUTSIDE_LAYERS",
                            (*scopes.OUTSIDE_LAYERS, "hvd_second"))
        core._place_compile_cache("tpu")
        assert _compiled_names("hvd_second", tmp_path) == {"hvd_second"}
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
