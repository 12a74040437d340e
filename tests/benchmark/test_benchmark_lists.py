"""Which cells list which per-layer metric, and what an accepted entry says,
held by name and by rule against ``BENCHMARK.json`` as it stands — not
against a list of cells or a position in ``per_layer`` written out here.

Until PR 40 these assertions were spelt out once a PR (the four cells of PR
25, "PR 26's eight are the last", "the expected cells lack ..."), every
next PR that appended a cell or a metric broke the copy before it, and
``tests/conftest.py`` marked twenty-two of them as strict expected
failures.  Here a rule says *why* a cell lists a reader (its configuration
has the scope, the kernel or the keys the reader goes by), the cells come
from ``workloads`` and a metric's cells from its own ``workloads`` list.  A
PR that appends a cell of a configuration this file knows is held to the
rules; a new configuration's cell is its own PR's tests' to hold, and a new
metric is no case here."""

import json
import os

import pytest

import benchmark_tiny
from benchmarks.harness.spec import Spec


def _json(*rel):
    with open(os.path.join(benchmark_tiny.REPO, *rel)) as fh:
        return json.load(fh)


BENCH = _json("BENCHMARK.json")
CONFIG_FILES = {c["name"]: c["file"] for c in BENCH["configs"]}
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
RATES = {m["name"]: m for m in BENCH["end_to_end"]}

_MS = ("ms", "lower", "device_trace", "mfu")
_SHARE = ("%", "higher", "device_trace", "mfu")
#: {layer, letter for letter as PERF.md section 3 has it: {metric: (unit,
#: better, source, moves)}}: the sixty-four entries ``per_layer`` had when
#: this file was written, which later PRs may not edit
ACCEPTED = {
    "harness, core.init and the compile cache": {
        "init_s": ("s", "lower", "host_clock", "setup_s"),
        "compile_s": ("s", "lower", "host_clock", "setup_s"),
    },
    "data/loader": {
        "input_wait_ms": ("ms", "lower", "host_clock", "mfu"),
    },
    "training.make_train_step host wrapper": {
        "dispatch_ms": ("ms", "lower", "host_clock", "mfu"),
    },
    "model step on the device": {
        "fwd_bwd_ms": _MS,
        "fwd_ms": _MS,
        "bwd_ms": _MS,
        "optimizer_ms": _MS,
        "unscoped_ms": _MS,
        "loss_ms": _MS,
        "bd_noise_ms": _MS,
        "recompute_ms": _MS,
        "recompute_mixer_ms": _MS,
        "recompute_moe_ms": _MS,
        "head_ms": _MS,
    },
    "ops/fusion and ops/collectives": {
        "allreduce_ms": _MS,
        "allreduce_exposed_ms": _MS,
        "allreduce_mb": ("MB", "lower", "device_trace", "mfu"),
        "grad_pack_ms": _MS,
    },
    "kernels: ops/flash_attention": {
        "flash_ms": _MS,
        "flash_roofline": _SHARE,
        "flash_fwd_ms": _MS,
        "flash_dq_ms": _MS,
        "flash_dkv_ms": _MS,
        "flash_fwd_roofline": _SHARE,
        "flash_dq_roofline": _SHARE,
        "flash_dkv_roofline": _SHARE,
        "flash_gqa_roofline": _SHARE,
        "flash_bd_roofline": _SHARE,
        "flash_bd_fwd_roofline": _SHARE,
        "flash_bd_dq_roofline": _SHARE,
        "flash_bd_dkv_roofline": _SHARE,
        "flash_mla_roofline": _SHARE,
        "flash_mla_fwd_roofline": _SHARE,
        "flash_mla_dq_roofline": _SHARE,
        "flash_mla_dkv_roofline": _SHARE,
        "flash_layout_ms": _MS,
        "flash_swa_roofline": _SHARE,
        "flash_swa_fwd_roofline": _SHARE,
        "flash_swa_dq_roofline": _SHARE,
        "flash_swa_dkv_roofline": _SHARE,
        "flash_full_roofline": _SHARE,
    },
    "kernels: XLA convolutions": {
        "conv_ms": _MS,
        "conv_roofline": _SHARE,
    },
    "device": {
        "device_idle_pct": ("%", "lower", "device_trace", "mfu"),
        "hbm_gb": ("GB", "lower", "program_counter", "mfu"),
    },
    "mixers: models/qwen3_next gated DeltaNet": {
        "gdn_ms": _MS,
        "gdn_proj_ms": _MS,
        "gdn_conv_ms": _MS,
    },
    "kernels: ops/gated_delta": {
        "gdn_scan_ms": _MS,
        "gdn_scan_roofline": _SHARE,
    },
    "parallel/moe routed experts": {
        "moe_ms": _MS,
        "moe_route_ms": _MS,
        "moe_experts_roofline": _SHARE,
        "moe_tiles": ("tiles", "lower", "device_trace", "mfu"),
        "bd_experts_roofline": _SHARE,
        "mla_experts_roofline": _SHARE,
        "swa_experts_roofline": _SHARE,
    },
    "mixers: models/kanana2 latent attention": {
        "mla_ms": _MS,
        "mla_latent_ms": _MS,
        "mla_proj_ms": _MS,
    },
    "mixers: models/qwen3_next and models/sdar softmax attention": {
        "attn_proj_ms": _MS,
    },
    "mixers: models/mellum2 attention by layer kind": {
        "attn_window_ms": _MS,
        "attn_full_ms": _MS,
    },
}
LAYER_OF = {name: layer for layer, metrics in ACCEPTED.items()
            for name in metrics}

GPT2, RESNET = "gpt2_small", "resnet50"
QWEN, SDAR = "qwen3_next_80b_a3b", "sdar_30b_a3b_chat"
KANANA2, MELLUM2 = "kanana2_30b_a3b", "mellum2_12b_a2p5b"
#: the configurations whose cells the rules below speak for
KNOWN = {GPT2, RESNET, QWEN, SDAR, KANANA2, MELLUM2}


def _of(*configs):
    return lambda cell, cfg: cell["config"] in configs


def _every(cell, cfg):
    return True


def _tokens(cell, cfg):
    """A transformer cell: it reports tokens a second."""
    return cell["name"] in RATES["tokens_per_s_chip"]["workloads"]


def _four_chips(cell, cfg):
    return cell["chips"] == 4


def _routed_experts(cell, cfg):
    return "router_num_experts" in cfg


def _recomputes(cell, cfg):
    return cfg.get("remat") == "decoder_layer"


#: {why a cell lists a reader: (the rule, the readers)}
RULES = {
    "no workloads key, or every cell: the harness's own clocks, the whole "
    "step's blocks, the device": (_every, [
        "init_s", "compile_s", "input_wait_ms", "dispatch_ms", "fwd_bwd_ms",
        "device_idle_pct", "hbm_gb", "fwd_ms", "bwd_ms", "unscoped_ms"]),
    "all-reduces exist only across chips": (_four_chips, [
        "allreduce_ms", "allreduce_exposed_ms", "allreduce_mb"]),
    "by scope or kernel name, in every transformer: the flash kernels and "
    "their layout, the head and its loss, the pack (XLA cancels ResNet's "
    "on one chip)": (_tokens, [
        "flash_ms", "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
        "flash_layout_ms", "head_ms", "loss_ms", "grad_pack_ms"]),
    "convolutions": (_of(RESNET), ["conv_ms", "conv_roofline"]),
    "GPT-2's keys taken from the configuration (and XLA folds SGD's update "
    "into ResNet's convolutions)": (_of(GPT2), [
        "flash_roofline", "flash_fwd_roofline", "flash_dq_roofline",
        "flash_dkv_roofline", "optimizer_ms"]),
    "the gated DeltaNet's scopes and kernels, this configuration's own "
    "keys": (_of(QWEN), [
        "gdn_ms", "gdn_scan_ms", "gdn_scan_roofline", "gdn_proj_ms",
        "gdn_conv_ms", "moe_experts_roofline", "flash_gqa_roofline"]),
    "the block-diffusion mask's pairs and both copies' rows": (_of(SDAR), [
        "flash_bd_roofline", "flash_bd_fwd_roofline", "flash_bd_dq_roofline",
        "flash_bd_dkv_roofline", "bd_experts_roofline", "bd_noise_ms"]),
    "latent attention's scopes and head sizes": (_of(KANANA2), [
        "mla_ms", "mla_latent_ms", "mla_proj_ms", "flash_mla_roofline",
        "flash_mla_fwd_roofline", "flash_mla_dq_roofline",
        "flash_mla_dkv_roofline", "mla_experts_roofline"]),
    "attention by layer kind": (_of(MELLUM2), [
        "attn_window_ms", "attn_full_ms", "flash_swa_roofline",
        "flash_swa_fwd_roofline", "flash_swa_dq_roofline",
        "flash_swa_dkv_roofline", "flash_full_roofline",
        "swa_experts_roofline"]),
    "softmax attention under hvd_attn_qkv / hvd_attn_out (latent "
    "attention has its own parts)": (_of(QWEN, SDAR, MELLUM2), [
        "attn_proj_ms"]),
    "parallel/moe.routed_experts: the configuration names a router": (
        _routed_experts, ["moe_ms", "moe_route_ms", "moe_tiles"]),
    "JAX's mark of a recomputed op: the configuration recomputes its "
    "decoder layers": (_recomputes, [
        "recompute_ms", "recompute_mixer_ms", "recompute_moe_ms"]),
}
RULE_OF = {name: (why, rule) for why, (rule, names) in RULES.items()
           for name in names}


def test_every_accepted_entry_has_a_rule_and_a_layer_once():
    ruled = [n for _, names in RULES.values() for n in names]
    assert sorted(ruled) == sorted(LAYER_OF) and len(ruled) == 64
    assert len(set(ruled)) == len(ruled)


@pytest.mark.parametrize("name", sorted(LAYER_OF))
def test_an_accepted_entry_says_what_it_said(name):
    """Unit, direction, source, the end-to-end metric it moves and its
    layer, letter for letter: by name, wherever in ``per_layer`` it stands
    and whatever follows it."""
    entry = ENTRIES[name]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ACCEPTED[LAYER_OF[name]][name]
    assert entry["layer"] == LAYER_OF[name]
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert os.path.isfile(os.path.join(
        benchmark_tiny.REPO, BENCH["paths"][0], "layer_metrics",
        name + ".py"))


@pytest.mark.parametrize("name", sorted(LAYER_OF))
def test_a_reader_is_listed_where_its_rule_says(name):
    """Among the cells of the configurations this file knows, a reader's
    ``workloads`` (every cell, where the entry has no such key) are the
    cells its rule names, no more and no fewer."""
    why, rule = RULE_OF[name]
    cells = [w for w in BENCH["workloads"] if w["config"] in KNOWN]
    want = {w["name"] for w in cells
            if rule(w, _json(CONFIG_FILES[w["config"]]))}
    listed = set(ENTRIES[name].get(
        "workloads", [w["name"] for w in BENCH["workloads"]]))
    assert listed & {w["name"] for w in cells} == want, why
    assert want, f"{name}: no cell is left to read it"
    # and a listed cell reports the end-to-end metric the reader moves
    moved = RATES[ENTRIES[name]["moves"]]
    assert listed <= set(moved.get(
        "workloads", [w["name"] for w in BENCH["workloads"]]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_a_cell_reads_what_lists_it(cell):
    """``Spec`` hands a cell the readers that list it and no others, each a
    module with a ``read``."""
    mine = Spec(benchmark_tiny.REPO).cell(cell)
    assert set(mine.per_layer) == {
        m["name"] for m in BENCH["per_layer"]
        if cell in m.get("workloads", [cell])}
    assert set(mine.end_to_end) == {
        m["name"] for m in BENCH["end_to_end"]
        if cell in m.get("workloads", [cell])}
    assert all(hasattr(m, "read") for m in mine.per_layer.values())


def test_the_end_to_end_entries_and_the_run_length_stand():
    """Bounds, sources and ``run_seconds`` are what later PRs add cells
    under and may not change."""
    assert [(m["name"], m["unit"], m["better"], m["bound"], m["source"])
            for m in BENCH["end_to_end"]] == [
        ("tokens_per_s_chip", "tokens/s", "higher", 0.01, "host_clock"),
        ("images_per_s_chip", "images/s", "higher", 0.01, "host_clock"),
        ("mfu", "%", "higher", 0.01, "host_clock"),
        ("step_ms_p95", "ms", "lower", 0.01, "host_clock"),
        ("setup_s", "s", "lower", 0.1, "host_clock")]
    assert BENCH["run_seconds"] == 20
    assert "workloads" not in RATES["mfu"] and "workloads" not in RATES[
        "setup_s"]
    # a rate is reported by the cells whose traffic is counted in it
    for w in BENCH["workloads"]:
        mix = _json(BENCH["paths"][0], "traffic", w["traffic"] + ".json")
        assert w["name"] in RATES[mix["rate_metric"]]["workloads"]
