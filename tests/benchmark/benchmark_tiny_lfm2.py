"""LFM2's toy sibling laid over ``benchmark_tiny``'s root: one more
configuration, traffic file and cell, added the way a PR adds them (new
files and entries at the end of their lists; ``benchmark_tiny`` itself is
the benchmark's file and stays as it is)."""

import json
import os

import benchmark_tiny

PUBLISHED = ["conv", "conv", "full_attention", "conv"] * 10
#: the published layers 1 to 5 (a dense ``conv`` layer, then
#: ``full_attention, conv, conv, conv`` with experts): four heads of 16 over
#: two k/v heads, four of eight experts held from the third on, two a token
LFM2_TINY = {
    "source": "test preset", "layer_types": PUBLISHED, "first_layer": 1,
    "num_hidden_layers": 5, "num_dense_layers": 1, "hidden_size": 64,
    "intermediate_size": 96, "conv_L_cache": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "moe_intermediate_size": 32, "num_experts": 4, "router_num_experts": 8,
    "first_expert": 2, "num_experts_per_tok": 2, "routed_scaling_factor": 1,
    "norm_eps": 1e-05, "vocab_size": 256, "initializer_range": 0.02,
    "qk_norm_init": 2.0, "moe_group_rows": 32, "moe_capacity_factor": 1.0,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 0.0001, "remat": "decoder_layer",
}
SEQ_TINY = {
    "rows_per_chip": 2, "dataset_rows_per_chip": 8,
    "arrays": [{"name": "ids", "shape": [64], "dtype": "int32", "low": 0,
                "high": "vocab_size"}],
    "items_per_row": 64, "rate_metric": "tokens_per_s_chip",
}
REAL_CELL = "lfm2-8k-b2"
TINY_CELL = "tiny-lfm2"


def make(tmp: str) -> str:
    root = benchmark_tiny.make(tmp)

    def write(rel, obj):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/lfm2_tiny.json", LFM2_TINY)
    write("configs/lfm2_tiny.py",
          "from benchmarks.configs.lfm2_24b_a2b import *  "
          "# noqa: F401,F403\n")
    write("traffic/seq64-b2-l2.json", SEQ_TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "lfm2_tiny", "source": "test preset",
         "file": "benchmarks/configs/lfm2_tiny.json", "reduced": [],
         "why": "toy"})
    bench["workloads"].append(
        {"name": TINY_CELL, "config": "lfm2_tiny", "traffic": "seq64-b2-l2",
         "chips": 1, "why": "toy"})
    # the toy reports what the real cell reports, and the harness's counter
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) or m["name"] == "steps_done":
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
