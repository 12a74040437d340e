"""SDAR's toy sibling laid over ``benchmark_tiny``'s root: one more
configuration, traffic file (three arrays a row) and cell, added the way a
PR adds them (new files and entries at the end of their lists;
``benchmark_tiny`` itself is the benchmark's file and stays as it is)."""

import json
import os

import benchmark_tiny

#: two layers, four of eight experts held from the third on, two a token,
#: blocks of four tokens; the last id of the vocabulary is [MASK]
SDAR_TINY = {
    "source": "test preset", "num_hidden_layers": 2, "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rope_theta": 10000, "moe_intermediate_size": 32, "num_experts": 4,
    "router_num_experts": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-06, "vocab_size": 256, "data_vocab_size": 255,
    "mask_token_id": 255, "block_length": 4, "initializer_range": 0.02,
    "qk_norm_init": 2.0, "moe_group_rows": 64, "moe_capacity_factor": 1.0,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 0.0001, "remat": "decoder_layer",
}
BD_TINY = {
    "rows_per_chip": 2, "dataset_rows_per_chip": 8,
    "arrays": [
        {"name": "ids", "shape": [64], "dtype": "int32", "low": 0,
         "high": "data_vocab_size"},
        {"name": "level", "shape": [16], "dtype": "int32", "low": 4096,
         "high": 65537},
        {"name": "draw", "shape": [64], "dtype": "int32", "low": 0,
         "high": 65536}],
    "items_per_row": 64, "rate_metric": "tokens_per_s_chip",
}
REAL_CELL = "sdar-bd4-8k"
TINY_CELL = "tiny-sdar"


def make(tmp: str) -> str:
    root = benchmark_tiny.make(tmp)

    def write(rel, obj):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/sdar_tiny.json", SDAR_TINY)
    write("configs/sdar_tiny.py",
          "from benchmarks.configs.sdar_30b_a3b_chat import *  "
          "# noqa: F401,F403\n")
    write("traffic/seq64-b2-bd4.json", BD_TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "sdar_tiny", "source": "test preset",
         "file": "benchmarks/configs/sdar_tiny.json", "reduced": [],
         "why": "toy"})
    bench["workloads"].append(
        {"name": TINY_CELL, "config": "sdar_tiny",
         "traffic": "seq64-b2-bd4", "chips": 1, "why": "toy"})
    # the toy reports what the real cell reports, and the harness's counter
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) or m["name"] == "steps_done":
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
