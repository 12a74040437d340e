"""Mellum-2's toy sibling laid over ``benchmark_tiny``'s root: one more
configuration, traffic file and cell, added the way a PR adds them (new
files and entries at the end of their lists; ``benchmark_tiny`` itself is
the benchmark's file and stays as it is)."""

import json
import os

import benchmark_tiny

SLIDING, FULL = "sliding_attention", "full_attention"
#: one period of four layers (the first four of ``layer_types``) under a
#: window of 16, four of eight experts held from the third on, two a token;
#: the full layers' YaRN over an original context of 32
MELLUM2_TINY = {
    "source": "test preset", "num_hidden_layers": 4,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window": 16,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
               "original_max_position_embeddings": 32, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.1386294361119891},
        SLIDING: {"rope_type": "default", "rope_theta": 10000}},
    "moe_intermediate_size": 32, "num_experts": 4, "router_num_experts": 8,
    "first_expert": 2, "num_experts_per_tok": 2, "rms_norm_eps": 1e-06,
    "vocab_size": 256, "initializer_range": 0.02,
    "q_proj_initializer_range": 0.06, "moe_group_rows": 32,
    "moe_capacity_factor": 1.0, "compute_dtype": "float32",
    "param_dtype": "float32", "optimizer": "adam", "learning_rate": 0.0001,
    "remat": "decoder_layer",
}
SEQ_TINY = {
    "rows_per_chip": 2, "dataset_rows_per_chip": 8,
    "arrays": [{"name": "ids", "shape": [64], "dtype": "int32", "low": 0,
                "high": "vocab_size"}],
    "items_per_row": 64, "rate_metric": "tokens_per_s_chip",
}
REAL_CELL = "mellum2-16k"
TINY_CELL = "tiny-mellum2"


def make(tmp: str) -> str:
    root = benchmark_tiny.make(tmp)

    def write(rel, obj):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/mellum2_tiny.json", MELLUM2_TINY)
    write("configs/mellum2_tiny.py",
          "from benchmarks.configs.mellum2_12b_a2p5b import *  "
          "# noqa: F401,F403\n")
    write("traffic/seq64-b2-m2.json", SEQ_TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "mellum2_tiny", "source": "test preset",
         "file": "benchmarks/configs/mellum2_tiny.json", "reduced": [],
         "why": "toy"})
    bench["workloads"].append(
        {"name": TINY_CELL, "config": "mellum2_tiny",
         "traffic": "seq64-b2-m2", "chips": 1, "why": "toy"})
    # the toy reports what the real cell reports, and the harness's counter
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) or m["name"] == "steps_done":
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
