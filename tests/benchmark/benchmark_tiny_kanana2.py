"""Kanana-2's toy sibling laid over ``benchmark_tiny``'s root: one more
configuration, traffic file and cell, added the way a PR adds them (new
files and entries at the end of their lists; ``benchmark_tiny`` itself is
the benchmark's file and stays as it is)."""

import json
import os

import benchmark_tiny

#: a dense layer and two expert layers, four of eight experts held from the
#: third on, two a token, q.k 24 (16 + 8 rotary) and v 16
KANANA2_TINY = {
    "source": "test preset", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "rope_theta": 10000,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "router_num_experts": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "n_shared_experts": 2, "routed_scaling_factor": 2.448,
    "rms_norm_eps": 1e-06, "vocab_size": 256, "initializer_range": 0.02,
    "q_proj_initializer_range": 0.146, "moe_group_rows": 64,
    "moe_capacity_factor": 1.25, "compute_dtype": "float32",
    "param_dtype": "float32", "optimizer": "adam", "learning_rate": 0.0001,
    "remat": "decoder_layer",
}
SEQ_TINY = {
    "rows_per_chip": 2, "dataset_rows_per_chip": 8,
    "arrays": [{"name": "ids", "shape": [64], "dtype": "int32", "low": 0,
                "high": "vocab_size"}],
    "items_per_row": 64, "rate_metric": "tokens_per_s_chip",
}
REAL_CELL = "kanana2-8k"
TINY_CELL = "tiny-kanana2"


def make(tmp: str) -> str:
    root = benchmark_tiny.make(tmp)

    def write(rel, obj):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/kanana2_tiny.json", KANANA2_TINY)
    write("configs/kanana2_tiny.py",
          "from benchmarks.configs.kanana2_30b_a3b import *  "
          "# noqa: F401,F403\n")
    write("traffic/seq64-b2-k2.json", SEQ_TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "kanana2_tiny", "source": "test preset",
         "file": "benchmarks/configs/kanana2_tiny.json", "reduced": [],
         "why": "toy"})
    bench["workloads"].append(
        {"name": TINY_CELL, "config": "kanana2_tiny",
         "traffic": "seq64-b2-k2", "chips": 1, "why": "toy"})
    # the toy reports what the real cell reports, and the harness's counter
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) or m["name"] == "steps_done":
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
