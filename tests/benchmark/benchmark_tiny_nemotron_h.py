"""Nemotron-3-Nano's toy sibling laid over ``benchmark_tiny``'s root: one
more configuration, traffic file and cell, added the way a PR adds them
(new files and entries at the end of their lists; ``benchmark_tiny`` itself
is the benchmark's file and stays as it is)."""

import json
import os

import benchmark_tiny

PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: the pattern's shorter repeating unit (``MEMEM*E``): four state-space
#: heads of 8 in two groups over a state of 16, four of eight experts held
#: from the third on, two a token
NEMOTRON_H_TINY = {
    "source": "test preset", "hybrid_override_pattern": PUBLISHED,
    "num_hidden_layers": 7, "hidden_size": 64, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 16, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "router_num_experts": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
    "norm_eps": 1e-05, "vocab_size": 256, "initializer_range": 0.02,
    "moe_group_rows": 32, "moe_capacity_factor": 1.25,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 0.0001, "remat": "decoder_layer",
}
SEQ_TINY = {
    "rows_per_chip": 2, "dataset_rows_per_chip": 8,
    "arrays": [{"name": "ids", "shape": [64], "dtype": "int32", "low": 0,
                "high": "vocab_size"}],
    "items_per_row": 64, "rate_metric": "tokens_per_s_chip",
}
REAL_CELL = "nemotron3-8k"
TINY_CELL = "tiny-nemotron-h"


def make(tmp: str) -> str:
    root = benchmark_tiny.make(tmp)

    def write(rel, obj):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/nemotron_h_tiny.json", NEMOTRON_H_TINY)
    write("configs/nemotron_h_tiny.py",
          "from benchmarks.configs.nemotron3_nano_30b_a3b import *  "
          "# noqa: F401,F403\n")
    write("traffic/seq64-b2-n3.json", SEQ_TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "nemotron_h_tiny", "source": "test preset",
         "file": "benchmarks/configs/nemotron_h_tiny.json", "reduced": [],
         "why": "toy"})
    bench["workloads"].append(
        {"name": TINY_CELL, "config": "nemotron_h_tiny",
         "traffic": "seq64-b2-n3", "chips": 1, "why": "toy"})
    # the toy reports what the real cell reports, and the harness's counter
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) or m["name"] == "steps_done":
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
