"""Qwen3-Next's toy sibling laid over ``benchmark_tiny``'s root: one more
configuration, traffic file and cell, added the way a PR adds them (new
files and entries at the end of their lists; ``benchmark_tiny`` itself is
the benchmark's file and stays as it is)."""

import json
import os

import benchmark_tiny

#: two periods of (DeltaNet, full attention), four of eight experts held
#: from the third on, two a token
QWEN_TINY = {
    "source": "test preset", "num_hidden_layers": 4,
    "full_attention_interval": 2, "hidden_size": 64, "head_dim": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rope_theta": 10000,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 16, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 4,
    "router_num_experts": 8, "first_expert": 2, "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-06, "vocab_size": 256, "initializer_range": 0.02,
    "compute_dtype": "float32", "param_dtype": "float32",
    "optimizer": "adam", "learning_rate": 0.0001, "remat": "decoder_layer",
}
REAL_CELL = "qwen3next-8k"
TINY_CELL = "tiny-qwen"


def make(tmp: str) -> str:
    root = benchmark_tiny.make(tmp)

    def write(rel, obj):
        with open(os.path.join(root, "benchmarks", rel), "w") as fh:
            fh.write(obj if isinstance(obj, str) else json.dumps(obj))

    write("configs/qwen3_next_tiny.json", QWEN_TINY)
    write("configs/qwen3_next_tiny.py",
          "from benchmarks.configs.qwen3_next_80b_a3b import *  "
          "# noqa: F401,F403\n")
    write("traffic/seq128-b2q.json", benchmark_tiny.SEQ_TINY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "qwen3_next_tiny", "source": "test preset",
         "file": "benchmarks/configs/qwen3_next_tiny.json", "reduced": [],
         "why": "toy"})
    bench["workloads"].append(
        {"name": TINY_CELL, "config": "qwen3_next_tiny",
         "traffic": "seq128-b2q", "chips": 1, "why": "toy"})
    # the toy reports what the real cell reports, and the harness's counter
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) or m["name"] == "steps_done":
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as fh:
        json.dump(bench, fh, indent=1)
    return root
