"""A cell's spec at a size the CPU runs in seconds: the published
structure of each configuration at the program's smoke widths, with the
cell's own traffic file cut to 2 rows of 64 tokens a chip."""

import json

from chipbench import BENCH, ROOT

CONFIGS = {
    "qwen2-1.5b": {
        "registry_id": "qwen2-1.5b-smoke", "reference": "decoder",
        "hidden_size": 128, "initializer_range": 0.02, "intermediate_size": 256,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "qkv_bias": True, "rms_norm_eps": 1e-6,
        "rope_theta": 1e6, "tie_word_embeddings": True, "vocab_size": 512},
    "granite-moe-1b-a400m": {
        "registry_id": "granite-moe-1b-a400m-smoke", "reference": "decoder",
        "hidden_size": 128, "initializer_range": 0.02, "intermediate_size": 64,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "qkv_bias": False, "rms_norm_eps": 1e-6,
        "rope_theta": 1e4, "tie_word_embeddings": True, "vocab_size": 512,
        "num_local_experts": 4, "num_experts_per_tok": 2, "capacity_factor": 1.25,
        "router_aux_loss_coef": 0.01},
}


def spec(workload: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    traffic.update(seq_len=64, batch_per_chip=2, warmup_steps=1)
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    return {"bench": bench, "cell": cell, "config_file": CONFIGS[cell["config"]],
            "traffic_file": traffic, "limits": limits}
