"""A tiny copy of the benchmark for the CPU tests: the harness and its
files copied into a temporary root, with two-layer granitemoe and
minicpm3 configurations and a short traffic mix added as new files and
entries."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TINY_CONF = {
    "name": "tiny-granitemoe", "source": "a two-layer granitemoe for tests",
    "model_type": "granitemoe", "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_local_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 256,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "embedding_multiplier": 12.0, "attention_multiplier": 0.015625,
    "residual_multiplier": 0.22, "logits_scaling": 6.0,
    "precision": {"weights": "float32", "matmul": "highest"},
    "deployment": {"prefill_instances": 2, "prefill_dp": 1, "decode_dp": 2,
                   "chunk": 64, "block_size": 16, "max_len": 256,
                   "max_batch_per_dp": 4, "decode_slots_per_dp": 8,
                   "t_default_s": 0.02,
                   "l_net_s": 0.001},
    "check": {"sample_requests": 4, "min_tokens_compared": 20,
              "logit_err": 1e-4, "control_matmul": "high"},
}

TINY_MLA_CONF = {
    "name": "tiny-minicpm3", "source": "a two-layer minicpm3 for tests",
    "model_type": "minicpm3", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "vocab_size": 256, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "scale_emb": 12.0,
    "scale_depth": 1.4, "dim_model_base": 16, "rope_scaling": None,
    "precision": TINY_CONF["precision"],
    "deployment": TINY_CONF["deployment"],
    "check": TINY_CONF["check"],
}

TINY_MIX = {
    "arrivals": {"process": "poisson", "rate_rps": 16.0},
    "prompt_len": {"mean": 48, "sigma": 0.8, "min": 16, "max": 128,
                   "quantum": 16},
    "output_len": {"mean": 12, "sigma": 0.5, "min": 4, "max": 24},
    "scheduler": "sbs-la", "lead_in_s": 0.5, "tail_s": 0.2,
    "drain_limit_s": 30.0, "shape_seed": 0,
}


# the tiny cells, by name: one per architecture the benchmark runs
CELLS = {"tiny.poisson": TINY_CONF, "tiny-mla.poisson": TINY_MLA_CONF}


def make_root(tmp: str, extra_per_layer=()) -> str:
    """A checkout-like root under `tmp` holding BENCHMARK.json (with the
    tiny cell added) and a copy of chipbench/; returns its path."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "chipbench/traffic/tiny.json"), "w") as f:
        json.dump(TINY_MIX, f)
    for cell, conf in CELLS.items():
        conf_file = f"chipbench/configs/{conf['name']}.json"
        with open(os.path.join(root, conf_file), "w") as f:
            json.dump(conf, f)
        manifest["configs"].append({"name": conf["name"],
                                    "source": conf["source"],
                                    "file": conf_file, "reduced": [],
                                    "why": "tests"})
        manifest["workloads"].append({"name": cell, "config": conf["name"],
                                      "traffic": "tiny", "chips": 1,
                                      "why": "tests"})
        for m in manifest["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(cell)
    manifest["per_layer"].extend(extra_per_layer)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
