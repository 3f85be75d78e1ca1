"""A tiny cell for the CPU tests: the real harness and reference at sizes
a test run holds, with the jnp paths instead of the Pallas kernels."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

CONFIG = {
    "name": "tiny-dense", "source": "test", "arch": "dense",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "num_hidden_layers": 2, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
    "attention_multiplier": 0.25, "logits_scaling": 1.0,
    "hidden_act": "silu", "dtype": "bfloat16", "value_head": True,
    "init_std": 0.02,
    "program": {"use_pallas": False, "fused_loss": True, "remat": False},
}

MIX = {"name": "tiny-math", "kind": "arithmetic", "max_operand": 100,
       "ops": "+-", "max_len": 32, "temperature": 1.0}

WORKLOAD = {
    "name": "tiny-rl", "config": "tiny-dense", "traffic": "tiny-math",
    "chips": 1,
    "engine": {"n_slots": 4, "prefill_chunk": 16},
    "pipeline": {"batch_size": 2, "pack_rows": 2, "pack_seq": 32,
                 "n_engines": 1, "broadcast": "streamed",
                 "broadcast_chunks": 8, "n_chips": 8, "train_chips": 4},
    "optimizer": {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
                  "grad_clip": 1.0},
    "rl": {"is_clamp": 5.0, "value_coef": 0.5},
    "warmup_steps": 4,
    "check": {"engine_rollouts": 3},
    # set from CPU readings at this size over five seeds: the program read
    # at most 0.0022 / 0.00094 / 0.00071, the float8 control at least
    # 0.014 / 0.0060 / 0.0023
    "limits": {"engine_lp_gap": 0.006, "grad_gap": 0.003,
               "update_gap": 0.0015, "install_mismatch": 0.0},
}


def cell(**limits) -> "harness.Cell":
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    wl = json.loads(json.dumps(WORKLOAD))
    wl["limits"].update(limits)
    per = [dict(m, workloads=["tiny-rl"]) for m in bench["per_layer"]]
    e2e = [m for m in bench["end_to_end"]]
    return harness.Cell("tiny-rl", 1, dict(CONFIG), dict(MIX), wl, e2e, per)
