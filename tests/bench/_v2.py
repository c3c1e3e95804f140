"""A CPU-sized configuration of the DeepSeek-V2 block
(``bench/arch/deepseek_v2.py``): the chip configuration's keys at width
64, four layers (a dense first one), 8 routed experts of which 4 are
held, top 3, one shared expert, served in float32."""
from __future__ import annotations

import json

import _tiny
from bench import spec

CHIP = json.loads((spec.BENCH / "configs" / "deepseek-v2-lite.json")
                  .read_text())
CONFIG = {**CHIP, "name": "tiny-v2", "hidden_size": 64,
          "intermediate_size": 96, "kv_lora_rank": 16,
          "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
          "num_attention_heads": 4, "num_key_value_heads": 4,
          "moe_intermediate_size": 32, "n_routed_experts": 4,
          "held_experts": [2, 6], "published": {"n_routed_experts": 8},
          "num_experts_per_tok": 3, "n_shared_experts": 1,
          "num_hidden_layers": 4, "vocab_size": 256,
          "initializer_range": 0.2, "torch_dtype": "float32",
          "rope_scaling": {**CHIP["rope_scaling"], "factor": 4,
                           "original_max_position_embeddings": 16},
          "assumed": {**CHIP["assumed"], "exit_layers": [1, 2]}}
CELL = {**_tiny.CELL, "config": "tiny-v2"}


def cell() -> dict:
    return {**_tiny.cell(), "name": "tiny-v2", "config": dict(CONFIG),
            "cell": dict(CELL)}

