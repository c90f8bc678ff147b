"""A tiny OLMo-shaped configuration and mixes, for running the harness on
the CPU in tests."""
from __future__ import annotations

import copy
import json

from bench.lib import spec

CELL = "tiny.open"


def config(adapters: bool = True) -> dict:
    cfg = json.loads((spec.BENCH / "configs" / "olmo_1b-mt256.json")
                     .read_text())
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, max_position_embeddings=128)
    cfg["deployment"].update(attn_backend="jnp", dtype="bfloat16",
                             num_slots=4, max_seq=128, pool_tokens=384,
                             block_size=16, prefill_chunk=16,
                             chunk_budget=32)
    if adapters:
        cfg["adapters"].update(num_tasks=8, rank=2, update_every=2)
        cfg["adapters"]["init_std"] = {"a": 64 ** -0.5, "b": 2 ** -0.5,
                                       "head_bias": 1.0}
    else:
        cfg["adapters"] = None
    return cfg


OPEN = {"loop": "open", "rate": 12.0,
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "lo": 4, "hi": 80},
        "output": {"dist": "uniform", "lo": 3, "hi": 12},
        "tasks": {"dist": "zipf", "s": 1.1, "n": 8}}
CLOSED = {"loop": "closed", "clients": 4,
          "prompt": {"dist": "uniform", "lo": 20, "hi": 60},
          "output": {"dist": "uniform", "lo": 4, "hi": 12}, "prime": True}


def bench(cell: str = CELL) -> dict:
    """The repository's BENCHMARK.json with its metrics applying to a tiny
    cell too."""
    b = copy.deepcopy(spec.load())
    b["workloads"].append({"name": cell, "config": "tiny", "traffic": "tiny",
                           "chips": 1, "why": "tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b
