"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program (``repro``). It
maps the configuration's published keys and deployment onto the program's
own config, hands the program the benchmark's weights in the program's
parameter layout, and builds the batcher and, where the configuration has
one, the adapter store.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.graph import band_graph
from repro.models import TransformerLM
from repro.serve import ContinuousBatcher, PagingSpec, TaskAdapterStore

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def arch(cfg: dict) -> ArchConfig:
    """The program's config for an OLMo-style dense model."""
    if cfg["model_type"] != "olmo":
        raise ValueError(f"no mapping for model_type {cfg['model_type']!r}")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ad = cfg.get("adapters")
    a = ArchConfig(
        name=cfg["model_type"],
        family="dense",
        num_layers=cfg["num_hidden_layers"],
        d_model=d,
        num_heads=h,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // h),
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        pattern=("attn",),
        mlp_kind="swiglu",
        norm_kind="nonparam_ln",
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        num_tasks=ad["num_tasks"] if ad else ArchConfig.num_tasks,
        adapter_rank=ad["rank"] if ad else 0,
        attn_backend=cfg["deployment"]["attn_backend"],
    )
    a.validate()
    return a


def num_tasks(cfg: dict) -> int:
    return arch(cfg).num_tasks


def params_from(model: TransformerLM, w: dict):
    """The benchmark's weights in the program's parameter tree; the tree's
    structure, shapes and dtypes must be what ``model.init`` makes."""
    params = {
        "embed": w["embed"],
        "stages": [{"slot0": {
            "norm1": {},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {},
            "mlp": {"wg": w["wg"], "wi": w["wi"], "wo": w["wd"]},
        }}],
        "final_norm": {},
        "task": {"head_bias": w["task_head_bias"]},
    }
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    same = jax.tree.structure(want) == jax.tree.structure(params) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params))
    )
    if not same:
        raise ValueError("the benchmark's weights do not match the program's "
                         "parameter layout")
    return params


def store_raw_from(raw: dict) -> dict:
    """The benchmark's adapter factors in the store's raw layout."""
    return {
        "stages": [{"slot0": {
            "attn": {"a": raw["attn_a"], "b": raw["attn_b"]},
            "mlp": {"a": raw["mlp_a"], "b": raw["mlp_b"]},
        }}],
        "task": {"head_bias": raw["head_bias"]},
    }


@dataclasses.dataclass
class System:
    model: TransformerLM
    batcher: ContinuousBatcher
    store: TaskAdapterStore | None
    paging: PagingSpec


def build(cfg: dict, w: dict, raw: dict | None, on_token) -> System:
    dep = cfg["deployment"]
    model = TransformerLM(arch(cfg), dtype=DTYPES[dep["dtype"]])
    paging = PagingSpec.sized(dep["block_size"], dep["max_seq"],
                              dep["pool_tokens"])
    store = None
    if raw is not None:
        ad = cfg["adapters"]
        if ad["graph"]["kind"] != "band":
            raise ValueError(f"unknown task graph {ad['graph']['kind']!r}")
        graph = band_graph(ad["num_tasks"], ad["graph"]["bandwidth"])
        store = TaskAdapterStore(
            model, graph, rank=ad["rank"], mixing=ad["mixing"],
            eta=ad["eta"], tau=ad["tau"], alpha=ad["alpha"],
            update_every=ad["update_every"], dtype=model.dtype,
        )
        store.set_raw(store_raw_from(raw))
    batcher = ContinuousBatcher(
        model, params_from(model, w), num_slots=dep["num_slots"],
        max_seq=dep["max_seq"], prefill_chunk=dep["prefill_chunk"],
        paging=paging, policy=dep["policy"], chunk_budget=dep["chunk_budget"],
        on_token=on_token, adapters=store,
    )
    return System(model, batcher, store, paging)
