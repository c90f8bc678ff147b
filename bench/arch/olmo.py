"""OLMo (arXiv:2402.00838) in the benchmark: model_type ``olmo``.

What ``bench.lib`` asks of every architecture (``spec.architecture``): the
program's config for the model, its weights drawn from a key, their place in
the program's parameter tree, the adapter store's raw factors and their
layout, and the shape the cost counts read. The weights are the dict the
plain reference (``bench/reference/olmo.py``) reads as it is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.lib import costs
from bench.lib.weights import normal
from repro.configs.base import ArchConfig


def _dims(cfg: dict) -> tuple:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["vocab_size"], d, cfg["num_hidden_layers"], h,
            cfg["num_key_value_heads"], cfg.get("head_dim", d // h),
            cfg["intermediate_size"])


def arch(cfg: dict) -> ArchConfig:
    """The program's config: a dense transformer of attention and SwiGLU
    blocks under non-parametric LayerNorm, rotary positions, a tied head."""
    v, d, layers, h, kvh, hd, ff = _dims(cfg)
    ad = cfg.get("adapters")
    a = ArchConfig(
        name=cfg["model_type"],
        family="dense",
        num_layers=layers,
        d_model=d,
        num_heads=h,
        num_kv_heads=kvh,
        head_dim=hd,
        d_ff=ff,
        vocab_size=v,
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


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _model(key, *, dims, dtype):
    v, d, layers, h, kvh, hd, ff, tasks = dims
    shapes = {
        "wq": ((layers, d, h * hd), d),
        "wk": ((layers, d, kvh * hd), d),
        "wv": ((layers, d, kvh * hd), d),
        "wo": ((layers, h * hd, d), h * hd),
        "wg": ((layers, d, ff), d),
        "wi": ((layers, d, ff), d),
        "wd": ((layers, ff, d), ff),
    }
    keys = jax.random.split(key, len(shapes) + 1)
    w = {"embed": normal(keys[0], (v, d), d ** -0.5, dtype)}
    for k, (name, (shape, fan_in)) in zip(keys[1:], shapes.items()):
        w[name] = normal(k, shape, fan_in ** -0.5, dtype)
    w["task_head_bias"] = jnp.zeros((tasks, v), dtype)
    return w


def weights(cfg: dict, key, tasks: int, dtype) -> dict:
    """{"embed": (V, d), "wq" .. "wd": (L, ...), "task_head_bias": (tasks,
    V)}: normal with std 1/sqrt(fan_in), the per-task head biases zero."""
    return _model(key, dims=_dims(cfg) + (tasks,), dtype=dtype)


def params(w: dict) -> dict:
    """The weights in the program's parameter tree."""
    return {
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


@functools.partial(jax.jit, static_argnames=("dims", "stds", "dtype"))
def _adapters(key, *, dims, stds, dtype):
    m, layers, d, r, v = dims
    sa, sb, sh = stds
    k = jax.random.split(key, 5)
    return {
        "attn_a": normal(k[0], (m, layers, d, r), sa, dtype),
        "attn_b": normal(k[1], (m, layers, r, d), sb, dtype),
        "mlp_a": normal(k[2], (m, layers, d, r), sa, dtype),
        "mlp_b": normal(k[3], (m, layers, r, d), sb, dtype),
        "head_bias": normal(k[4], (m, v), sh, dtype),
    }


def adapter_raw(cfg: dict, key, dtype) -> dict:
    """Every task's raw factors before any mixing: a rank-r pair per block
    for the attention branch and one for the MLP, and a head bias."""
    ad = cfg["adapters"]
    dims = (ad["num_tasks"], cfg["num_hidden_layers"], cfg["hidden_size"],
            ad["rank"], cfg["vocab_size"])
    std = ad["init_std"]
    return _adapters(key, dims=dims, stds=(std["a"], std["b"],
                                           std["head_bias"]), dtype=dtype)


def store_raw(raw: dict) -> dict:
    """The raw factors in the adapter store's layout."""
    return {
        "stages": [{"slot0": {
            "attn": {"a": raw["attn_a"], "b": raw["attn_b"]},
            "mlp": {"a": raw["mlp_a"], "b": raw["mlp_b"]},
        }}],
        "task": {"head_bias": raw["head_bias"]},
    }


def shape(cfg: dict) -> costs.Shape:
    """Every layer is attention and a SwiGLU MLP."""
    v, d, layers, h, kvh, hd, ff = _dims(cfg)
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    return costs.Shape(attn_layers=layers, heads=h, kv_heads=kvh,
                       head_dim=hd, body_params=layers * (attn + 3 * d * ff),
                       d=d, vocab=v)
