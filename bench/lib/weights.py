"""Weights and adapter stores made from the seed, on the device, in one call.

The benchmark owns these arrays: the program is handed them (mapped into its
own parameter layout by ``bench.lib.program``), and the plain reference reads
them as they are. Both see the same bf16 values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


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
    w = {"embed": _normal(keys[0], (v, d), d ** -0.5, dtype)}
    for k, (name, (shape, fan_in)) in zip(keys[1:], shapes.items()):
        w[name] = _normal(k, shape, fan_in ** -0.5, dtype)
    w["task_head_bias"] = jnp.zeros((tasks, v), dtype)
    return w


def model_weights(cfg: dict, seed: int, tasks: int, dtype=jnp.bfloat16):
    """{"embed": (V, d), "wq" .. "wd": (L, ...), "task_head_bias": (tasks,
    V)}: normal with std 1/sqrt(fan_in), the per-task head biases zero."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dims = (cfg["vocab_size"], d, cfg["num_hidden_layers"], h,
            cfg["num_key_value_heads"], cfg.get("head_dim", d // h),
            cfg["intermediate_size"], tasks)
    key = jax.random.fold_in(seed_key(seed), 0)
    return _model(key, dims=dims, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("dims", "stds", "dtype"))
def _adapters(key, *, dims, stds, dtype):
    m, layers, d, r, v = dims
    sa, sb, sh = stds
    k = jax.random.split(key, 5)
    return {
        "attn_a": _normal(k[0], (m, layers, d, r), sa, dtype),
        "attn_b": _normal(k[1], (m, layers, r, d), sb, dtype),
        "mlp_a": _normal(k[2], (m, layers, d, r), sa, dtype),
        "mlp_b": _normal(k[3], (m, layers, r, d), sb, dtype),
        "head_bias": _normal(k[4], (m, v), sh, dtype),
    }


def adapter_raw(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The store's raw per-task factors before any mixing."""
    ad = cfg["adapters"]
    dims = (ad["num_tasks"], cfg["num_hidden_layers"], cfg["hidden_size"],
            ad["rank"], cfg["vocab_size"])
    std = ad["init_std"]
    key = jax.random.fold_in(seed_key(seed), 1)
    return _adapters(key, dims=dims, stds=(std["a"], std["b"],
                                           std["head_bias"]), dtype=dtype)
