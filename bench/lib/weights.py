"""Weights and adapter stores made from the seed, on the device, in one call.

The benchmark owns these arrays: the program is handed them (mapped into its
own parameter layout by the architecture's module, ``bench/arch/``), and the
plain reference reads them as they are. Both see the same bf16 values. The
seed gives one key for the weights and one for the adapters; what is drawn
from each, and in which order, is the architecture's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import spec


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def model_weights(cfg: dict, seed: int, tasks: int, dtype=jnp.bfloat16):
    """The architecture's weights, with the per-task head biases
    ``task_head_bias`` (tasks, V), as the reference reads them."""
    key = jax.random.fold_in(seed_key(seed), 0)
    return spec.architecture(cfg["model_type"]).weights(cfg, key, tasks,
                                                        dtype)


def adapter_raw(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The store's raw per-task factors before any mixing."""
    key = jax.random.fold_in(seed_key(seed), 1)
    return spec.architecture(cfg["model_type"]).adapter_raw(cfg, key, dtype)
