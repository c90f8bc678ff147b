"""Shared building blocks: norms, RoPE, MLPs, initializers.

Pure-pytree style (no flax): ``init_*`` returns a params dict, ``apply``-style
functions are free functions. All matmuls accumulate in float32
(``preferred_element_type``) so bf16 runs stay stable on the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    scale = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def matmul(x: Array, w: Array) -> Array:
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def freeze_dead_slots(new_state, old_state, live):
    """Slot-masked recurrent-state update for batched serving: keep the
    state of dead slots (live=False) frozen. Unlike position-indexed KV
    caches, SSM/xLSTM states are cumulative, so a masked-out slot must not
    absorb the padding token a batched decode tick feeds it. live: (B,)
    bool or None (no masking); states are pytrees of (B, ...) leaves."""
    if live is None:
        return new_state
    return jax.tree.map(
        lambda n, o: jnp.where(
            live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o
        ),
        new_state, old_state,
    )


# ------------------------------------------------------- per-task adapters
def apply_task_lora(x: Array, ad: dict) -> Array:
    """Batched low-rank per-task delta: x @ a @ b with per-ROW factors.

    x: (B, C, d) block activations; ad["a"]: (B, d, r), ad["b"]: (B, r, d) —
    one factor pair per batch row, pre-gathered by task id (multi-LoRA).
    Accumulates in f32 like every other matmul here. Zero factors contribute
    an exact IEEE +0.0, so adding the result preserves token-for-token
    parity with the adapter-free path.
    """
    a = ad["a"].astype(jnp.float32)
    b = ad["b"].astype(jnp.float32)
    h = jnp.einsum(
        "bcd,bdr->bcr", x.astype(jnp.float32), a,
        preferred_element_type=jnp.float32,
    )
    out = jnp.einsum("bcr,bro->bco", h, b, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


# -------------------------------------------------------------------- norms
def rms_norm(x: Array, gain: Array | None, eps: float | None = None) -> Array:
    eps = 1e-6 if eps is None else eps
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    if gain is not None:
        out = out * (1.0 + gain.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x: Array, gain: Array | None, bias: Array | None,
               eps: float | None = None) -> Array:
    eps = 1e-5 if eps is None else eps
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    if gain is not None:
        out = out * gain.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def nonparam_layer_norm(x: Array, eps: float | None = None) -> Array:
    """OLMo's non-parametric LayerNorm: no gain, no bias [arXiv:2402.00838]."""
    return layer_norm(x, None, None, eps)


def apply_norm(kind: str, x: Array, params: dict | None,
               eps: float | None = None) -> Array:
    """eps: None gives each kind its own default."""
    if kind == "rmsnorm":
        return rms_norm(x, params["gain"] if params else None, eps)
    if kind == "layernorm":
        return layer_norm(
            x,
            params.get("gain") if params else None,
            params.get("bias") if params else None,
            eps,
        )
    if kind == "nonparam_ln":
        return nonparam_layer_norm(x, eps)
    raise ValueError(kind)


def init_norm(kind: str, d: int, dtype) -> dict:
    if kind == "rmsnorm":
        return {"gain": jnp.zeros((d,), dtype)}  # stored as (1 + gain)
    if kind == "layernorm":
        return {"gain": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if kind == "nonparam_ln":
        return {}
    raise ValueError(kind)


# --------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 1e4) -> Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- MLPs
def init_mlp(key, d: int, d_ff: int, kind: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "wg": dense_init(ks[0], (d, d_ff), dtype=dtype),
            "wi": dense_init(ks[1], (d, d_ff), dtype=dtype),
            "wo": dense_init(ks[2], (d_ff, d), dtype=dtype),
        }
    if kind == "gelu":
        return {
            "wi": dense_init(ks[0], (d, d_ff), dtype=dtype),
            "wo": dense_init(ks[1], (d_ff, d), dtype=dtype),
        }
    raise ValueError(kind)


def apply_mlp(params: dict, x: Array, kind: str) -> Array:
    if kind == "swiglu":
        gate = jax.nn.silu(matmul(x, params["wg"]))
        return matmul(gate * matmul(x, params["wi"]), params["wo"])
    if kind == "gelu":
        return matmul(jax.nn.gelu(matmul(x, params["wi"])), params["wo"])
    raise ValueError(kind)
