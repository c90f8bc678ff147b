"""Mamba2 (SSD — state-space duality) layer, chunked for TPU.

Training/prefill uses the chunked SSD algorithm: quadratic attention-like
work *within* chunks (MXU-friendly (c x c) matmuls) plus a `lax.scan` over
chunk states — O(S c) instead of O(S^2). Decode is the O(1) recurrence.

Per-layer state: conv buffer (B, kernel-1, conv_dim) and SSM state
(B, n_heads, head_dim, d_state).

Simplifications vs the reference CUDA implementation (documented in
DESIGN.md): single B/C group (n_groups=1), no projection biases. The
depthwise conv takes an optional bias (``conv_bias``, as Granite 4.0-H has),
and the output is gated before its RMSNorm (``norm(y * silu(z))``, the
reference's gated RMSNorm with ``norm_before_gate=False``), whose epsilon
the caller passes (``eps``; None: ``rms_norm``'s default).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, freeze_dead_slots, matmul, rms_norm

Array = jax.Array

CONV_K = 4  # depthwise causal conv kernel width


def dims(d_model: int, d_state: int, head_dim: int = 64, expand: int = 2):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * d_state  # conv over [x, B, C]
    return d_inner, n_heads, conv_dim


def init_mamba2(key, d_model: int, d_state: int, head_dim: int, dtype,
                conv_bias: bool = False):
    d_inner, n_heads, conv_dim = dims(d_model, d_state, head_dim)
    ks = jax.random.split(key, 6)
    p = {
        # in_proj -> [z (d_inner), x (d_inner), B (ds), C (ds), dt (nh)]
        "w_in": dense_init(ks[0], (d_model, 2 * d_inner + 2 * d_state + n_heads), dtype=dtype),
        "conv_w": dense_init(ks[1], (CONV_K, conv_dim), dtype=dtype),
        "a_log": jnp.zeros((n_heads,), jnp.float32),  # A = -exp(a_log) = -1
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "norm_gain": jnp.zeros((d_inner,), dtype),
        "w_out": dense_init(ks[2], (d_inner, d_model), dtype=dtype),
    }
    if conv_bias:
        # torch's Conv1d default: uniform in +-1/sqrt(fan_in), fan_in = K
        bound = CONV_K ** -0.5
        p["conv_b"] = jax.random.uniform(
            ks[3], (conv_dim,), jnp.float32, -bound, bound
        ).astype(dtype)
    return p


def _split_proj(params, x, d_model, d_state, head_dim):
    d_inner, n_heads, conv_dim = dims(d_model, d_state, head_dim)
    proj = matmul(x, params["w_in"])
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner : d_inner + conv_dim]
    dt = proj[..., d_inner + conv_dim :]  # (.., nh)
    return z, xbc, dt, d_inner, n_heads


def _causal_conv(xbc: Array, conv_w: Array, prefix: Array | None = None,
                 bias: Array | None = None) -> Array:
    """Depthwise causal conv over time. xbc: (B, S, C); prefix: optional
    (B, K-1, C) window carried in from earlier tokens (zeros when absent —
    the sequence starts here); bias: optional (C,)."""
    if prefix is None:
        pad = jnp.pad(xbc, ((0, 0), (CONV_K - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([prefix, xbc], axis=1)
    out = sum(
        pad[:, k : k + xbc.shape[1], :] * conv_w[k][None, None, :]
        for k in range(CONV_K)
    )
    if bias is not None:
        out = out + bias
    return jax.nn.silu(out)


def mamba2_full(
    params, x, *, d_state: int, head_dim: int, chunk: int = 256,
    state=None, valid: Array | None = None, eps: float | None = None,
):
    """Full-sequence chunked SSD. x: (B, S, d_model) -> (y, final_state).

    final_state: (conv_tail (B, K-1, conv_dim), ssm (B, nh, hd, ds)).

    state: optional incoming (conv_tail, ssm) — the serving prefill threads a
    slot's recurrent cache in so a chunk continues mid-sequence (None keeps
    the training behaviour: zero conv window, zero SSM state). valid:
    optional (B, S) bool marking real tokens; each row must be a contiguous
    PREFIX (serving chunks are left-packed). Invalid tokens are exact
    no-ops on the state — their dt is forced to 0, so they neither decay nor
    feed the recurrence — and the returned conv_tail is the window ending at
    each row's LAST VALID token, which is what decode resumes from.
    """
    bsz, s, d_model = x.shape
    z, xbc, dt, d_inner, nh = _split_proj(params, x, d_model, d_state, head_dim)
    conv_prefix = (
        jnp.zeros((bsz, CONV_K - 1, xbc.shape[-1]), xbc.dtype)
        if state is None
        else state[0].astype(xbc.dtype)
    )
    # raw (pre-conv) window, indexed by tokens consumed: after n valid
    # tokens the carry-out tail is window[n : n + K-1]
    window = jnp.concatenate([conv_prefix, xbc], axis=1)  # (B, K-1+S, conv)
    if valid is None:
        conv_tail = window[:, s : s + CONV_K - 1, :]
    else:
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)  # (B,)
        # n_valid <= S and window spans K-1+S rows, so idx <= S+K-2 is in
        # bounds by construction
        idx = n_valid[:, None] + jnp.arange(CONV_K - 1)[None, :]
        conv_tail = jnp.take_along_axis(
            window, idx[:, :, None], axis=1, mode="promise_in_bounds"
        )
    xbc = _causal_conv(xbc, params["conv_w"], prefix=conv_prefix,
                       bias=params.get("conv_b"))
    xs = xbc[..., :d_inner].reshape(bsz, s, nh, head_dim)
    b_in = xbc[..., d_inner : d_inner + d_state]  # (B, S, ds)
    c_in = xbc[..., d_inner + d_state :]  # (B, S, ds)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,nh)
    if valid is not None:
        # dt == 0 makes a token a no-op on the SSD recurrence: zero decay
        # (da == 0) and zero input contribution (dt scales B x)
        dt = jnp.where(valid[:, :, None], dt, 0.0)
    a = -jnp.exp(params["a_log"])  # (nh,)
    da = dt * a[None, None, :]  # log-decay per step, (B, S, nh)

    if s % chunk != 0:
        chunk = s
    nc = s // chunk
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def r(t):  # reshape to (nc, B, c, ...) for the chunk scan
        return t.reshape((bsz, nc, chunk) + t.shape[2:]).swapaxes(0, 1)

    xs_c, b_c, c_c, dt_c, da_c = map(
        lambda t: r(t.astype(jnp.float32)), (xs, b_in, c_in, dt, da)
    )

    def process_chunk(s_prev, inp):
        """One chunk: quadratic intra-chunk term + contribution of the
        incoming state; emits the chunk's outputs and the updated state."""
        xs_i, b_i, c_i, dt_i, da_i = inp
        cum = jnp.cumsum(da_i, axis=1)  # (B, c, nh)
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # (B, t, s', nh)
        l_mat = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
        cb = jnp.einsum("btn,bsn->bts", c_i, b_i)  # single B/C group
        w_mat = cb[..., None] * l_mat * dt_i[:, None, :, :]  # (B, t, s', nh)
        y_intra = jnp.einsum("btsh,bshd->bthd", w_mat, xs_i)
        y_inter = jnp.einsum("btn,bth,bhdn->bthd", c_i, jnp.exp(cum), s_prev)
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)  # (B, c, nh)
        st = jnp.einsum(
            "bsh,bsn,bshd->bhdn", decay_to_end * dt_i, b_i, xs_i
        )
        s_new = s_prev * jnp.exp(cum[:, -1, :])[:, :, None, None] + st
        return s_new, y_intra + y_inter

    s0 = (
        jnp.zeros((bsz, nh, head_dim, d_state), jnp.float32)
        if state is None
        else state[1].astype(jnp.float32)
    )
    s_final, y_chunks = jax.lax.scan(
        process_chunk, s0, (xs_c, b_c, c_c, dt_c, da_c)
    )
    y = y_chunks.swapaxes(0, 1).reshape(bsz, s, nh, head_dim)
    y = y + params["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, s, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm_gain"], eps)
    return matmul(y, params["w_out"]), (conv_tail, s_final)


def mamba2_step(params, x, state, *, d_state: int, head_dim: int, live=None,
                eps: float | None = None):
    """Single-token decode. x: (B, 1, d_model); state = (conv_tail, ssm);
    live: optional (B,) bool — slots with live=False emit garbage output but
    keep their state untouched (continuous-batching dead slots)."""
    bsz, _, d_model = x.shape
    conv_tail, ssm = state  # (B, K-1, conv_dim), (B, nh, hd, ds)
    z, xbc, dt, d_inner, nh = _split_proj(params, x, d_model, d_state, head_dim)
    window = jnp.concatenate([conv_tail, xbc], axis=1)  # (B, K, conv_dim)
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"])
    if "conv_b" in params:
        conv_out = conv_out + params["conv_b"]
    conv_out = jax.nn.silu(conv_out)[:, None, :]
    new_tail = window[:, 1:, :]
    xs = conv_out[..., :d_inner].reshape(bsz, nh, head_dim)
    b_in = conv_out[:, 0, d_inner : d_inner + d_state]  # (B, ds)
    c_in = conv_out[:, 0, d_inner + d_state :]

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B, nh)
    a = -jnp.exp(params["a_log"])
    dec = jnp.exp(dt * a[None, :])  # (B, nh)
    ssm_new = ssm * dec[:, :, None, None] + jnp.einsum(
        "bh,bn,bhd->bhdn", dt, b_in.astype(jnp.float32), xs.astype(jnp.float32)
    )
    y = jnp.einsum("bn,bhdn->bhd", c_in.astype(jnp.float32), ssm_new)
    y = y + params["d_skip"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, 1, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["norm_gain"], eps)
    new_state = freeze_dead_slots((new_tail, ssm_new), state, live)
    return matmul(y, params["w_out"]), new_state
