"""Plain float32 reference for Granite 4.0-H without experts (model_type
``granitemoehybrid``; ibm-granite/granite-4.0-h-micro).

Independent of the program under test: it imports nothing of it and is given
only the weights the benchmark made from the seed (``bench/arch/
granitemoehybrid.py`` lays them out: ``w["layers"][j]`` holds place j of the
period of ``layer_types``, stacked over the periods; an attention place has
``wq``) and the tokens the program served.

The model, as the published configuration and its reference implementation
(transformers' ``GraniteMoeHybrid``) describe it:
  * the token embedding times ``embedding_multiplier``, tied with the head;
  * per layer: RMSNorm, the mixer, ``x += residual_multiplier * mixer``;
    RMSNorm, a SwiGLU MLP ``(silu(h W_g) * (h W_i)) W_d``,
    ``x += residual_multiplier * mlp``; every RMSNorm with ``rms_norm_eps``;
  * the attention mixer: causal GQA with no positional encoding and the
    softmax scale ``attention_multiplier``, no biases;
  * the Mamba-2 mixer: ``in_proj`` to (z, xBC, dt); a depthwise causal conv
    of width ``mamba_d_conv`` with bias, then silu, over xBC; split into x
    (heads x head width), B and C (one group); ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; token by token, per head,
    ``h = exp(dt A) h + dt x B^T`` and ``y = h C + D x``; the gated RMSNorm
    ``norm(y * silu(z))``; ``out_proj``;
  * a final RMSNorm, the tied head, the logits divided by
    ``logits_scaling``.

Every matmul runs at ``highest`` precision in float32, the weights upcast
one layer at a time inside a scan over the periods (3.2 B parameters in
float32 would not fit beside the bf16 copy). Attention is computed in
blocks of queries and the head in blocks of positions, so that one sequence
of ``max_seq`` tokens fits; the recurrence runs token by token (no chunked
form). The ``dot`` argument swaps in float8 matmuls for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.olmo import HIGHEST, _fp8, dot_f32, dot_fp8

# queries per attention block, positions per head block
Q_BLOCK = 512
HEAD_BLOCK = 1024


def _blocks(n: int, size: int) -> int:
    """A block size that divides n: ``size`` where it does, else n."""
    return size if n % size == 0 else n


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def ssm(x, dt, b, c, a, d_skip):
    """The Mamba-2 recurrence, token by token. x: (S, H, P); dt: (S, H)
    after softplus; b, c: (S, N); a: (H,) negative; d_skip: (H,).
    Returns y (S, H, P)."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t, precision=HIGHEST)

    h0 = jnp.zeros(x.shape[1:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y + d_skip[:, None] * x


def mamba(h, lw, dims, dot):
    s = h.shape[0]
    nh, hp, ds, eps = dims["nh"], dims["shd"], dims["ds"], dims["eps"]
    di = nh * hp
    proj = dot(h, lw["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * ds], proj[:, -nh:]
    kw = lw["conv_w"]  # (K, channels): tap k meets the input K-1-k back
    k = kw.shape[0]
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[i:i + s] * kw[i] for i in range(k))
                      + lw["conv_b"])
    x, b, c = xbc[:, :di], xbc[:, di:di + ds], xbc[:, di + ds:]
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = ssm(x.reshape(s, nh, hp), dt, b, c, -jnp.exp(lw["a_log"]),
            lw["d_skip"]).reshape(s, di)
    return dot(rms_norm(y * jax.nn.silu(z), lw["norm"], eps), lw["out_proj"])


def attention(h, lw, dims, dot):
    """Causal GQA, no positions, in blocks of queries."""
    s = h.shape[0]
    kvh, hd = dims["kvh"], dims["hd"]
    g = dims["h"] // kvh
    q = dot(h, lw["wq"]).reshape(s, kvh, g, hd)
    k = dot(h, lw["wk"]).reshape(s, kvh, hd)
    v = dot(h, lw["wv"]).reshape(s, kvh, hd)
    if dot is dot_fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    blk = _blocks(s, Q_BLOCK)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk)
        sc = jnp.einsum("qkge,ske->kgqs", qb, k,
                        precision=HIGHEST) * dims["scale"]
        mask = jnp.arange(s)[None, :] <= start + jnp.arange(blk)[:, None]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        if dot is dot_fp8:
            p = _fp8(p)
        return jnp.einsum("kgqs,ske->qkge", p, v, precision=HIGHEST)

    o = jax.lax.map(one, jnp.arange(0, s, blk))
    return dot(o.reshape(s, dims["h"] * hd), lw["wo"])


def hidden(w, tokens, dims, dot=dot_f32):
    """The final RMSNorm's output (S, d) of one sequence."""
    f32 = jnp.float32
    x = w["embed"][tokens].astype(f32) * dims["emb"]
    r, eps = dims["res"], dims["eps"]

    def period(x, lws):
        for lw in lws:
            lw = jax.tree.map(lambda t: t.astype(f32), lw)
            h = rms_norm(x, lw["norm1"], eps)
            mix = attention if "wq" in lw else mamba
            x = x + r * mix(h, lw, dims, dot)
            h = rms_norm(x, lw["norm2"], eps)
            ff = dot(jax.nn.silu(dot(h, lw["wg"])) * dot(h, lw["wi"]),
                     lw["wd"])
            x = x + r * ff
        return x, None

    x, _ = jax.lax.scan(period, x, w["layers"])
    return rms_norm(x, w["final_norm"].astype(f32), eps)


def logits(w, x, dims, dot=dot_f32):
    """The tied head over hidden states x (n, d), divided by
    ``logits_scaling``."""
    return dot(x, w["embed"].astype(jnp.float32).T) / dims["logits"]


def forward(w, tokens, *, cfg, dot=dot_f32):
    """Logits (S, V) of one sequence (tokens (S,) int32)."""
    d = dims_of(cfg)
    return logits(w, hidden(w, tokens, d, dot), d, dot)


def dims_of(cfg: dict) -> dict:
    """What the forward pass reads of the configuration (the layers' kinds
    it reads off their weights)."""
    h = cfg["num_attention_heads"]
    return {
        "h": h, "kvh": cfg["num_key_value_heads"],
        "hd": cfg["hidden_size"] // h, "scale": cfg["attention_multiplier"],
        "nh": cfg["mamba_n_heads"], "shd": cfg["mamba_d_head"],
        "ds": cfg["mamba_d_state"], "eps": cfg["rms_norm_eps"],
        "emb": cfg["embedding_multiplier"], "res": cfg["residual_multiplier"],
        "logits": cfg["logits_scaling"],
    }


def row_stats(w, tokens, versions, ad, targets, base_bias, *, cfg,
              control=False):
    """Per position: the reference's best logit, the reference's logit of
    ``targets`` (the served next token), and the control's first choice
    read against the reference's logits. This architecture has no
    adapters (``ad`` is None, ``versions`` unread)."""
    if ad is not None:
        raise ValueError("granitemoehybrid has no adapters in the benchmark")
    d = dims_of(cfg)
    return _row_stats(w, tokens, targets, base_bias,
                      dims=tuple(sorted(d.items())), control=control)


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _row_stats(w, tokens, targets, base_bias, *, dims, control):
    d = dict(dims)
    bias = base_bias.astype(jnp.float32)
    ref = hidden(w, tokens, d)
    low = hidden(w, tokens, d, dot_fp8) if control else ref
    s = tokens.shape[0]
    blk = _blocks(s, HEAD_BLOCK)

    def one(start):
        rows = jax.lax.dynamic_slice_in_dim
        lg = logits(w, rows(ref, start, blk), d) + bias
        got = jnp.take_along_axis(lg, rows(targets, start, blk)[:, None],
                                  axis=-1)[:, 0]
        if not control:
            return jnp.max(lg, -1), got, got
        pick = jnp.argmax(logits(w, rows(low, start, blk), d, dot_fp8)
                          + bias, -1)
        ctrl = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return jnp.max(lg, -1), got, ctrl

    out = jax.lax.map(one, jnp.arange(0, s, blk))
    return tuple(t.reshape(s) for t in out)
