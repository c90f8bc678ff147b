"""Plain float32 reference for OLMo (arXiv:2402.00838), with per-task adapters.

Independent of the program under test: it imports nothing of it and is given
only the weights the benchmark made from the seed, the token sequences the
program served and which store version each position was computed under.

The model, as the paper describes it:
  * token embedding, tied with the output head;
  * per block: non-parametric LayerNorm (no gain, no bias), multi-head
    attention with rotary embeddings (rotate-half pairing, theta from the
    config) and no biases, a residual add; non-parametric LayerNorm, a SwiGLU
    MLP ``(silu(h W_g) * (h W_i)) W_d``, a residual add;
  * a final non-parametric LayerNorm and the tied head.

The serving adapters (the paper's graph-mixed per-task parameters): each
task owns, per block, a rank-r pair ``a (d, r)``, ``b (r, d)`` for the
attention branch and one for the MLP branch, applied off that branch's
normed input (``out += (h a) b``), and a head bias added to the logits. The
served copy of task t after v mixing updates is
``sum_k (mu^(v+1))[k, t] raw[k]`` with ``mu = alpha (I + tau/eta L)^-1``
over the task graph's Laplacian L (eq. after (7) of arXiv:1802.03830).

Every matmul runs at ``highest`` precision in float32, layer by layer under a
scan, so one sequence of ``max_seq`` tokens fits beside nothing else. The
``dot`` argument swaps in the lower precision for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST
# versions of the adapter store one request can span, padded to this count
# so that one compiled reference serves every request
MAX_VERSIONS = 32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def dot_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def dot_fp8(a, b):
    """The control's matmul: both operands in float8 e4m3, products summed
    in float32."""
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def layer_norm(x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS)


def rope(x, theta):
    """x: (S, H, hd); position s rotates pair (i, i + hd/2) by s * f_i."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lora(h, a, b, onehot, dot):
    """Per-position rank-r delta: position s uses version onehot[s]."""
    if dot is dot_fp8:
        h, a, b = _fp8(h), _fp8(a), _fp8(b)
    u = jnp.einsum("sd,vdr->svr", h, a, precision=HIGHEST)
    if dot is dot_fp8:
        u = _fp8(u)
    u = u * onehot[:, :, None]
    return jnp.einsum("svr,vrd->sd", u, b, precision=HIGHEST)


def forward(w, tokens, versions, ad, *, heads, theta, dot=dot_f32):
    """Logits (S, V) of one sequence.

    w: the benchmark's weights (any float dtype; upcast layer by layer);
    tokens: (S,) int32; versions: (S,) int32 index into the adapter
    versions; ad: None, or {"attn_a": (L, NV, d, r), "attn_b": (L, NV, r,
    d), "mlp_a", "mlp_b", "head_bias": (NV, V)} float32, the served factors
    of this sequence's task at each version."""
    f32 = jnp.float32
    embed = w["embed"].astype(f32)
    x = embed[tokens]
    s, d = x.shape
    hd = d // heads
    mask = jnp.tril(jnp.ones((s, s), bool))
    onehot = None
    if ad is not None:
        onehot = jax.nn.one_hot(versions, ad["head_bias"].shape[0], dtype=f32)

    def block(x, lw):
        lw = jax.tree.map(lambda t: t.astype(f32), lw)
        h = layer_norm(x)
        q = rope(dot(h, lw["wq"]).reshape(s, heads, hd), theta)
        k = rope(dot(h, lw["wk"]).reshape(s, heads, hd), theta)
        v = dot(h, lw["wv"]).reshape(s, heads, hd)
        if dot is dot_f32:
            sc = jnp.einsum("qhe,khe->hqk", q, k, precision=HIGHEST)
        else:
            sc = jnp.einsum("qhe,khe->hqk", _fp8(q), _fp8(k), precision=HIGHEST)
        sc = jnp.where(mask[None], sc / np.sqrt(hd), -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if dot is dot_f32:
            o = jnp.einsum("hqk,khe->qhe", p, v, precision=HIGHEST)
        else:
            o = jnp.einsum("hqk,khe->qhe", _fp8(p), _fp8(v), precision=HIGHEST)
        out = dot(o.reshape(s, d), lw["wo"])
        if ad is not None:
            out = out + _lora(h, lw["attn_a"], lw["attn_b"], onehot, dot)
        x = x + out
        h = layer_norm(x)
        ff = dot(jax.nn.silu(dot(h, lw["wg"])) * dot(h, lw["wi"]), lw["wd"])
        if ad is not None:
            ff = ff + _lora(h, lw["mlp_a"], lw["mlp_b"], onehot, dot)
        return x + ff, None

    layers = {k: w[k] for k in ("wq", "wk", "wv", "wo", "wg", "wi", "wd")}
    if ad is not None:
        layers.update({k: ad[k] for k in ("attn_a", "attn_b", "mlp_a", "mlp_b")})
    x, _ = jax.lax.scan(block, x, layers)
    logits = dot(layer_norm(x), embed.T)
    if ad is not None:
        logits = logits + onehot @ ad["head_bias"]
    return logits


def row_stats(w, tokens, versions, ad, targets, base_bias, *, cfg,
              control=False):
    """Per position: the reference's best logit, the reference's logit of
    ``targets`` (the served next token), and the control's first choice.

    The reference runs in float32 at ``highest``; with ``control`` the
    float8 forward also runs over the same tokens and its argmax is read
    against the reference's logits. base_bias: (V,) the task's own head bias
    from the weights; cfg: the configuration, which gives the heads and the
    rotary base."""
    return _row_stats(w, tokens, versions, ad, targets, base_bias,
                      heads=cfg["num_attention_heads"],
                      theta=cfg["rope_theta"], control=control)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "control"))
def _row_stats(w, tokens, versions, ad, targets, base_bias, *, heads, theta,
               control):
    ref = forward(w, tokens, versions, ad, heads=heads, theta=theta)
    ref = ref + base_bias.astype(jnp.float32)
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
    if not control:
        return best, served, served
    low = forward(w, tokens, versions, ad, heads=heads, theta=theta,
                  dot=dot_fp8) + base_bias.astype(jnp.float32)
    pick = jnp.argmax(low, axis=-1)
    ctrl = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return best, served, ctrl


# ------------------------------------------------------------ graph mixing
def band_adjacency(m: int, bandwidth: int) -> np.ndarray:
    a = np.zeros((m, m))
    for i in range(m):
        for off in range(1, bandwidth + 1):
            j = (i + off) % m
            a[i, j] = a[j, i] = 1.0
    return a


def bsr_mixing(adjacency: np.ndarray, eta: float, tau: float,
               alpha: float) -> np.ndarray:
    """mu = alpha (I + tau/eta L)^-1, in float64."""
    lap = np.diag(adjacency.sum(axis=1)) - adjacency
    m = adjacency.shape[0]
    return alpha * np.linalg.inv(np.eye(m) + (tau / eta) * lap)


def adapter_versions(mu: np.ndarray, raw: dict, task: int, versions):
    """The served factors of ``task`` at each store version in ``versions``
    (sorted, distinct), padded to MAX_VERSIONS, laid out as ``forward``
    takes them. raw: {"attn_a": (m, L, d, r), "attn_b": (m, L, r, d),
    "mlp_a", "mlp_b", "head_bias": (m, V)} as the benchmark made them."""
    if len(versions) > MAX_VERSIONS:
        raise ValueError(
            f"{len(versions)} store versions in one request; the reference "
            f"holds {MAX_VERSIONS}"
        )
    wts = np.zeros((MAX_VERSIONS, mu.shape[0]), np.float32)
    for j, v in enumerate(versions):
        wts[j] = np.linalg.matrix_power(mu, int(v) + 1)[:, task]
    mixed = _mix_rows(jnp.asarray(wts), raw)
    return {k: (t if k == "head_bias" else jnp.moveaxis(t, 0, 1))
            for k, t in mixed.items()}


@jax.jit
def _mix_rows(weights, raw):
    return jax.tree.map(
        lambda t: jnp.einsum("vm,m...->v...", weights, t.astype(jnp.float32),
                             precision=HIGHEST),
        raw,
    )
