"""Granite 4.0-H in the benchmark: model_type ``granitemoehybrid``, without
experts (``num_local_experts`` 0).

Each layer is a mixer, Mamba-2 or NoPE GQA attention as ``layer_types``
says, followed by its own SwiGLU MLP, both under RMSNorm with the branch
scaled by ``residual_multiplier``. The weights are kept by place in the
period of ``layer_types``: ``w["layers"][j]`` holds the weights of every
layer at place j, stacked over the periods, which is how the program stacks
them (``stages[0]["slot<j>"]``) and how the plain reference
(``bench/reference/granite.py``) scans them. Besides what every
architecture's module gives (``bench.lib.spec.architecture``), this one
counts the Mamba-2 mixers' work for the readers of the layer "ssm mixers".
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.lib import costs
from bench.lib.weights import normal
from repro.configs.base import ArchConfig

KINDS = {"mamba": "mamba", "attention": "attn"}


def _check(cfg: dict) -> None:
    """The keys whose other values the program does not implement."""
    want = {"num_local_experts": 0, "mamba_n_groups": 1, "mamba_d_conv": 4,
            "mamba_expand": 2, "mamba_proj_bias": False,
            "attention_bias": False, "hidden_act": "silu",
            "normalization_function": "rmsnorm",
            "position_embedding_type": "nope"}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if bad:
        raise ValueError(f"granitemoehybrid: unsupported settings {bad}")
    if cfg["mamba_n_heads"] * cfg["mamba_d_head"] != 2 * cfg["hidden_size"]:
        raise ValueError("granitemoehybrid: mamba_n_heads * mamba_d_head "
                         "must be mamba_expand * hidden_size")


def period(cfg: dict) -> tuple[str, ...]:
    """The program's block kinds over the shortest period of
    ``layer_types``."""
    types = cfg["layer_types"]
    n = len(types)
    if n != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must list num_hidden_layers layers")
    for p in range(1, n + 1):
        if n % p == 0 and types == types[:p] * (n // p):
            return tuple(KINDS[t] for t in types[:p])
    raise AssertionError("unreachable: the whole list is a period")


def _dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = period(cfg)
    return dict(
        v=cfg["vocab_size"], d=d, h=h, kvh=cfg["num_key_value_heads"],
        hd=d // h, ff=cfg["shared_intermediate_size"], kinds=kinds,
        periods=cfg["num_hidden_layers"] // len(kinds),
        nh=cfg["mamba_n_heads"], shd=cfg["mamba_d_head"],
        ds=cfg["mamba_d_state"], k=cfg["mamba_d_conv"],
    )


def arch(cfg: dict) -> ArchConfig:
    """The program's config: the period of ``layer_types`` as its block
    pattern, every mixer with its own MLP, the four scalar multipliers."""
    _check(cfg)
    m = _dims(cfg)
    a = ArchConfig(
        name=cfg["model_type"],
        family="hybrid",
        num_layers=cfg["num_hidden_layers"],
        d_model=m["d"],
        num_heads=m["h"],
        num_kv_heads=m["kvh"],
        head_dim=m["hd"],
        d_ff=m["ff"],
        vocab_size=m["v"],
        pattern=m["kinds"],
        mlp_kind="swiglu",
        norm_kind="rmsnorm",
        norm_eps=cfg["rms_norm_eps"],
        use_rope=False,
        attn_scale=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        mixer_mlp=True,
        ssm_state=m["ds"],
        ssm_head_dim=m["shd"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        mamba_chunk=cfg["mamba_chunk_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        attn_backend=cfg["deployment"]["attn_backend"],
    )
    a.validate()
    return a


def _layer_shapes(m: dict, kind: str) -> dict:
    """name -> (shape after the period axis, std or a draw's name)."""
    d, ff = m["d"], m["ff"]
    out = {"norm1": ((d,), "ones"), "norm2": ((d,), "ones"),
           "wg": ((d, ff), d ** -0.5), "wi": ((d, ff), d ** -0.5),
           "wd": ((ff, d), ff ** -0.5)}
    if kind == "attn":
        q, kv = m["h"] * m["hd"], m["kvh"] * m["hd"]
        out.update(wq=((d, q), d ** -0.5), wk=((d, kv), d ** -0.5),
                   wv=((d, kv), d ** -0.5), wo=((q, d), q ** -0.5))
    else:
        di = m["nh"] * m["shd"]
        conv = di + 2 * m["ds"]
        out.update(
            in_proj=((d, di + conv + m["nh"]), d ** -0.5),
            conv_w=((m["k"], conv), m["k"] ** -0.5),
            conv_b=((conv,), m["k"] ** -0.5),
            a_log=((m["nh"],), "a_log"), dt_bias=((m["nh"],), "dt_bias"),
            d_skip=((m["nh"],), "ones"), norm=((di,), "ones"),
            out_proj=((di, d), di ** -0.5),
        )
    return out


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _draw(key, *, shape, std, dtype):
    """One weight stacked over the periods (``shape`` leads with them)."""
    if std == "ones":
        return jnp.ones(shape, dtype)
    if std == "a_log":  # A = -(1 .. n_heads)
        a = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, shape).astype(dtype)
    if std == "dt_bias":  # softplus^-1 of dt, log-uniform in [1e-3, 0.1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(math.log(1e-3) + u * (math.log(0.1) - math.log(1e-3)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return normal(key, shape, std, dtype)


def weights(cfg: dict, key, tasks: int, dtype) -> dict:
    """{"embed": (V, d), "final_norm": (d,), "task_head_bias": (tasks, V),
    "layers": [place j of the period: {name: (periods, ...)}]}: drawn one
    array per call, so that no draw holds more than one weight in float32.

    The embedding's std is 1/sqrt(hidden_size) over ``embedding_multiplier``,
    so that the scaled input has OLMo's 1/sqrt(hidden_size). At
    1/sqrt(hidden_size) itself the x12 input outweighs the 80 branches
    (x0.22) and the tied head picks the input token again at every
    position, by a margin no rounding flips: the program, the reference and
    the float8 control would all agree, and the check would test nothing."""
    m = _dims(cfg)
    layers = [_layer_shapes(m, kind) for kind in m["kinds"]]
    names = [(j, n) for j, shapes in enumerate(layers) for n in shapes]
    keys = jax.random.split(key, len(names) + 1)
    emb_std = m["d"] ** -0.5 / cfg["embedding_multiplier"]
    w = {"embed": _draw(keys[0], shape=(m["v"], m["d"]), std=emb_std,
                        dtype=dtype),
         "final_norm": jnp.ones((m["d"],), dtype),
         "task_head_bias": jnp.zeros((tasks, m["v"]), dtype),
         "layers": [{} for _ in layers]}
    for k, (j, n) in zip(keys[1:], names):
        shape, std = layers[j][n]
        w["layers"][j][n] = _draw(k, shape=(m["periods"],) + shape, std=std,
                                  dtype=dtype)
    return w


def params(w: dict) -> dict:
    """The weights in the program's parameter tree. The program keeps an
    RMSNorm's weight as its offset from 1 and A_log, dt_bias and D in
    float32; every other array is passed as it is."""
    def gain(t):
        return {"gain": t - 1}

    f32 = jnp.float32
    slots = {}
    for j, lw in enumerate(w["layers"]):
        p = {"norm2": gain(lw["norm2"]),
             "mlp": {"wg": lw["wg"], "wi": lw["wi"], "wo": lw["wd"]}}
        if "wq" in lw:
            p["norm1"] = gain(lw["norm1"])
            p["attn"] = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
        else:
            p["norm"] = gain(lw["norm1"])
            p["mamba"] = {
                "w_in": lw["in_proj"], "conv_w": lw["conv_w"],
                "conv_b": lw["conv_b"], "a_log": lw["a_log"].astype(f32),
                "dt_bias": lw["dt_bias"].astype(f32),
                "d_skip": lw["d_skip"].astype(f32),
                "norm_gain": lw["norm"] - 1, "w_out": lw["out_proj"],
            }
        slots[f"slot{j}"] = p
    hb = w["task_head_bias"]
    return {
        "embed": w["embed"],
        "stages": [slots],
        "final_norm": gain(w["final_norm"]),
        "task": {"head_bias": hb,
                 "final_gain": jnp.zeros((hb.shape[0], w["embed"].shape[1]),
                                         hb.dtype)},
    }


NO_ADAPTERS = ("granitemoehybrid has no adapter layout in the benchmark: "
               "set the configuration's adapters to null")


def adapter_raw(cfg: dict, key, dtype) -> dict:
    raise NotImplementedError(NO_ADAPTERS)


def store_raw(raw: dict) -> dict:
    raise NotImplementedError(NO_ADAPTERS)


def shape(cfg: dict) -> costs.Shape:
    """The attention layers, and the matmul parameters per token of all 40
    mixers and MLPs."""
    m = _dims(cfg)
    n_attn = m["kinds"].count("attn") * m["periods"]
    n_mamba = m["kinds"].count("mamba") * m["periods"]
    q, kv = m["h"] * m["hd"], m["kvh"] * m["hd"]
    attn = 2 * m["d"] * q + 2 * m["d"] * kv
    body = (n_attn * attn + n_mamba * _mamba_params(m)
            + cfg["num_hidden_layers"] * 3 * m["d"] * m["ff"])
    return costs.Shape(attn_layers=n_attn, heads=m["h"], kv_heads=m["kvh"],
                       head_dim=m["hd"], body_params=body, d=m["d"],
                       vocab=m["v"])


# ------------------------------------------------ the Mamba-2 mixers' work
def _mamba_params(m: dict) -> int:
    """Matmul parameters of one mixer: in_proj and out_proj."""
    di = m["nh"] * m["shd"]
    return m["d"] * (2 * di + 2 * m["ds"] + m["nh"]) + di * m["d"]


def _mamba_layers(m: dict) -> int:
    return m["kinds"].count("mamba") * m["periods"]


def recurrence_flops(cfg: dict, tokens: int) -> float:
    """The mixers' operations outside their matmul parameters for
    ``tokens`` real positions: the depthwise conv (2 K per channel) and the
    recurrence, per head element ``h = a h + (dt x) B`` (3 operations) and
    ``y = C h`` (2)."""
    m = _dims(cfg)
    conv = 2 * m["k"] * (m["nh"] * m["shd"] + 2 * m["ds"])
    rec = 5 * m["nh"] * m["shd"] * m["ds"]
    return float(tokens * _mamba_layers(m) * (conv + rec))


def mixer_flops(cfg: dict, tokens: int) -> float:
    """Useful operations of every Mamba-2 mixer for ``tokens`` real
    positions: projections, conv and recurrence."""
    m = _dims(cfg)
    proj = 2.0 * tokens * _mamba_layers(m) * _mamba_params(m)
    return proj + recurrence_flops(cfg, tokens)


def mixer_weight_bytes(cfg: dict, act_bytes: int = 2) -> float:
    """Every mixer's weights read once: the projections and the conv in
    bf16, A_log, dt_bias and D in float32, the gated norm's weight."""
    m = _dims(cfg)
    di = m["nh"] * m["shd"]
    conv = (m["k"] + 1) * (di + 2 * m["ds"])
    one = (_mamba_params(m) + conv + di) * act_bytes + 3 * m["nh"] * 4
    return float(_mamba_layers(m) * one)


def mixer_state_bytes(cfg: dict, act_bytes: int = 2) -> float:
    """One slot's state in every mixer, read once or written once: the SSM
    state in float32 and the conv's last K-1 inputs."""
    m = _dims(cfg)
    ssm = m["nh"] * m["shd"] * m["ds"] * 4
    conv = (m["k"] - 1) * (m["nh"] * m["shd"] + 2 * m["ds"]) * act_bytes
    return float(_mamba_layers(m) * (ssm + conv))
