"""Operations and bytes the algorithm needs, worked out from shapes.

The arithmetic is that of ``benchmarks/costmodel.py`` (2 N per token for
inference, plus attention over the context: 2 * keys * heads * (qk + v)
per query per attention layer), restated here for the served shapes so that the
yardstick stays with the benchmark. Attention bytes count the LIVE context
only: the keys and values a query may see, once per slot and layer, plus
the queries and outputs. A kernel that streams pages it does not need
therefore reads below its roofline.
"""
from __future__ import annotations

from dataclasses import dataclass

from bench.lib import spec


@dataclass(frozen=True)
class Shape:
    """What the counts need of a model, from its architecture's module
    (``bench/arch/<model_type>.py``): its attention layers, with their
    heads and head width, and the matmul parameters per token of the whole
    body (every layer's mixer and MLP, not the embedding or the head)."""
    attn_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    body_params: int
    d: int
    vocab: int
    act_bytes: int = 2  # bf16 activations and cache

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return spec.architecture(cfg["model_type"]).shape(cfg)


def body_flops(s: Shape, tokens: int) -> float:
    """Matmul operations of the body for ``tokens`` positions."""
    return 2.0 * s.body_params * tokens


def head_flops(s: Shape, rows: int) -> float:
    """The output head for ``rows`` positions (one per emitted token)."""
    return 2.0 * s.d * s.vocab * rows


def attn_flops(s: Shape, start: int, n: int) -> float:
    """Queries at positions start .. start+n-1, each over its causal
    prefix (position p sees p + 1 keys), summed over attention layers."""
    keys = n * start + n * (n + 1) // 2
    return 2.0 * keys * s.heads * 2 * s.head_dim * s.attn_layers


def attn_bytes(s: Shape, start: int, n: int) -> float:
    """Live keys and values (start + n positions) read once, queries read
    and outputs written, summed over attention layers."""
    kv = 2 * (start + n) * s.kv_heads * s.head_dim
    qo = 2 * n * s.heads * s.head_dim
    return float((kv + qo) * s.act_bytes * s.attn_layers)


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def useful_flops(ticks, s: Shape) -> float:
    """The work a window needed: the body for every real token computed,
    attention over each token's live causal prefix, the head for every
    token emitted. Padding in the step slab is not useful work."""
    flops = 0.0
    for t in ticks:
        for a, k in t.chunks:
            flops += body_flops(s, k) + attn_flops(s, a, k)
        flops += head_flops(s, t.emitted)
    return flops
