"""Architecture config schema + registry.

Each assigned architecture gets one file in this package defining
``CONFIG = ArchConfig(...)`` with the exact assignment card values, plus a
``smoke()`` reduced variant (2 layers, d_model <= 512, <= 4 experts) used by
the CPU smoke tests. ``repro.configs.get(name)`` resolves either.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Literal

BlockKind = Literal["attn", "attn_moe", "shared_attn", "mamba", "mlstm", "slstm"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # block layout: `pattern` is cycled over the depth; remainder layers use
    # the pattern prefix. "shared_attn" re-uses ONE weight set everywhere.
    pattern: tuple[str, ...] = ("attn",)
    mlp_kind: str = "swiglu"
    norm_kind: str = "rmsnorm"
    qkv_bias: bool = False
    rope_theta: float = 1e4
    # False: attention without positional encoding (NoPE)
    use_rope: bool = True
    # softmax scale of GQA attention; None: 1/sqrt(head_dim)
    attn_scale: float | None = None
    # norm epsilon; None: each norm kind's own default (rmsnorm 1e-6,
    # layernorm 1e-5)
    norm_eps: float | None = None
    # scalar multipliers (Granite 4.0): the input embedding is scaled by
    # embedding_multiplier, every block's branch output by
    # residual_multiplier before its residual add, and the logits divided
    # by logits_scaling before the per-task head bias
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # every recurrent mixer block ("mamba", "mlstm", "slstm") is followed by
    # its own norm and MLP, as an attention block is
    mixer_mlp: bool = False
    sliding_window: int | None = None
    # MLA (DeepSeek-V2)
    use_mla: bool = False
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head_dim: int = 128
    # MoE
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # dispatch groups: set to the data-axis size so the MoE scatter/gather
    # stays shard-local (see models/moe.py)
    moe_groups: int = 1
    # SSM / xLSTM
    ssm_state: int = 0
    ssm_head_dim: int = 64
    # a bias on the Mamba-2 depthwise causal conv
    mamba_conv_bias: bool = False
    # IO
    input_mode: str = "tokens"  # tokens | vlm | audio
    num_codebooks: int = 1
    tie_embeddings: bool = False
    # multi-task personalization (the paper's technique)
    num_tasks: int = 16
    # default low-rank width for serving-time per-task adapters
    # (repro.serve.adapters.TaskAdapterStore); 0 = store callers must pass
    # an explicit rank
    adapter_rank: int = 0
    # perf knobs
    q_chunk: int = 1024
    mamba_chunk: int = 128
    remat: bool = True
    # serving attention backend: "jnp" (masked einsum over the cache /
    # gathered pages) or "pallas" (flash decode + chunked flash prefill
    # kernels, dense and block-table paged). "pallas" covers GQA attention
    # (causal + sliding window); MLA layers fall back to the jnp path and
    # recurrent mamba2/xLSTM blocks have no attention — see
    # repro.kernels.runtime.resolve_attn_backend for the fallback matrix.
    # The kernels COMPILE on TPU and run in interpret mode elsewhere — CPU
    # tests rely on that to exercise the kernel code path.
    attn_backend: str = "jnp"
    # unroll the period scan into a Python loop (exact HLO cost probes)
    unroll: bool = False
    # §Perf levers (default OFF == paper-faithful baseline):
    # chunked+remat xLSTM time scans (memory term)
    xlstm_chunk: int = 0
    # chunkwise-PARALLEL mLSTM (exact; intra-chunk math on the MXU) —
    # beyond-paper compute-term lever, uses xlstm_chunk (default 64) as c
    xlstm_parallel: bool = False
    # explicit FSDP gather of MoE expert weights before the expert einsums
    # (collective term — avoids activation-sized all-reduces)
    fsdp_gather_moe: bool = False
    # replicate the MLA compressed cache over the model axis (decode):
    # score contractions become local per head shard, killing the per-layer
    # (B, H, S) partial-score all-reduce at the cost of cache replication
    mla_replicate_cache: bool = False
    # shard the MLA compressed cache on the SEQUENCE dim over model
    # (flash-decode layout): score/ctx contractions go local, leaving only
    # (B,H)-sized softmax-stat and (B,H,r)-sized ctx partial all-reduces
    mla_cache_seq_shard: bool = False
    # optional with_sharding_constraint spec for the residual stream,
    # e.g. ("data", None, "model") — applied at period boundaries
    activation_sharding: tuple | None = None
    # optional constraint for the logits, e.g. ("data", None, "model"):
    # keeps the vocab dim sharded through the loss (never materializes the
    # full-vocab tensor per device)
    logits_sharding: tuple | None = None
    # long-context capability: True iff decode vs a 500k context is
    # sub-quadratic / bounded-state (SSM, hybrid, sliding window)
    long_context_ok: bool = False
    source: str = ""  # citation from the assignment card

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def num_periods(self) -> int:
        return self.num_layers // self.period

    @property
    def remainder(self) -> tuple[str, ...]:
        return self.pattern[: self.num_layers % self.period]

    @property
    def uses_moe(self) -> bool:
        return any(k == "attn_moe" for k in self.pattern)

    def validate(self) -> None:
        assert self.num_heads % max(self.num_kv_heads, 1) == 0
        if not self.use_mla:
            assert self.d_model % self.num_heads == 0 or self.head_dim > 0
        if self.uses_moe:
            assert self.num_experts >= self.top_k > 0
        for k in self.pattern:
            assert k in ("attn", "attn_moe", "shared_attn", "mamba", "mlstm", "slstm")
        assert self.attn_backend in ("jnp", "pallas"), self.attn_backend


_ARCHS = [
    "zamba2_7b",
    "deepseek_v2_236b",
    "mixtral_8x22b",
    "pixtral_12b",
    "xlstm_350m",
    "qwen1_5_110b",
    "musicgen_large",
    "qwen2_5_14b",
    "olmo_1b",
    "granite_4_h_micro",
    "phi4_mini_3_8b",
    "multitask_linreg",
    "multitask_lm",
]


def canonical(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def list_archs() -> list[str]:
    return list(_ARCHS)


def get(name: str, smoke: bool = False) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    cfg = mod.smoke() if smoke else mod.CONFIG
    cfg.validate()
    return cfg
