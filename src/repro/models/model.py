"""Config-driven LM assembly: dense / MoE / MLA / SSM / hybrid / VLM / audio.

Layer stacks are grouped by the config's block ``pattern``: one ``lax.scan``
over pattern periods (stacked params, O(1) HLO size in depth) plus an
unstacked remainder stage. "shared_attn" blocks (Zamba2) reuse a single
weight set across all periods via closure capture.

Four entry points, all pure functions of (params, inputs):
  * ``forward``      — full-sequence logits (training / evaluation).
  * ``prefill``      — full-sequence + populated caches, last-token logits.
  * ``decode_step``  — one token against caches at ``pos``.
  * ``prefill_step`` — a (B, C) prompt chunk against caches at per-slot
    offsets, all C tokens computed in parallel (serving prefill).

Every block's mixer runs under a ``jax.named_scope`` ("attention", "mamba2",
"mlstm", "slstm") and its MLP under "mlp" ("moe"), so that a profile of the
compiled step can put device time on them.

Multi-task personalization (the paper's technique) lives in ``params['task']``:
per-task final-norm gain, lm-head bias and (MoE) router bias, all with a
leading task axis that the launcher shards over the data mesh axis. The
graph-mixed update is applied by `repro/train/trainer.py` via
`repro.core.distributed.GraphMultiTask`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.runtime import resolve_attn_backend
from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import xlstm as xlstm_lib
from repro.models.attention import MLADims
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    apply_task_lora,
    dense_init,
    init_mlp,
    init_norm,
    matmul,
)
from repro.models.moe import apply_moe, init_moe

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    cfg: ArchConfig
    dtype: Any = jnp.float32

    # ------------------------------------------------------------------ init
    def _mla_dims(self) -> MLADims:
        c = self.cfg
        return MLADims(c.num_heads, c.qk_nope, c.qk_rope, c.v_head_dim, c.kv_lora)

    def _init_block(self, key, kind: str) -> dict:
        c = self.cfg
        ks = jax.random.split(key, 4)
        if kind in ("attn", "attn_moe", "shared_attn"):
            if c.use_mla:
                att = attn_lib.init_mla(ks[0], c.d_model, self._mla_dims(), self.dtype)
            else:
                att = attn_lib.init_gqa(
                    ks[0], c.d_model, c.num_heads, c.num_kv_heads, c.head_dim,
                    c.qkv_bias, self.dtype,
                )
            p = {
                "norm1": init_norm(c.norm_kind, c.d_model, self.dtype),
                "attn": att,
                "norm2": init_norm(c.norm_kind, c.d_model, self.dtype),
            }
            if kind == "attn_moe":
                p["moe"] = init_moe(
                    ks[1], c.d_model, c.d_ff, c.num_experts,
                    c.num_shared_experts, self.dtype,
                )
            else:
                p["mlp"] = init_mlp(ks[1], c.d_model, c.d_ff, c.mlp_kind, self.dtype)
            return p
        if kind == "mamba":
            mixer = mamba_lib.init_mamba2(
                ks[0], c.d_model, c.ssm_state, c.ssm_head_dim, self.dtype,
                c.mamba_conv_bias,
            )
        elif kind == "mlstm":
            mixer = xlstm_lib.init_mlstm(ks[0], c.d_model, c.num_heads)
        elif kind == "slstm":
            mixer = xlstm_lib.init_slstm(ks[0], c.d_model, c.num_heads)
        else:
            raise ValueError(kind)
        p = {"norm": init_norm(c.norm_kind, c.d_model, self.dtype), kind: mixer}
        if c.mixer_mlp:
            p["norm2"] = init_norm(c.norm_kind, c.d_model, self.dtype)
            p["mlp"] = init_mlp(ks[1], c.d_model, c.d_ff, c.mlp_kind, self.dtype)
        return p

    def _stage_patterns(self) -> list[tuple[str, ...]]:
        c = self.cfg
        stages = []
        if c.num_periods > 0:
            stages.append(c.pattern)
        if c.remainder:
            stages.append(c.remainder)
        return stages

    def init(self, key) -> PyTree:
        c = self.cfg
        keys = jax.random.split(key, 8)
        params: dict = {}
        v_total = c.vocab_size * c.num_codebooks
        if c.input_mode == "audio":
            params["embed"] = dense_init(
                keys[0], (c.num_codebooks, c.vocab_size, c.d_model), in_axis=2,
                dtype=self.dtype,
            )
        else:
            params["embed"] = dense_init(
                keys[0], (c.vocab_size, c.d_model), in_axis=1, dtype=self.dtype
            )
        # stages
        stage_params = []
        kidx = 1
        for si, pat in enumerate(self._stage_patterns()):
            reps = c.num_periods if si == 0 and c.num_periods > 0 else 1
            slots = {}
            for j, kind in enumerate(pat):
                if kind == "shared_attn":
                    continue  # single copy, initialized below
                skeys = jax.random.split(jax.random.fold_in(keys[1], kidx), reps)
                kidx += 1
                slots[f"slot{j}"] = jax.vmap(
                    lambda k, kk=kind: self._init_block(k, kk)
                )(skeys)
            stage_params.append(slots)
        params["stages"] = stage_params
        if any(k == "shared_attn" for k in c.pattern):
            params["shared_attn"] = self._init_block(keys[2], "shared_attn")
        params["final_norm"] = init_norm(c.norm_kind, c.d_model, self.dtype)
        if not c.tie_embeddings:
            params["head"] = dense_init(keys[3], (c.d_model, v_total), dtype=self.dtype)
        # ---- per-task personalization (paper's technique) ----
        task: dict = {"head_bias": jnp.zeros((c.num_tasks, v_total), self.dtype)}
        if c.norm_kind != "nonparam_ln":
            task["final_gain"] = jnp.zeros((c.num_tasks, c.d_model), self.dtype)
        if c.uses_moe:
            task["router_bias"] = jnp.zeros((c.num_tasks, c.num_experts), self.dtype)
        params["task"] = task
        return params

    # ----------------------------------------------------------------- embed
    def _embed(self, params, batch) -> Array:
        c = self.cfg
        # token ids come from untrusted callers: CLIP an out-of-vocab id to
        # the last embedding row instead of the NaN-fill default, which
        # would poison the whole row's activations (R001)
        if c.input_mode == "audio":
            toks = batch["tokens"]  # (B, S, K)
            x = sum(
                jnp.take(params["embed"][k], toks[:, :, k], axis=0, mode="clip")
                for k in range(c.num_codebooks)
            )
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0, mode="clip")
            if c.input_mode == "vlm":
                x = jnp.where(
                    batch["vision_mask"][..., None],
                    batch["vision_embeds"].astype(x.dtype),
                    x,
                )
        if c.embedding_multiplier != 1.0:
            x = x * c.embedding_multiplier
        return x

    # Per-task param gathers must CLIP out-of-range ids, not use jnp.take's
    # default NaN fill: serving dead lanes carry the null-adapter id
    # num_tasks (one past the params["task"] stacks), and a NaN-filled dead
    # row would poison LIVE rows through the MoE dispatch's shared expert
    # buffers. Clipped dead-lane gathers feed only discarded outputs.
    _TAKE_MODE = "clip"

    def _router_bias(self, params, batch, seq: int, task_ad=None) -> Array | None:
        if not self.cfg.uses_moe or "task_ids" not in batch:
            return None
        bias = jnp.take(
            params["task"]["router_bias"], batch["task_ids"], axis=0,
            mode=self._TAKE_MODE,
        )
        if task_ad is not None and "router_bias" in task_ad:
            bias = bias + task_ad["router_bias"].astype(bias.dtype)
        return jnp.broadcast_to(bias[:, None, :], (bias.shape[0], seq, bias.shape[1]))

    def _logits(self, params, x, batch, task_ad=None) -> Array:
        c = self.cfg
        x = self._norm(x, params["final_norm"])
        if "final_gain" in params["task"] and "task_ids" in batch:
            gain = jnp.take(
                params["task"]["final_gain"], batch["task_ids"], axis=0,
                mode=self._TAKE_MODE,
            )
            if task_ad is not None and "final_gain" in task_ad:
                gain = gain + task_ad["final_gain"].astype(gain.dtype)
            x = x * (1.0 + gain[:, None, :].astype(x.dtype))
        if c.tie_embeddings:
            head = params["embed"].T
        else:
            head = params["head"]
        logits = jax.lax.dot_general(
            x, head, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if c.logits_scaling != 1.0:
            logits = logits / c.logits_scaling
        if "task_ids" in batch:
            hb = jnp.take(
                params["task"]["head_bias"], batch["task_ids"], axis=0,
                mode=self._TAKE_MODE,
            )
            if task_ad is not None and "head_bias" in task_ad:
                hb = hb + task_ad["head_bias"].astype(hb.dtype)
            logits = logits + hb[:, None, :].astype(jnp.float32)
        if c.logits_sharding is not None:
            from jax.sharding import PartitionSpec

            logits = jax.lax.with_sharding_constraint(
                logits, PartitionSpec(*c.logits_sharding)
            )
        if c.num_codebooks > 1:
            b, s, _ = logits.shape
            logits = logits.reshape(b, s, c.num_codebooks, c.vocab_size)
        return logits

    # ------------------------------------------------------ block pieces
    def _norm(self, x, p) -> Array:
        c = self.cfg
        return apply_norm(c.norm_kind, x, p or None, c.norm_eps)

    def _residual(self, x, out) -> Array:
        """The residual add, the branch scaled by ``residual_multiplier``."""
        r = self.cfg.residual_multiplier
        return x + (out if r == 1.0 else out * r)

    def _rope(self, t, positions) -> Array:
        c = self.cfg
        return attn_lib.apply_rope(t, positions, c.rope_theta) if c.use_rope else t

    def _ffn(self, kind, p, x, router_bias, live=None, ad=None):
        """The second half of every block that has one: norm, the MLP
        (name scope ``mlp``) or MoE (``moe``), the per-task MLP delta where
        the adapters hold one, the residual add. live: None, (B,) or (B, C)
        — ``apply_moe`` takes either. Returns (x, aux)."""
        c = self.cfg
        h = self._norm(x, p["norm2"])
        aux = jnp.zeros((), jnp.float32)
        if kind == "attn_moe":
            with jax.named_scope("moe"):
                ff, aux = apply_moe(
                    p["moe"], h, top_k=c.top_k,
                    capacity_factor=c.capacity_factor,
                    router_bias=router_bias, groups=c.moe_groups,
                    fsdp_gather=c.fsdp_gather_moe, live=live,
                )
        else:
            with jax.named_scope("mlp"):
                ff = apply_mlp(p["mlp"], h, c.mlp_kind)
        if ad is not None and "mlp" in ad:
            ff = ff + apply_task_lora(h, ad["mlp"])
        return self._residual(x, ff), aux

    # name scope each recurrent mixer runs under (the benchmark's trace
    # reduction puts device time on a mixer by it)
    _MIXER_SCOPES = {"mamba": "mamba2", "mlstm": "mlstm", "slstm": "slstm"}

    def _recurrent_block(self, kind, p, x, mix, ad=None):
        """A recurrent block: norm, ``mix(h) -> (out, state)`` under the
        mixer's name scope, the per-task delta off the normed input, the
        residual add, then (``cfg.mixer_mlp``) the block's own MLP.
        Returns (x, state)."""
        h = self._norm(x, p["norm"])
        with jax.named_scope(self._MIXER_SCOPES[kind]):
            out, state = mix(h)
        if ad is not None:
            out = out + apply_task_lora(h, ad["out"])
        x = self._residual(x, out)
        if self.cfg.mixer_mlp:
            x, _ = self._ffn(kind, p, x, None, ad=ad)
        return x, state

    # ----------------------------------------------------- full-seq blocks
    def _block_full(self, kind, p, x, positions, router_bias, want_cache):
        """Returns (x, cache_entry, aux). cache entry is the FULL-SEQ state
        (attn: (k, v) over the sequence; ssm: final state)."""
        c = self.cfg
        if kind in self._ATTN_KINDS:
            cache = ()
            h = self._norm(x, p["norm1"])
            with jax.named_scope("attention"):
                if c.use_mla:
                    out, (c_kv, k_rope) = attn_lib.mla_full(
                        p["attn"], h, self._mla_dims(), positions,
                        c.rope_theta, q_chunk=c.q_chunk,
                    )
                    if want_cache:
                        cache = (c_kv, k_rope)
                else:
                    q, k, v = attn_lib.gqa_project(
                        p["attn"], h, c.num_heads, c.num_kv_heads, c.head_dim
                    )
                    q, k = self._rope(q, positions), self._rope(k, positions)
                    o = attn_lib.causal_attend(
                        q, k, v, sliding_window=c.sliding_window,
                        q_chunk=c.q_chunk, scale=c.attn_scale,
                    )
                    b, s, _, _ = o.shape
                    out = matmul(
                        o.reshape(b, s, c.num_heads * c.head_dim),
                        p["attn"]["wo"],
                    )
                    if want_cache:
                        cache = (k, v)
            x = self._residual(x, out)
            x, aux = self._ffn(kind, p, x, router_bias)
            return x, cache, aux
        if kind == "mamba":
            def mix(h):
                return mamba_lib.mamba2_full(
                    p["mamba"], h, d_state=c.ssm_state,
                    head_dim=c.ssm_head_dim, chunk=c.mamba_chunk,
                    eps=c.norm_eps,
                )
        elif kind == "mlstm":
            def mix(h):
                if c.xlstm_parallel:
                    return xlstm_lib.mlstm_chunkwise(
                        p["mlstm"], h, n_heads=c.num_heads,
                        chunk=c.xlstm_chunk or 64,
                    )
                return xlstm_lib.mlstm_full(
                    p["mlstm"], h, n_heads=c.num_heads, chunk=c.xlstm_chunk
                )
        elif kind == "slstm":
            def mix(h):
                return xlstm_lib.slstm_full(
                    p["slstm"], h, n_heads=c.num_heads, chunk=c.xlstm_chunk
                )
        else:
            raise ValueError(kind)
        x, state = self._recurrent_block(kind, p, x, mix)
        return x, (state if want_cache else ()), jnp.zeros((), jnp.float32)

    def _constrain(self, x):
        spec = self.cfg.activation_sharding
        if spec is not None:
            from jax.sharding import PartitionSpec

            x = jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))
        return x

    def _run_stages(self, params, x, positions, router_bias, want_cache):
        c = self.cfg
        total_aux = jnp.zeros((), jnp.float32)
        caches = []
        for si, pat in enumerate(self._stage_patterns()):
            slots = params["stages"][si]

            def body(carry, xs, pat=pat, slots=slots):
                h = carry
                aux_acc = jnp.zeros((), jnp.float32)
                cache_out = {}
                for j, kind in enumerate(pat):
                    p = (
                        params["shared_attn"]
                        if kind == "shared_attn"
                        else xs[f"slot{j}"]
                    )
                    h, cache, aux = self._block_full(
                        kind, p, h, positions, router_bias, want_cache
                    )
                    aux_acc = aux_acc + aux
                    cache_out[f"slot{j}"] = cache
                return self._constrain(h), (cache_out, aux_acc)

            if c.unroll:
                reps = jax.tree_util.tree_leaves(slots)[0].shape[0]
                stage_cache_list, aux_list = [], []
                for i in range(reps):
                    sl = jax.tree.map(lambda t: t[i], slots)
                    x, (co, au) = body(x, sl)
                    stage_cache_list.append(co)
                    aux_list.append(au)
                stage_cache = jax.tree.map(
                    lambda *ts: jnp.stack(ts), *stage_cache_list
                )
                auxes = jnp.stack(aux_list)
            else:
                if c.remat and not want_cache:
                    body = jax.checkpoint(body, prevent_cse=False)
                x, (stage_cache, auxes) = jax.lax.scan(body, x, slots)
            caches.append(stage_cache)
            total_aux = total_aux + jnp.sum(auxes)
        return x, caches, total_aux

    # ------------------------------------------------------------- forward
    def forward(self, params, batch) -> tuple[Array, Array]:
        """Training/eval forward: logits (B, S, [K,] V) + moe aux loss."""
        x = self._constrain(self._embed(params, batch))
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        rb = self._router_bias(params, batch, s)
        x, _, aux = self._run_stages(params, x, positions, rb, want_cache=False)
        return self._logits(params, x, batch), aux

    def loss_fn(self, params, batch, aux_weight: float = 0.01):
        """Softmax cross-entropy, written sharding-friendly: the label logit
        is extracted by a masked REDUCTION over the vocab axis (lowers to a
        partial sum + small all-reduce when vocab is model-sharded) instead of
        a gather, which would force GSPMD to materialize full-vocab logits."""
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1
        )
        label_logit = jnp.sum(
            jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
        )
        nll = lse - label_logit
        loss = jnp.mean(nll) + aux_weight * aux
        return loss, {"nll": jnp.mean(nll), "aux": aux}

    # ------------------------------------------------------------- serving
    _ATTN_KINDS = ("attn", "attn_moe", "shared_attn")

    def _empty_attn_cache(self, b, max_seq, paging=None):
        """Dense: per-slot (B, max_seq, ...) stripes. Paged: ONE shared
        (num_blocks, block_size, ...) pool (slots address it through block
        tables — see repro.serve.paging for the layout invariants)."""
        c = self.cfg
        if paging is not None:
            lead = (paging.num_blocks, paging.block_size)
        else:
            lead = (b, max_seq)
        if c.use_mla:
            return (
                jnp.zeros(lead + (c.kv_lora,), self.dtype),
                jnp.zeros(lead + (c.qk_rope,), self.dtype),
            )
        return (
            jnp.zeros(lead + (c.num_kv_heads, c.head_dim), self.dtype),
            jnp.zeros(lead + (c.num_kv_heads, c.head_dim), self.dtype),
        )

    def _empty_block_cache(self, kind, b, max_seq, paging=None):
        c = self.cfg
        if kind in self._ATTN_KINDS:
            return self._empty_attn_cache(b, max_seq, paging)
        if kind == "mamba":
            d_inner, nh, conv_dim = mamba_lib.dims(
                c.d_model, c.ssm_state, c.ssm_head_dim
            )
            return (
                jnp.zeros((b, mamba_lib.CONV_K - 1, conv_dim), self.dtype),
                jnp.zeros((b, nh, c.ssm_head_dim, c.ssm_state), jnp.float32),
            )
        if kind == "mlstm":
            d_inner = int(c.d_model * 2.0)
            hd = d_inner // c.num_heads
            return xlstm_lib.mlstm_init_state(b, c.num_heads, hd)
        if kind == "slstm":
            return xlstm_lib.slstm_init_state(
                b, c.num_heads, c.d_model // c.num_heads
            )
        raise ValueError(kind)

    def init_cache(self, batch_size: int, max_seq: int, paging=None) -> list:
        """Cache pytree: list (stage) of {slot: stacked entries (P, ...)}.

        paging: optional ``repro.serve.paging.PagingSpec`` — attention
        entries become shared (P, num_blocks, block_size, ...) pools
        (addressed via block tables in ``decode_step``); recurrent SSM /
        xLSTM states are O(1) per slot and stay dense (P, B, ...)."""
        caches = []
        for si, pat in enumerate(self._stage_patterns()):
            reps = self.cfg.num_periods if si == 0 and self.cfg.num_periods > 0 else 1
            stage = {}
            for j, kind in enumerate(pat):
                one = self._empty_block_cache(kind, batch_size, max_seq, paging)
                stage[f"slot{j}"] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (reps,) + t.shape), one
                )
            caches.append(stage)
        return caches

    def reset_slot_state(self, caches, reset, max_seq: int, paging=None):
        """Restore (re)admitted slots' PER-SLOT cache entries to the pristine
        init value (recurrent states are cumulative and must be cleared on
        slot reuse; the init values are not all zeros — mLSTM stabilizer m0
        is -1e30 — so reference entries are traced in as constants).

        Paged attention pools need NO clearing: the new request rewrites
        every position it can read (prefill writes 0..S0-1, decode writes
        each pos) and reads are masked by ``kv_idx <= pos``, so stale bytes
        in recycled blocks are unreachable. reset: (B,) bool."""
        b = reset.shape[0]
        out = []
        for si, pat in enumerate(self._stage_patterns()):
            reps = self.cfg.num_periods if si == 0 and self.cfg.num_periods > 0 else 1
            stage = {}
            for j, kind in enumerate(pat):
                entry = caches[si][f"slot{j}"]
                if paging is not None and kind in self._ATTN_KINDS:
                    stage[f"slot{j}"] = entry  # pooled: nothing per-slot
                    continue
                one = self._empty_block_cache(kind, b, max_seq)
                empty = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (reps,) + t.shape), one
                )

                def clear(c, e):
                    m = reset.reshape((1, -1) + (1,) * (c.ndim - 2))
                    return jnp.where(m, e, c)

                stage[f"slot{j}"] = jax.tree.map(clear, entry, empty)
            out.append(stage)
        return out

    def prefill(self, params, batch, max_seq: int):
        """Run the full prompt, return (last_logits, caches padded to max_seq)."""
        c = self.cfg
        x = self._constrain(self._embed(params, batch))
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        rb = self._router_bias(params, batch, s)
        x, raw_caches, _ = self._run_stages(params, x, positions, rb, want_cache=True)

        def pad_attn(t):  # (P, B, S, ...) -> (P, B, max_seq, ...)
            pad = [(0, 0)] * t.ndim
            pad[2] = (0, max_seq - t.shape[2])
            return jnp.pad(t, pad)

        caches = []
        for si, pat in enumerate(self._stage_patterns()):
            stage = {}
            for j, kind in enumerate(pat):
                entry = raw_caches[si][f"slot{j}"]
                if kind in ("attn", "attn_moe", "shared_attn"):
                    entry = jax.tree.map(pad_attn, entry)
                stage[f"slot{j}"] = entry
            caches.append(stage)
        logits = self._logits(params, x[:, -1:, :], batch)
        return logits, caches

    @staticmethod
    def _cache_write(cache, new, pos, live=None):
        """Sharding-friendly cache write: masked select along the sequence
        dim instead of dynamic_update_slice — each shard writes locally, so
        sequence-sharded KV caches (flash-decode layout) never get gathered.
        cache: (B, S, ...), new: (B, 1, ...), pos: (B,) per-slot positions,
        live: optional (B,) bool — dead slots keep their cache untouched."""
        s = cache.shape[1]
        mask = jnp.arange(s)[None, :] == pos[:, None]  # (B, S)
        if live is not None:
            mask &= live[:, None]
        mask = mask.reshape(mask.shape + (1,) * (cache.ndim - 2))
        return jnp.where(mask, new.astype(cache.dtype), cache)

    @staticmethod
    def _cache_write_slab(cache, new, pos, valid):
        """Masked (B, C)-slab cache write at per-slot offsets — the chunk
        counterpart of ``_cache_write`` (same masked-select idiom, so
        sequence-sharded caches still write shard-locally). cache: (B, S,
        ...), new: (B, C, ...), pos: (B,) first-token positions (chunk token
        i lands at ``pos + i``), valid: (B, C) — invalid lanes write
        nothing."""
        s = cache.shape[1]
        c = new.shape[1]
        tgt = jnp.where(
            valid, pos[:, None] + jnp.arange(c)[None, :], -1
        )  # (B, C); -1 never matches a cache row
        onehot = jnp.arange(s)[None, :, None] == tgt[:, None, :]  # (B, S, C)
        hit = jnp.any(onehot, axis=2)  # (B, S)
        src = jnp.argmax(onehot, axis=2)  # (B, S) chunk index per cache row
        idx = src.reshape(src.shape + (1,) * (new.ndim - 2))
        # argmax over the (B, S, C) onehot is in [0, C-1] by construction
        val = jnp.take_along_axis(
            new, idx, axis=1, mode="promise_in_bounds"
        )  # (B, S, ...)
        mask = hit.reshape(hit.shape + (1,) * (cache.ndim - 2))
        return jnp.where(mask, val.astype(cache.dtype), cache)

    def _make_attend(self, pos, block_tables, kv_len):
        """Backend-dispatching GQA attention closure for one serving
        dispatch. The effective backend is resolved ONCE per trace through
        the fallback matrix (``repro.kernels.runtime.resolve_attn_backend``):
        "pallas" serves GQA from the flash kernels (dense or block-table
        paged — the paged kernels consume the pool + table directly, no
        gathered view); MLA configs resolve to "jnp" and never build this
        closure's pallas path. kv_len: (B,) keys each slot's valid queries
        may see (0 for none), which bounds the Pallas kernel's walk. All
        arguments are trace-time constants or traced arrays, so varying
        batch CONTENT never retraces."""
        c = self.cfg
        backend = resolve_attn_backend(c.attn_backend, mla=c.use_mla)
        return lambda q, kc, vc: attn_lib.cached_attend(
            q, kc, vc, pos, sliding_window=c.sliding_window,
            backend=backend, block_tables=block_tables, kv_len=kv_len,
            scale=c.attn_scale,
        )

    def _gather_adapters(self, adapters, task_ids):
        """Per-row multi-LoRA gather for one serving dispatch: pick each
        batch row's task adapters from the stacked serving tree (built by
        ``repro.serve.adapters.TaskAdapterStore.refresh``, leading axis
        num_tasks + 1 with a terminal zero null row for dead lanes). Stage
        leaves (T, P, ...) -> (P, B, ...) so they scan alongside the
        period-stacked params; task leaves (T, ...) -> (B, ...)."""
        # mode="clip", same rationale as _TAKE_MODE: dead lanes carry the
        # null id num_tasks (the tree's terminal zero row — in bounds), and
        # a corrupted id must clamp to SOME task's adapters rather than
        # NaN-fill through the shared MoE buffers (the PR 7 bug)
        stage_ad = [
            jax.tree.map(
                lambda t: jnp.moveaxis(
                    jnp.take(t, task_ids, axis=0, mode="clip"), 0, 1
                ),
                stage,
            )
            for stage in adapters["stages"]
        ]
        task_ad = jax.tree.map(
            lambda t: jnp.take(t, task_ids, axis=0, mode="clip"),
            adapters["task"],
        )
        return stage_ad, task_ad

    def _attn_block(
        self, kind, p, x, cache, pos, router_bias, moe_live, write, view,
        attend, ad=None,
    ):
        """Attention block body shared by decode (C == 1) and parallel
        prefill (C > 1): project the chunk, write its KV slab through
        ``write``, attend with per-query positions ``pos + i``, then
        MLP/MoE. GQA attends through ``attend(q, k_cache, v_cache)`` — the
        backend dispatcher (``attn_lib.cached_attend``) that picks the jnp
        masked-einsum path or the Pallas flash kernels and consumes raw
        caches (dense stripes OR paged pools). MLA always attends over the
        jnp ``view`` of the cache (the absorbed-matrix decode runs in the
        compressed latent space — see repro.kernels.runtime for the
        fallback matrix). x: (B, C, d); pos: (B,) first-token positions;
        moe_live: (B,) live or (B, C) valid mask — ``apply_moe`` accepts
        either."""
        c = self.cfg
        b, cl = x.shape[:2]
        q_pos = pos[:, None] + jnp.arange(cl)[None, :]  # (B, C)
        h = self._norm(x, p["norm1"])
        with jax.named_scope("attention"):
            if c.use_mla:
                c_cache, r_cache = cache
                c_kv = matmul(h, p["attn"]["w_dkv"])  # (B, C, r)
                k_rope = attn_lib.apply_rope(
                    matmul(h, p["attn"]["w_krope"])[:, :, None, :],
                    q_pos,
                    c.rope_theta,
                )[:, :, 0, :]
                c_cache = write(c_cache, c_kv)
                r_cache = write(r_cache, k_rope)
                out = attn_lib.mla_decode(
                    p["attn"], h, self._mla_dims(), view(c_cache),
                    view(r_cache), pos, c.rope_theta,
                )
                new_cache = (c_cache, r_cache)
            else:
                k_cache, v_cache = cache
                q, k, v = attn_lib.gqa_project(
                    p["attn"], h, c.num_heads, c.num_kv_heads, c.head_dim
                )
                q, k = self._rope(q, q_pos), self._rope(k, q_pos)
                k_cache = write(k_cache, k)
                v_cache = write(v_cache, v)
                o = attend(q, k_cache, v_cache)
                out = matmul(
                    o.reshape(b, cl, c.num_heads * c.head_dim), p["attn"]["wo"]
                )
                new_cache = (k_cache, v_cache)
        if ad is not None:
            # parallel per-task delta off the same normed input (h is still
            # the norm1 output on both the GQA and MLA paths)
            out = out + apply_task_lora(h, ad["attn"])
        x = self._residual(x, out)
        x, _ = self._ffn(kind, p, x, router_bias, moe_live, ad)
        return x, new_cache

    def _block_decode(
        self, kind, p, x, cache, pos, kv_len, router_bias, live=None,
        block_tables=None, ad=None,
    ):
        """pos: (B,) per-slot positions; kv_len: (B,) keys visible to each
        slot's query (0 for a dead slot); live: optional (B,) slot mask;
        block_tables: optional (B, max_blocks) — paged attention caches
        (cache entries are shared pools, writes scatter through the table,
        reads attend over the gathered per-slot view); ad: optional per-row
        adapter factors for this block (already gathered by task id)."""
        c = self.cfg
        if kind in self._ATTN_KINDS:
            if block_tables is None:
                write = lambda cc, new: self._cache_write(cc, new, pos, live)
                view = lambda cc: cc
            else:
                write = lambda cc, new: attn_lib.paged_cache_write(
                    cc, new, pos, block_tables, live
                )
                view = lambda cc: attn_lib.gather_pages(cc, block_tables)
            attend = self._make_attend(pos, block_tables, kv_len)
            return self._attn_block(
                kind, p, x, cache, pos, router_bias, live, write, view,
                attend, ad,
            )
        if kind == "mamba":
            def mix(h):
                return mamba_lib.mamba2_step(
                    p["mamba"], h, cache, d_state=c.ssm_state,
                    head_dim=c.ssm_head_dim, live=live, eps=c.norm_eps,
                )
        elif kind == "mlstm":
            def mix(h):
                return xlstm_lib.mlstm_step(
                    p["mlstm"], h, cache, n_heads=c.num_heads, live=live
                )
        elif kind == "slstm":
            def mix(h):
                return xlstm_lib.slstm_step(
                    p["slstm"], h, cache, n_heads=c.num_heads, live=live
                )
        else:
            raise ValueError(kind)
        return self._recurrent_block(kind, p, x, mix, ad)

    def _run_cached_stages(self, params, x, caches, block_fn,
                           stage_adapters=None):
        """Stage loop shared by ``decode_step`` and ``prefill_step``: scan
        (or unroll) the period-stacked params + cache entries, calling
        ``block_fn(kind, p, h, cache, ad)`` per block. stage_adapters:
        optional list (stage) of {slot: adapter leaves (P, B, ...)} already
        gathered per batch row — scanned alongside params so every period
        applies its own adapter slice in the SAME dispatch. Returns
        (x, new_caches)."""
        new_caches = []
        for si, pat in enumerate(self._stage_patterns()):
            slots = params["stages"][si]
            # {} has no leaves, so it rides through scan/unroll untouched
            ad_si = stage_adapters[si] if stage_adapters is not None else {}

            def body(carry, xs, pat=pat):
                h = carry
                slot_params, slot_caches, slot_ad = xs
                out_caches = {}
                for j, kind in enumerate(pat):
                    p = (
                        params["shared_attn"]
                        if kind == "shared_attn"
                        else slot_params.get(f"slot{j}")
                    )
                    h, nc = block_fn(
                        kind, p, h, slot_caches[f"slot{j}"],
                        slot_ad.get(f"slot{j}"),
                    )
                    out_caches[f"slot{j}"] = nc
                return h, out_caches

            if self.cfg.unroll:
                reps = jax.tree_util.tree_leaves(caches[si])[0].shape[0]
                outs = []
                for i in range(reps):
                    xs_i = jax.tree.map(
                        lambda t: t[i], (slots, caches[si], ad_si)
                    )
                    x, co = body(x, xs_i)
                    outs.append(co)
                stage_cache = jax.tree.map(lambda *ts: jnp.stack(ts), *outs)
            else:
                x, stage_cache = jax.lax.scan(
                    body, x, (slots, caches[si], ad_si)
                )
            new_caches.append(stage_cache)
        return x, new_caches

    def decode_step(self, params, batch, caches, pos, live=None,
                    block_tables=None, adapters=None):
        """One-token decode. batch: {'tokens': (B,1[,K]) [, task_ids, vlm...]}.

        pos: () shared position or (B,) PER-SLOT positions — the vectorized
        continuous-batching path advances every slot at its own depth in one
        dispatch. live: optional (B,) bool; dead slots run through the math
        (their lane is padding) but their KV/recurrent state is left
        untouched, so a freed slot can be re-admitted later.
        block_tables: optional (B, max_blocks) int32 — caches must then come
        from ``init_cache(..., paging=spec)`` (shared attention pools;
        recurrent states stay dense and ignore the table).
        GQA attention dispatches on ``cfg.attn_backend`` ("pallas" = flash
        decode kernels, dense or paged; MLA/recurrent layers always take
        the jnp path — see repro.kernels.runtime).
        adapters: optional graph-mixed serving tree from
        ``TaskAdapterStore.serving`` — per-row low-rank deltas gathered by
        ``batch['task_ids']`` (same traced-array pytree every tick, so
        swapping adapter VALUES never retraces).
        Returns (logits (B,1,[K,]V), new caches)."""
        x = self._constrain(self._embed(params, batch))
        b = x.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        kv_len = pos + 1 if live is None else jnp.where(live, pos + 1, 0)
        stage_ad = task_ad = None
        if adapters is not None:
            stage_ad, task_ad = self._gather_adapters(
                adapters, batch["task_ids"]
            )
        rb = self._router_bias(params, batch, 1, task_ad)
        x, new_caches = self._run_cached_stages(
            params, x, caches,
            lambda kind, p, h, cache, ad: self._block_decode(
                kind, p, h, cache, pos, kv_len, rb, live, block_tables, ad
            ),
            stage_ad,
        )
        logits = self._logits(params, x, batch, task_ad)
        return logits, new_caches

    def _block_prefill(
        self, kind, p, x, cache, pos, kv_len, valid, router_bias,
        block_tables=None, ad=None,
    ):
        """(B, C)-chunk counterpart of ``_block_decode``: all C tokens of the
        chunk are computed in parallel against the cache. pos: (B,) per-slot
        position of the chunk's FIRST token; kv_len: (B,) keys visible to
        the slot's valid queries (0 for none); valid: (B, C) real-token
        mask — rows must be contiguous prefixes (serving chunks are
        left-packed).
        Slots with an all-False row (mid-decode, not being prefilled) keep
        their KV rows and recurrent state exactly untouched."""
        c = self.cfg
        if kind in self._ATTN_KINDS:
            if block_tables is None:
                write = lambda cc, new: self._cache_write_slab(
                    cc, new, pos, valid
                )
                view = lambda cc: cc
            else:
                write = lambda cc, new: attn_lib.paged_cache_write_slab(
                    cc, new, pos, block_tables, valid
                )
                view = lambda cc: attn_lib.gather_pages(cc, block_tables)
            attend = self._make_attend(pos, block_tables, kv_len)
            return self._attn_block(
                kind, p, x, cache, pos, router_bias, valid, write, view,
                attend, ad,
            )
        if kind == "mamba":
            def mix(h):
                return mamba_lib.mamba2_full(
                    p["mamba"], h, d_state=c.ssm_state,
                    head_dim=c.ssm_head_dim, chunk=c.mamba_chunk, state=cache,
                    valid=valid, eps=c.norm_eps,
                )
        elif kind == "mlstm":
            # always the EXACT sequential cell, never mlstm_chunkwise even
            # under cfg.xlstm_parallel: serving prefill must continue decode
            # numerics bit-for-bit (the chunkwise reformulation reassociates
            # floats ~1e-4, enough to flip near-tied greedy argmax against
            # the decode/scan path); chunkwise stays a train/full-prefill
            # lever where there is no decode stream to stay consistent with
            def mix(h):
                return xlstm_lib.mlstm_full(
                    p["mlstm"], h, n_heads=c.num_heads, chunk=c.xlstm_chunk,
                    state=cache, valid=valid,
                )
        elif kind == "slstm":
            def mix(h):
                return xlstm_lib.slstm_full(
                    p["slstm"], h, n_heads=c.num_heads, chunk=c.xlstm_chunk,
                    state=cache, valid=valid,
                )
        else:
            raise ValueError(kind)
        return self._recurrent_block(kind, p, x, mix, ad)

    def prefill_step(self, params, batch, caches, positions, valid,
                     block_tables=None, adapters=None):
        """Multi-token prefill: ONE dispatch computes a whole (B, C) prompt
        chunk — all C tokens in parallel — against caches at per-slot
        offsets. batch: {'tokens': (B, C[, K]) [, task_ids, vlm extras]};
        positions: (B,) position of each slot's first chunk token; valid:
        (B, C) contiguous-prefix mask of real prompt tokens (all-False rows
        ride along untouched, exactly like ``live=False`` in
        ``decode_step``). Attention writes the chunk's KV slab first, then
        query i attends with the same ``kv_idx <= pos + i`` mask decode
        uses (via the chunked flash-prefill kernel when
        ``cfg.attn_backend == "pallas"``); recurrent layers run their
        full-sequence kernels with the slot's cached state threaded in.
        Returns (logits (B, 1, [K,] V)
        after each slot's LAST VALID token, new caches) — the lm head runs
        on one gathered hidden state per slot, not the whole chunk (only
        the last-valid logits are ever consumed; all-False rows yield
        garbage logits the caller masks). Same logits shape as
        ``decode_step``."""
        x = self._constrain(self._embed(params, batch))
        b, cl = x.shape[:2]
        pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (b,))
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=1)  # (B,)
        kv_len = jnp.where(n_valid > 0, pos + n_valid, 0)
        stage_ad = task_ad = None
        if adapters is not None:
            stage_ad, task_ad = self._gather_adapters(
                adapters, batch["task_ids"]
            )
        rb = self._router_bias(params, batch, cl, task_ad)
        x, new_caches = self._run_cached_stages(
            params, x, caches,
            lambda kind, p, h, cache, ad: self._block_prefill(
                kind, p, h, cache, pos, kv_len, valid, rb, block_tables, ad
            ),
            stage_ad,
        )
        # lm head over ONE hidden state per slot (its last valid token) —
        # the (B, C, V) logits slab would be C x the largest matmul in the
        # model for rows that are immediately discarded
        # max(n_valid - 1, 0) is in [0, C-1]: n_valid <= C by construction
        idx = jnp.maximum(n_valid - 1, 0)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None], axis=1, mode="promise_in_bounds"
        )  # (B,1,d)
        logits = self._logits(params, x_last, batch, task_ad)
        return logits, new_caches
