"""Per-task serving adapters, graph-mixed over the task-relatedness graph.

This is the paper's weighted neighbor averaging lifted into the serving
stack: every task (tenant) owns a stack of low-rank deltas — one
``(d, r) x (r, d)`` factor pair per transformer block branch plus the
per-task head biases — stored task-leading so the whole store is one pytree
of ``(num_tasks, ...)`` leaves. Between ticks the store re-mixes ALL leaves
with the graph's averaging weights ``mu`` (``TaskGraph.bsr_mixing`` /
``bol_mixing`` / ``consensus_mixing``) in one fused ``graph_mix_tree``
dispatch, then publishes a ``serving`` tree with a terminal ZERO null row
(index ``num_tasks``) that dead batcher lanes gather — the same reserved
null-resource pattern as paged attention's block 0.

The serving hot path never touches the store's internals: the batcher
passes ``store.serving`` (constant structure and shapes) into the jitted
step pair, where ``TransformerLM._gather_adapters`` picks each batch row's
factors by task id — multi-LoRA serving of a mixed-task batch in the same
O(1) dispatches per tick as single-task serving, with zero extra retraces.

Online adaptation follows ``repro.core.delayed`` (Appendix G, Theorem 7):
the store keeps a ring buffer of the last ``max_delay + 1`` stacked
iterates; each ``update()`` mixes STALE neighbor views (one bounded delay
per source task — see ``per_source_stale``) and takes a gradient step on
whatever per-task gradient signals finished requests pushed since the last
update. ``note_request`` is the batcher's finish hook: it counts retired
requests and runs ``update()`` every ``update_every`` finishes — host-side,
between ticks, never blocking a dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.delayed import per_source_stale
from repro.core.graph import TaskGraph
from repro.kernels.graph_mix import graph_mix_tree
from repro.models.model import TransformerLM

MIXINGS = ("bsr", "bol", "consensus")


def _mixing_matrix(graph: TaskGraph, mixing: str, eta: float, tau: float,
                   alpha: float) -> np.ndarray:
    if mixing == "bsr":
        return graph.bsr_mixing(eta, tau, alpha)
    if mixing == "bol":
        return graph.bol_mixing(eta, tau, alpha)
    if mixing == "consensus":
        return graph.consensus_mixing()
    raise ValueError(f"mixing must be one of {MIXINGS}, got {mixing!r}")


class TaskAdapterStore:
    """Graph-mixed stacked low-rank adapters for multi-task serving.

    Layout (all leaves task-leading, ``m = num_tasks``, ``P`` = periods of
    the stage, ``r`` = rank, ``d`` = d_model)::

        raw = {
          "stages": [ {  # one dict per model stage, mirrors params["stages"]
            "slot<j>": {"attn": {"a": (m,P,d,r), "b": (m,P,r,d)},
                        "mlp":  {"a": (m,P,d,r), "b": (m,P,r,d)}}   # attn kinds
                     | {"out":  {"a": (m,P,d,r), "b": (m,P,r,d)}}   # recurrent
          } ... ],
          "task": {"head_bias": (m, V_total)
                   [, "final_gain": (m, d)] [, "router_bias": (m, E)]},
        }

    ``serving`` is the graph-mixed copy with one extra ZERO row appended to
    every leaf — row ``null_task == num_tasks`` — gathered by dead lanes.
    """

    def __init__(
        self,
        model: TransformerLM,
        graph: TaskGraph,
        *,
        rank: int | None = None,
        mixing: str = "bsr",
        eta: float = 1.0,
        tau: float = 1.0,
        alpha: float = 1.0,
        lr: float = 0.01,
        max_delay: int = 0,
        fixed_delay: bool = False,
        update_every: int = 1,
        seed: int = 0,
        dtype=None,
    ):
        cfg = model.cfg
        if graph.m != cfg.num_tasks:
            raise ValueError(
                f"task graph has {graph.m} tasks but the model serves "
                f"num_tasks={cfg.num_tasks}"
            )
        rank = rank if rank is not None else cfg.adapter_rank
        if rank <= 0:
            raise ValueError(
                "adapter rank must be positive — pass rank= or set "
                "cfg.adapter_rank"
            )
        if max_delay < 0:
            raise ValueError(f"max_delay must be >= 0, got {max_delay}")
        if update_every <= 0:
            raise ValueError(f"update_every must be >= 1, got {update_every}")
        self.model = model
        self.graph = graph
        self.rank = rank
        self.mixing = mixing
        self.lr = lr
        self.max_delay = max_delay
        self.fixed_delay = fixed_delay
        self.update_every = update_every
        self.dtype = dtype if dtype is not None else model.dtype
        self.null_task = cfg.num_tasks
        self.mu = jnp.asarray(
            _mixing_matrix(graph, mixing, eta, tau, alpha), jnp.float32
        )
        self._rng = np.random.default_rng(seed)
        self.raw = self._zeros_raw()
        self._grads = jax.tree.map(jnp.zeros_like, self.raw)
        self._hist: list = [self.raw]  # newest first, len <= max_delay + 1
        self._finished = 0
        self.updates = 0
        self.serving = None
        self.refresh()

    # ------------------------------------------------------------ structure
    @property
    def num_tasks(self) -> int:
        return self.model.cfg.num_tasks

    def _zeros_raw(self):
        cfg = self.model.cfg
        m, r, d = cfg.num_tasks, self.rank, cfg.d_model

        def pair(reps):
            return {
                "a": jnp.zeros((m, reps, d, r), self.dtype),
                "b": jnp.zeros((m, reps, r, d), self.dtype),
            }

        stages = []
        for si, pat in enumerate(self.model._stage_patterns()):
            reps = cfg.num_periods if si == 0 and cfg.num_periods > 0 else 1
            slots = {}
            for j, kind in enumerate(pat):
                if kind in TransformerLM._ATTN_KINDS:
                    slots[f"slot{j}"] = {"attn": pair(reps), "mlp": pair(reps)}
                else:
                    slots[f"slot{j}"] = {"out": pair(reps)}
            stages.append(slots)
        v_total = cfg.vocab_size * cfg.num_codebooks
        task = {"head_bias": jnp.zeros((m, v_total), self.dtype)}
        if cfg.norm_kind != "nonparam_ln":
            task["final_gain"] = jnp.zeros((m, cfg.d_model), self.dtype)
        if cfg.uses_moe:
            task["router_bias"] = jnp.zeros((m, cfg.num_experts), self.dtype)
        return {"stages": stages, "task": task}

    def zeros_like_task(self):
        """A zero gradient/delta tree for ONE task (leaves without the
        leading task axis) — the shape ``push_grads`` expects."""
        return jax.tree.map(lambda t: jnp.zeros(t.shape[1:], t.dtype), self.raw)

    # -------------------------------------------------------------- content
    def set_raw(self, tree) -> None:
        """Replace the raw per-task parameters (tests / checkpoint load).
        Resets the delay history — the new iterate is the only one — and
        republishes ``serving``."""
        want = jax.tree.map(lambda t: (t.shape, jnp.dtype(t.dtype)), self.raw)
        got = jax.tree.map(
            lambda t: (jnp.shape(t), jnp.dtype(jnp.asarray(t).dtype)), tree
        )
        if want != got:
            raise ValueError(
                "set_raw: tree structure/shapes/dtypes must match the "
                "store's layout"
            )
        self.raw = jax.tree.map(jnp.asarray, tree)
        self._hist = [self.raw]
        self.refresh()

    def randomize(self, scale: float = 1e-2) -> None:
        """Fill the raw store with gaussian factors (benchmarks / tests that
        need NONZERO per-task adapters quickly)."""
        leaves, treedef = jax.tree_util.tree_flatten(self.raw)
        key = jax.random.PRNGKey(int(self._rng.integers(2**31)))
        ks = jax.random.split(key, len(leaves))
        self.set_raw(jax.tree_util.tree_unflatten(
            treedef,
            [
                (jax.random.normal(k, t.shape, jnp.float32) * scale).astype(
                    t.dtype
                )
                for k, t in zip(ks, leaves)
            ],
        ))

    def refresh(self) -> None:
        """Re-mix every leaf with ``mu`` (one fused kernel dispatch per
        dtype) and publish the serving tree with the appended zero null
        row. Structure and shapes never change, so swapping ``serving``
        between ticks never retraces the jitted steps."""
        mixed = graph_mix_tree(self.mu, self.raw)
        self.serving = jax.tree.map(
            lambda t: jnp.concatenate(
                [t, jnp.zeros((1,) + t.shape[1:], t.dtype)], axis=0
            ),
            mixed,
        )

    # ------------------------------------------------- delayed adaptation
    def push_grads(self, task_id: int, grads) -> None:
        """Accumulate a gradient signal for one task (tree shaped like
        ``zeros_like_task()``), consumed by the next ``update()``."""
        if not 0 <= task_id < self.num_tasks:
            raise ValueError(
                f"task_id {task_id} outside [0, {self.num_tasks})"
            )
        self._grads = jax.tree.map(
            lambda g_all, g: g_all.at[task_id].add(
                jnp.asarray(g, g_all.dtype)
            ),
            self._grads, grads,
        )

    def note_request(self, req) -> None:
        """Batcher finish hook: every ``update_every`` retired requests,
        run one delayed mixing+gradient update (host-side, between ticks)."""
        self._finished += 1
        if self._finished % self.update_every == 0:
            self.update()

    def update(self) -> None:
        """One delayed BOL-style update (core/delayed.py semantics):

        ``raw <- graph_mix(mu, stale) - lr * pending_grads``

        where ``stale`` picks each SOURCE task's iterate from the history
        ring at a bounded delay <= min(max_delay, len(hist) - 1) —
        resampled per update, or pinned to the bound with fixed_delay.
        Traced as the host span ``serve.adapters.update``."""
        with TraceAnnotation("serve.adapters.update"):
            m = self.num_tasks
            bound = min(self.max_delay, len(self._hist) - 1)
            if self.fixed_delay:
                delays = np.full(m, bound, np.int32)
            else:
                delays = self._rng.integers(0, bound + 1, size=m).astype(np.int32)
            if bound == 0:
                stale = self._hist[0]
            else:
                d = jnp.asarray(delays)
                stacked = jax.tree.map(
                    lambda *ts: jnp.stack(ts), *self._hist
                )  # (H, m, ...) leaves, newest first
                stale = jax.tree.map(lambda h: per_source_stale(h, d), stacked)
            new = graph_mix_tree(self.mu, stale)
            new = jax.tree.map(
                lambda t, g: t - self.lr * g.astype(t.dtype), new, self._grads
            )
            self._grads = jax.tree.map(jnp.zeros_like, self._grads)
            self.raw = new
            self._hist = [new] + self._hist[: self.max_delay]
            self.updates += 1
            self.refresh()
