"""The system under test, built from a configuration file.

With the architecture modules (``bench/arch/``), the only code of the
benchmark that imports the program (``repro``). The architecture's module
maps the configuration onto the program's own config and the benchmark's
weights and adapter factors into the program's layouts; this module checks
the parameter layout against the program's, builds the batcher and, where
the configuration has one, the adapter store, and reads the batcher's
counters.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.lib import spec
from repro.core.graph import band_graph
from repro.models import TransformerLM
from repro.serve import ContinuousBatcher, PagingSpec, TaskAdapterStore

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# monotone counters the batcher keeps as attributes; read where it has no
# ``counters`` dict, which holds every counter by name
COUNTER_ATTRS = ("attn_blocks_walked", "attn_blocks_listed")


def num_tasks(cfg: dict) -> int:
    return spec.architecture(cfg["model_type"]).arch(cfg).num_tasks


def checked(model: TransformerLM, params):
    """``params`` if its structure, shapes and dtypes are what
    ``model.init`` makes, else ValueError."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    same = jax.tree.structure(want) == jax.tree.structure(params) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params))
    )
    if not same:
        raise ValueError("the benchmark's weights do not match the program's "
                         "parameter layout")
    return params


def counters(batcher) -> dict:
    """The batcher's monotone counters by name: its ``counters`` dict where
    it has one, else those of COUNTER_ATTRS it has; {} for neither."""
    c = getattr(batcher, "counters", None)
    if c is not None:
        return dict(c)
    return {k: getattr(batcher, k) for k in COUNTER_ATTRS
            if hasattr(batcher, k)}


@dataclasses.dataclass
class System:
    model: TransformerLM
    batcher: ContinuousBatcher
    store: TaskAdapterStore | None
    paging: PagingSpec


def build(cfg: dict, w: dict, raw: dict | None, on_token) -> System:
    dep = cfg["deployment"]
    mod = spec.architecture(cfg["model_type"])
    model = TransformerLM(mod.arch(cfg), dtype=DTYPES[dep["dtype"]])
    paging = PagingSpec.sized(dep["block_size"], dep["max_seq"],
                              dep["pool_tokens"])
    store = None
    if raw is not None:
        ad = cfg["adapters"]
        if ad["graph"]["kind"] != "band":
            raise ValueError(f"unknown task graph {ad['graph']['kind']!r}")
        graph = band_graph(ad["num_tasks"], ad["graph"]["bandwidth"])
        store = TaskAdapterStore(
            model, graph, rank=ad["rank"], mixing=ad["mixing"],
            eta=ad["eta"], tau=ad["tau"], alpha=ad["alpha"],
            update_every=ad["update_every"], dtype=model.dtype,
        )
        store.set_raw(mod.store_raw(raw))
    batcher = ContinuousBatcher(
        model, checked(model, mod.params(w)), num_slots=dep["num_slots"],
        max_seq=dep["max_seq"], prefill_chunk=dep["prefill_chunk"],
        paging=paging, policy=dep["policy"], chunk_budget=dep["chunk_budget"],
        on_token=on_token, adapters=store,
    )
    return System(model, batcher, store, paging)
