"""Serving executor: wires scheduler decisions into the jitted step pair.

``ContinuousBatcher`` is the EXECUTOR layer of the serving core (see
``docs/serving.md`` for the full picture):

  * ``repro.serve.slots.SlotMap``  — pure slot/position/live bookkeeping,
  * ``repro.serve.scheduler.Scheduler`` — queue, admission policies
    (fifo/sjf/priority), the Sarathi-style per-tick prefill token budget,
    deadlines and cancellation decisions,
  * this module — the only layer that touches device state: the cache
    pytree, the ``BlockAllocator`` + block tables (paged mode), and the two
    jitted callables from ``repro.serve.step``.

Two execution regimes, selected by ``chunk_budget``:

  * ``chunk_budget=None`` (default) — admission prefills whole prompts
    immediately (chunked (num_slots, C) dispatches), then one jitted decode
    dispatch per tick advances every live slot. With ``policy="fifo"`` this
    is token-for-token the pre-scheduler behavior: the refactor's parity
    oracle, pinned by the serving tests and benchmark.
  * ``chunk_budget=N`` — SLA mode: every tick issues ONE fused prefill
    dispatch in which decoding slots advance one token each AND mid-prompt
    slots prefill at most N prompt tokens (policy-ordered), all in the same
    (num_slots, C) slab under per-row validity masks. A long prompt can no
    longer stall decoding slots for its whole prefill (head-of-line
    blocking): each tick bounds prefill work by N. ``model.prefill_step``
    with a single valid token is numerically the decode step (pinned by the
    chunk-width-invariance parity tests), so only latency changes, never
    tokens.

Emission hooks: ``on_token(request, token)`` streams every generated token
the tick it is produced; ``sample_fn(request, logits_row)`` replaces greedy
argmax (``ServeEngine`` uses it for temperature sampling keyed by request
id). Requests can be cancelled mid-flight (``cancel(uid)``) or expire via
``Request.timeout_s`` — both free the slot and its paged blocks
immediately and are returned in ``finished`` with ``cancelled`` /
``timed_out`` set and ``done`` False.

Paged mode (pass a ``repro.serve.paging.PagingSpec``): admission reserves
``ceil((len(prompt) + max_new) / block_size)`` blocks for the request
lifetime (allocator backpressure queues requests that cannot get them) and
every retirement path — finish, cancel, timeout — returns them.

``prefix_cache=True`` (paged, attention-only models) puts a
``repro.serve.paging.RadixPrefixCache`` in front of admission: a request
whose prompt shares a cached prefix aliases those blocks (refcounted)
instead of recomputing them, prefill starts at ``cached_tokens``, a
partially-shared boundary block is copy-on-written in one fused dispatch
(``serve.step.make_cow_copy``), and retirement decrefs instead of freeing
— fully prefilled prompt blocks stay resident (LRU-evicted lazily) for
future hits. Greedy outputs are token-for-token identical to the
no-sharing path: registered blocks hold final KV values for exactly the
positions the masked attention reads. See ``docs/serving.md``.

``decode_dispatches`` / ``prefill_dispatches`` / ``mixed_dispatches`` /
``ticks`` count real jitted calls so tests and
``benchmarks/serve_throughput.py`` can assert the O(1)-dispatch property
in both regimes. In paged mode on the Pallas backend,
``attn_blocks_walked`` / ``attn_blocks_listed`` sum over dispatches the
table blocks one layer's attention kernel walks (``cdiv(kv_len,
block_size)`` a slot, ``kv_len`` being the keys its valid queries may see)
and the table entries it holds (``num_slots x max_blocks``); a scan-mode
prefill counts its C kernel calls. Their ratio is how often the kernel's
skip of dead entries engages.

Tracing: each phase of ``step()`` is a ``jax.profiler.TraceAnnotation``
span on the profiler's clock, about a microsecond each when no profiler
runs. ``serve.step`` holds ``serve.retire``, ``serve.admit`` (admission,
binding, block allocation), then per dispatch ``serve.assemble`` (host
slab and uploads), ``serve.dispatch`` (enqueue), ``serve.device_wait``,
``serve.fetch`` (device-to-host copy) and ``serve.emit``, and last
``serve.finish``, which holds the store's ``serve.adapters.update``.

Fault tolerance (see ``docs/serving.md`` "Fault tolerance & graceful
degradation"): ``faults=`` injects a seeded ``repro.serve.faults.FaultPlan``
at named seams (allocator exhaustion, dispatch exceptions, NaN lanes,
adapter failures, clock skew — every seam a no-op when ``faults=None``);
``preempt=True`` (paged mode) swaps a running victim's blocks to host
memory under block pressure instead of refusing admission, requeuing the
victim for later restoration; lane quarantine turns a non-finite logits
row into a terminal ``Request.failed`` for THAT request only; transient
faults retry with bounded backoff (``max_retries``) and exhaustion goes
terminal-failed — ``run()`` never raises anything but the documented
``TickBudgetExceeded``. ``check_invariants()`` reconciles allocator
refcounts against slot tables, trie chains, and the queue at any point.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.runtime import resolve_attn_backend
from repro.models.model import TransformerLM
from repro.serve.faults import FaultError, FaultPlan
from repro.serve.paging import BlockAllocator, PagingSpec, RadixPrefixCache
from repro.serve.scheduler import Scheduler
from repro.serve.slots import SlotMap
from repro.serve.step import make_cow_copy, make_serve_step, make_swap


class TickBudgetExceeded(RuntimeError):
    """``run(max_ticks)`` spent its budget with requests still unfinished.

    The unfinished requests are flagged ``timed_out`` and remain queued /
    in-flight; pass ``on_exhausted="flag"`` to get partial results back
    instead of this exception."""


@dataclasses.dataclass
class Request:
    uid: int
    tokens: np.ndarray  # (S0,) prompt — or (S0, K) for audio codebooks
    max_new: int
    task_id: int = 0
    # per-request model extras, aligned with the prompt: VLM requests carry
    # {"vision_embeds": (S0, d_model) float32, "vision_mask": (S0,) bool}.
    # None means a pure-text prompt (zero embeds, False mask).
    extras: dict | None = None
    # scheduling: lower priority value runs first under policy="priority"
    # (nice-style); timeout_s expires the request that many seconds after
    # submit() — queued OR mid-flight — freeing its slot and paged blocks.
    priority: int = 0
    timeout_s: float | None = None
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # finished before emitting max_new tokens (slot capacity hit). submit()
    # validates len(prompt) + max_new against capacity, so this stays False
    # for every request admitted through the public API — it exists so a
    # capacity-clipped finish can never again masquerade as a completed one.
    truncated: bool = False
    # retirement flags: cancel(uid) / deadline expiry / run() tick-budget
    # exhaustion. A flagged request is NEVER done — callers cannot mistake
    # a truncated run for completion.
    cancelled: bool = False
    timed_out: bool = False
    # terminal failure: lane quarantine (non-finite logits) or transient-
    # fault retry exhaustion. ``error`` carries the reason. A failed
    # request is NEVER done — exactly like the other retirement flags.
    failed: bool = False
    error: str | None = None
    # bounded-retry bookkeeping: transient injected faults requeue with a
    # deadline-aware backoff; ``not_before`` gates re-admission.
    retries: int = 0
    not_before: float = 0.0
    # preemptive swap-out: times this request was swapped out, and (while
    # preempted) the host-side snapshot {"kv": pytree, "pos": int} that
    # re-admission restores through one donated scatter.
    preemptions: int = 0
    _swap: dict | None = None
    # bookkeeping stamped by the scheduler/executor; the three times are
    # on the scheduler's clock, and a preempted, re-admitted request keeps
    # its first admit_time as it keeps submit_time
    submit_time: float | None = None
    admit_time: float | None = None  # first bound to a slot
    first_token_time: float | None = None  # first generated token emitted
    prompt_done: int = 0  # prompt tokens already written to the cache
    cached_tokens: int = 0  # prompt tokens served from the prefix cache
    _arrival: int = 0

    @property
    def prefill_remaining(self) -> int:
        return len(self.tokens) - self.prompt_done


class ContinuousBatcher:
    """Slot-based continuous batching executor (one dispatch per tick)."""

    def __init__(
        self,
        model: TransformerLM,
        params,
        num_slots: int,
        max_seq: int,
        prefill_chunk: int = 16,
        paging: PagingSpec | None = None,
        prefix_cache: bool = False,
        prefill_mode: str = "parallel",
        policy: str = "fifo",
        chunk_budget: int | None = None,
        scheduler: Scheduler | None = None,
        now_fn=None,
        on_token=None,
        sample_fn=None,
        adapters=None,
        faults: FaultPlan | None = None,
        preempt: bool = False,
        quarantine: bool | None = None,
        max_retries: int = 3,
        retry_backoff_s: float = 0.0,
    ):
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        self.paging = paging
        self.prefill_mode = prefill_mode
        self.on_token = on_token
        self.sample_fn = sample_fn
        if adapters is not None and adapters.num_tasks != model.cfg.num_tasks:
            raise ValueError(
                f"adapter store serves {adapters.num_tasks} tasks but the "
                f"model has num_tasks={model.cfg.num_tasks}"
            )
        self.adapters = adapters
        # dead/free lanes gather this id: the serving tree's reserved zero
        # null row (index num_tasks) — exact-zero adapters, and for the
        # params["task"] takes an out-of-range id jnp.take clamps to the
        # last task, whose gathered rows only feed discarded dead-lane
        # outputs
        self._null_task = model.cfg.num_tasks
        self.scheduler = scheduler if scheduler is not None else Scheduler(
            policy=policy, chunk_budget=chunk_budget, now_fn=now_fn
        )
        self.slots = SlotMap(num_slots)
        if paging is not None:
            # a slot's logical length is bounded by BOTH max_seq and its
            # block-table capacity
            self.slot_capacity = min(max_seq, paging.tokens_per_slot)
            self.allocator = BlockAllocator(paging)
            self.block_tables = np.zeros(
                (num_slots, paging.max_blocks_per_slot), np.int32
            )
            self.slot_blocks: list[list[int]] = [[] for _ in range(num_slots)]
        else:
            self.slot_capacity = max_seq
        self.prefix = None
        self._cow_fn = None
        if prefix_cache:
            if paging is None:
                raise ValueError(
                    "prefix_cache=True requires a paged cache layout "
                    "(pass a PagingSpec) — dense per-slot stripes cannot "
                    "alias blocks between slots"
                )
            kinds = set(model.cfg.pattern)
            recurrent = kinds - set(TransformerLM._ATTN_KINDS)
            if recurrent:
                # a recurrent layer's state at position p depends on ALL
                # positions <= p and lives outside the paged KV pools, so
                # aliasing KV blocks would resume from a stale/foreign state
                raise ValueError(
                    f"prefix_cache=True requires an attention-only model; "
                    f"layer kinds {sorted(recurrent)} carry recurrent state "
                    "the KV blocks do not capture"
                )
            self.prefix = RadixPrefixCache(self.allocator)
            self._cow_fn = make_cow_copy(paging)
            if self.scheduler.cost_fn is None:
                # sjf should order by UNCACHED prompt tokens — a long
                # prompt with a resident prefix is a short job
                self.scheduler.cost_fn = lambda r: (
                    len(r.tokens) - self.prefix.match(r.task_id, r.tokens).tokens
                )
        # ---- fault tolerance & graceful degradation (docs/serving.md) ----
        self.faults = faults
        # quarantine defaults on exactly when a fault plan is present: the
        # finiteness check needs host logits every tick, which the greedy
        # fast path otherwise never materializes (faults=None stays
        # zero-overhead; pass quarantine=True to run it standalone).
        self.quarantine = (faults is not None) if quarantine is None else quarantine
        self.preempt = preempt
        if preempt and paging is None:
            raise ValueError(
                "preempt=True requires a paged cache layout (a PagingSpec): "
                "dense per-slot stripes hold no blocks to swap out"
            )
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self._swap_out_fn = self._swap_in_fn = None
        if preempt:
            self._swap_out_fn, self._swap_in_fn = make_swap(paging)
        if faults is not None:
            # clock-skew seam: every deadline decision the scheduler makes
            # sees the plan's skewed time (timeout storms)
            base_now = self.scheduler._now
            self.scheduler._now = lambda: base_now() + faults.skew()
        self.caches = model.init_cache(num_slots, max_seq, paging)
        self.finished: list[Request] = []
        self.ticks = 0
        self.decode_dispatches = 0
        self.prefill_dispatches = 0
        self.mixed_dispatches = 0  # fused prefill+decode (chunk_budget mode)
        self.cow_copies = 0  # copy-on-write dispatches (prefix-cache mode)
        self.prefill_tokens = 0  # prompt tokens actually computed
        self.swap_outs = 0  # preemptive swap-out dispatches
        self.swap_ins = 0  # swap-in (restore) dispatches
        self.quarantined = 0  # requests failed by the finiteness check
        self.dispatch_faults = 0  # injected dispatch failures absorbed
        self.adapter_faults = 0  # injected adapter-update failures absorbed
        self.retire_faults = 0  # injected mid-retirement failures absorbed
        # the paged kernel's walk (module docstring); counted only where
        # the kernel runs
        self.attn_blocks_walked = 0
        self.attn_blocks_listed = 0
        self._counts_walk = paging is not None and resolve_attn_backend(
            model.cfg.attn_backend, mla=model.cfg.use_mla
        ) == "pallas"
        self._consec_dispatch_faults = 0
        self._stalled_steps = 0  # no-progress rounds (count against run())
        self._pending_prefill: set[int] = set()  # gulp resume after a fault
        self._needs_reset: set[int] = set()  # fresh slots awaiting reset
        self._just_restored: set[int] = set()
        self._tick_fn, self._prefill_fn = make_serve_step(
            model, max_seq, paging, prefill_mode
        )

    # --------------------------------------------------- bookkeeping views
    # (the structures live in the scheduler/slot-map layers; these views
    # keep the executor's public surface stable)
    @property
    def queue(self) -> list[Request]:
        return self.scheduler.queue

    @property
    def active(self) -> list[Request | None]:
        return self.slots.reqs

    @property
    def pos(self) -> np.ndarray:
        return self.slots.pos

    # ------------------------------------------------------------- plumbing
    def submit(self, req: Request):
        """Validate a request BEFORE it can occupy a slot.

        Rejects (a) empty prompts — prefill would emit no logits and the
        first "generated" token would silently be argmax(0) == token 0 —
        and (b) requests whose prompt + max_new budget cannot fit a slot,
        which would otherwise finish early at the capacity guard with no
        signal (silent truncation)."""
        n = len(req.tokens)
        if n == 0:
            raise ValueError(
                f"request {req.uid}: empty prompt — at least one prompt "
                "token is required to produce the first logits"
            )
        if not 0 <= req.task_id < self.model.cfg.num_tasks:
            # jnp.take clamps out-of-range indices under jit, so an invalid
            # id would silently serve the FIRST/LAST task's parameters —
            # reject at admission instead
            raise ValueError(
                f"request {req.uid}: task_id {req.task_id} outside "
                f"[0, {self.model.cfg.num_tasks}) — out-of-range ids would "
                "silently clamp to another task's parameters"
            )
        total = n + req.max_new
        if total > self.slot_capacity:
            detail = (
                f"max_seq={self.max_seq}"
                if self.paging is None
                else f"min(max_seq={self.max_seq}, "
                f"{self.paging.max_blocks_per_slot} blocks x "
                f"{self.paging.block_size})"
            )
            raise ValueError(
                f"request {req.uid}: prompt ({n}) + max_new ({req.max_new}) "
                f"= {total} tokens exceeds the per-slot capacity "
                f"{self.slot_capacity} ({detail}); it would be silently "
                "truncated"
            )
        if self.paging is not None:
            needed = self.paging.blocks_for(total)
            if needed > self.paging.num_blocks - 1:
                raise ValueError(
                    f"request {req.uid}: needs {needed} KV blocks but the "
                    f"pool only has {self.paging.num_blocks - 1} allocatable "
                    "blocks — it could never be admitted"
                )
        self._validate_extras(req, n)
        self.scheduler.submit(req)

    def _validate_extras(self, req: Request, n: int):
        """Per-request extras must be usable by the prefill dispatch.

        VLM (pixtral-style) inputs used to be dropped silently: admission
        always dispatched ``extras={}``, so every vision token prefilled
        with zero embeds and generation quietly degraded to text-only.
        Extras are now wired through admission — but only shapes the model
        can consume are accepted, and extras on a non-VLM model are an
        error, not a no-op."""
        cfg = self.model.cfg
        if req.extras is None:
            return
        if cfg.input_mode != "vlm":
            raise ValueError(
                f"request {req.uid}: extras are only supported for "
                f"input_mode='vlm' models, not {cfg.input_mode!r}"
            )
        missing = {"vision_embeds", "vision_mask"} - set(req.extras)
        if missing:
            raise ValueError(
                f"request {req.uid}: vlm extras must carry "
                f"'vision_embeds' and 'vision_mask' (missing {sorted(missing)})"
            )
        emb = np.asarray(req.extras["vision_embeds"])
        msk = np.asarray(req.extras["vision_mask"])
        if emb.shape != (n, cfg.d_model) or msk.shape != (n,):
            raise ValueError(
                f"request {req.uid}: vlm extras must be aligned with the "
                f"prompt — want vision_embeds ({n}, {cfg.d_model}) and "
                f"vision_mask ({n},), got {emb.shape} and {msk.shape}"
            )

    def _block_tables(self):
        return (
            jnp.asarray(self.block_tables) if self.paging is not None else None
        )

    def _adapter_tree(self):
        """The graph-mixed serving tree for this tick (constant structure
        and shapes, so value swaps between ticks never retrace); None
        (empty pytree) without a store — the jitted signature is shared."""
        return self.adapters.serving if self.adapters is not None else None

    def _free_slot_blocks(self, s: int):
        if self.paging is not None and self.slot_blocks[s]:
            if self.prefix is not None:
                # decref, not free: blocks registered in the prefix trie
                # stay resident (cached-idle, LRU-evictable) for future
                # hits; unregistered ones return to the free list
                self.prefix.release(self.slot_blocks[s])
            else:
                self.allocator.free(self.slot_blocks[s])
            self.slot_blocks[s] = []
            self.block_tables[s, :] = 0

    def _register_prefix(self, s: int, req: Request):
        """Insert a COMPLETELY prefilled prompt's full blocks into the
        prefix trie (only final KV values are ever aliasable)."""
        if self.prefix is not None and req.prefill_remaining == 0:
            self.prefix.insert(req.task_id, req.tokens, self.slot_blocks[s])

    def _set_table(self, s: int, blocks: list[int]) -> None:
        self.slot_blocks[s] = list(blocks)
        self.block_tables[s, :] = 0
        self.block_tables[s, : len(blocks)] = blocks

    def _try_bind(self, s: int, req: Request) -> bool:
        """Scheduler placement callback: reserve the request's blocks for
        its whole lifetime and bind the slot — or report backpressure.
        Under ``preempt=True``, block pressure first tries to swap out a
        strictly-lower-priority running victim instead of refusing. A
        transient ``FaultError`` on any admission dispatch (COW, swap-in)
        unwinds every reference the attempt acquired and requeues the
        request with bounded retry — never a leak, never a crash."""
        if self.paging is None:
            self.slots.bind(s, req)
            return True
        needed = self.paging.blocks_for(len(req.tokens) + req.max_new)
        if self.faults is not None and self.faults.fires("alloc", uid=req.uid):
            # simulated allocator exhaustion: indistinguishable from real
            # backpressure downstream (admission stops for the round)
            return False
        try:
            if req._swap is not None:
                return self._bind_restore(s, req, needed)
            if self.prefix is not None:
                if self.faults is not None and self.faults.fires(
                    "incref", uid=req.uid
                ):
                    return False
                return self._bind_prefix(s, req, needed)
            if not self.allocator.can_alloc(needed):
                if not self._preempt_for(req, needed):
                    return False  # wait for finishing requests' blocks
            blocks = self.allocator.alloc(needed)
        except FaultError as e:
            self._note_retry(req, str(e))
            return False
        self._set_table(s, blocks)
        self.slots.bind(s, req)
        return True

    def _bind_prefix(self, s: int, req: Request, needed: int) -> bool:
        """Prefix-cache admission: alias the cached chain, COW the
        partially-shared boundary block, bind at ``cached_tokens``."""
        admit = self.prefix.admit(req.task_id, req.tokens, needed)
        if admit is None and self._preempt_for(req, needed):
            admit = self.prefix.admit(req.task_id, req.tokens, needed)
        if admit is None:
            return False  # truly out of live + unreclaimable memory
        blocks = list(admit.blocks)
        if admit.cow is not None:
            # the boundary block is only partially shared: copy the shared
            # rows into the slot's private block in ONE fused dispatch.
            # The source stays PINNED (increfed) across the dispatch; the
            # finally clause drops the pin on success AND failure, and a
            # failure additionally unwinds the chain + fresh references —
            # an exception between incref and release can no longer leak
            # refcounts (regression-tested with an injected dispatch
            # fault).
            src, dst, rows = admit.cow
            ok = False
            try:
                if self.faults is not None and self.faults.fires(
                    "dispatch", uid=req.uid, where="cow"
                ):
                    raise FaultError("injected copy-on-write dispatch failure")
                self.caches = self._cow_fn(
                    self.caches,
                    jnp.asarray(src, jnp.int32),
                    jnp.asarray(dst, jnp.int32),
                    jnp.asarray(rows, jnp.int32),
                )
                self.cow_copies += 1
                ok = True
            finally:
                self.prefix.release([src])
                if not ok:
                    self.prefix.release(blocks)
        self._set_table(s, blocks)
        # prefill resumes after the cached prefix
        req.prompt_done = admit.cached_tokens
        req.cached_tokens = admit.cached_tokens
        self.slots.bind(s, req, pos=admit.cached_tokens)
        return True

    def _bind_restore(self, s: int, req: Request, needed: int) -> bool:
        """Re-admit a preempted request: fresh blocks + ONE donated scatter
        restoring its saved pages. The prefix trie is bypassed on purpose —
        the snapshot holds mid-generation KV that must stay private, so
        restored blocks never alias cached chains (and the scatter never
        writes into one)."""
        if self.prefix is not None:
            if not self.prefix.can_alloc(needed):
                if not self._preempt_for(req, needed):
                    return False
            blocks = self.prefix.alloc(needed)
        else:
            if not self.allocator.can_alloc(needed):
                if not self._preempt_for(req, needed):
                    return False
            blocks = self.allocator.alloc(needed)
        try:
            if self.faults is not None and self.faults.fires(
                "dispatch", uid=req.uid, where="swap"
            ):
                raise FaultError("injected swap-in dispatch failure")
            self.caches = self._swap_in_fn(
                self.caches,
                jnp.asarray(self._padded_row(blocks)),
                jnp.asarray(s, jnp.int32),
                jax.tree.map(jnp.asarray, req._swap["kv"]),
            )
        except FaultError:
            # unwind the fresh blocks; the host snapshot stays on the
            # request, so a later retry restores from it unchanged
            if self.prefix is not None:
                self.prefix.release(blocks)
            else:
                self.allocator.free(blocks)
            raise
        self.swap_ins += 1
        self._set_table(s, blocks)
        self.slots.bind(s, req, pos=req._swap["pos"])
        req._swap = None
        self._just_restored.add(s)
        return True

    def _count_walk(self, valid: np.ndarray) -> None:
        """Add one dispatch's kernel walk to the counters, from the host's
        positions and the (B, C) valid mask (a contiguous prefix per row).
        The scan prefill runs one decode kernel per chunk column i, in
        which a slot valid there sees ``pos + i + 1`` keys."""
        if not self._counts_walk:
            return
        if self.prefill_mode == "scan":
            cols = np.arange(1, valid.shape[1] + 1)
            kv_len = np.where(valid, self.pos[:, None] + cols, 0)
        else:
            n = valid.sum(axis=1)
            kv_len = np.where(n > 0, self.pos + n, 0)
        bs = self.paging.block_size
        self.attn_blocks_walked += int((-(-kv_len // bs)).sum())
        self.attn_blocks_listed += kv_len.size * self.block_tables.shape[1]

    def _padded_row(self, blocks: list[int]) -> np.ndarray:
        """A slot's table row at full ``max_blocks_per_slot`` width, padded
        with the null block 0 — the fixed shape the swap pair is traced
        with."""
        row = np.zeros(self.paging.max_blocks_per_slot, np.int32)
        row[: len(blocks)] = blocks
        return row

    # --------------------------------------------- preemptive swap-out
    def _blocks_available(self, n: int) -> bool:
        if self.prefix is not None:
            return self.prefix.can_alloc(n)
        return self.allocator.can_alloc(n)

    def _pick_victim(self, req: Request):
        """Victim policy: among running slots whose priority value is
        STRICTLY greater than the incoming request's (nice-style: they
        matter strictly less), pick the lowest-priority one, breaking ties
        by most blocks held, then latest arrival. Strict dominance means a
        restored request can never be re-preempted by the one it yielded
        to — no livelock cycles. Only slots past prefill with at least one
        emitted token are preemptable (a mid-prefill snapshot would save
        half-written pages)."""
        candidates = [
            (s, r) for s, r in self.slots.live_items()
            if r.priority > req.priority
            and r.prefill_remaining == 0
            and r.out
        ]
        if not candidates:
            return None
        return max(
            candidates,
            key=lambda sr: (
                sr[1].priority,
                len(self.slot_blocks[sr[0]]),
                sr[1]._arrival,
            ),
        )

    def _preempt_for(self, req: Request, needed: int) -> bool:
        """Swap out victims until ``needed`` blocks are coverable (or no
        dominated victim remains). Returns whether pressure was relieved."""
        if not self.preempt:
            return False
        while not self._blocks_available(needed):
            victim = self._pick_victim(req)
            if victim is None:
                return False
            vs, vreq = victim
            try:
                self._swap_out_slot(vs, vreq)
            except FaultError:
                # swap-out fault: the victim keeps running untouched (the
                # fault fired before the gather); give up on preemption
                # this round
                self.dispatch_faults += 1
                return False
        return True

    def _swap_out_slot(self, s: int, req: Request) -> None:
        """ONE fused gather of the slot's pages (and dense per-slot state)
        to host memory, then free the blocks and requeue the request at
        its original arrival position. Restoration goes through the
        normal admission path (``_bind_restore``)."""
        if self.faults is not None and self.faults.fires(
            "dispatch", uid=req.uid, where="swap"
        ):
            raise FaultError("injected swap-out dispatch failure")
        saved = self._swap_out_fn(
            self.caches,
            jnp.asarray(self._padded_row(self.slot_blocks[s])),
            jnp.asarray(s, jnp.int32),
        )
        req._swap = {
            "kv": jax.tree.map(np.asarray, saved),
            "pos": int(self.pos[s]),
        }
        req.preemptions += 1
        self.swap_outs += 1
        self._free_slot_blocks(s)
        self.slots.release(s)
        self.scheduler.requeue(req)

    # ----------------------------------------------------- bounded retry
    def _backoff_delay(self, req: Request) -> float:
        """Deadline-aware exponential backoff: doubles per retry but is
        capped at half the request's remaining deadline budget, so backoff
        can never itself expire the request."""
        if self.retry_backoff_s <= 0.0:
            return 0.0
        delay = self.retry_backoff_s * (2 ** (req.retries - 1))
        if req.timeout_s is not None and req.submit_time is not None:
            remaining = (
                req.submit_time + req.timeout_s - self.scheduler.now()
            )
            delay = min(delay, max(0.0, 0.5 * remaining))
        return delay

    def _note_retry(self, req: Request, error: str) -> None:
        """Bounded retry for a transient admission fault: requeue with
        backoff, or — once ``max_retries`` is exhausted — retire the
        request terminally failed. Never an uncaught crash."""
        req.retries += 1
        if req.retries > self.max_retries:
            self.scheduler.cancel(req.uid)  # drop from the queue if queued
            s = self.slots.slot_of(req.uid)
            if s is not None:
                self._free_slot_blocks(s)
                self.slots.release(s)
            req.failed = True
            req.error = (
                f"{error} (retries exhausted after {req.retries - 1})"
            )
            self.finished.append(req)
            return
        req.not_before = self.scheduler.now() + self._backoff_delay(req)

    # ------------------------------------------------------------- emission
    def _emit(self, req: Request, row=None, greedy=None):
        """Append one generated token (greedy argmax, the decode dispatch's
        in-jit argmax, or the pluggable sampler) and stream it."""
        if self.sample_fn is not None:
            tok = self.sample_fn(req, row)
        elif greedy is not None:
            tok = greedy
        else:
            tok = np.argmax(row, axis=-1)
        tok = int(tok) if np.ndim(tok) == 0 else np.asarray(tok, np.int32)
        if not req.out:
            req.first_token_time = self.scheduler.now()
        req.out.append(tok)
        if self.on_token is not None:
            self.on_token(req, tok)

    def _finish_ready(self):
        for s, req in self.slots.live_items():
            # capacity guard: pos is the NEXT write position, so the slot is
            # exhausted only when pos == capacity (position capacity - 1 is
            # writable; the old `>= capacity - 1` guard wasted the last
            # token of every slot and truncated requests sized exactly to
            # capacity)
            if len(req.out) >= req.max_new or self.pos[s] >= self.slot_capacity:
                req.done = True
                # finished at the capacity guard, not by request completion
                req.truncated = len(req.out) < req.max_new
                self.finished.append(req)
                # free blocks BEFORE releasing the binding: an exception
                # between the two leaves the slot bound with its blocks —
                # consistent, reconcilable, retried next round. The other
                # order leaves an unbound slot still holding blocks, which
                # nothing ever frees.
                self._free_slot_blocks(s)
                self.slots.release(s)  # state cleared on re-admission
                if self.adapters is not None:
                    # stream the finish into the store's delayed-update
                    # loop (host-side, between ticks). An injected update
                    # failure drops THIS request's signal only; the store's
                    # cadence picks the next finish up unchanged.
                    try:
                        if self.faults is not None and self.faults.fires(
                            "adapter", uid=req.uid
                        ):
                            raise FaultError(
                                "injected adapter update failure"
                            )
                        self.adapters.note_request(req)
                    except FaultError:
                        self.adapter_faults += 1

    # --------------------------------------------------- retirement paths
    def cancel(self, uid) -> bool:
        """Cancel a request by uid, queued or mid-flight. Frees its slot
        and paged blocks immediately; the request lands in ``finished``
        with ``cancelled=True`` and never emits another token. Returns
        False if no such request is queued or in flight."""
        req = self.scheduler.cancel(uid)
        if req is None:
            s = self.slots.slot_of(uid)
            if s is None:
                return False
            req = self.slots.reqs[s]
            self._free_slot_blocks(s)  # blocks first (see _finish_ready)
            self.slots.release(s)
        req.cancelled = True
        self.finished.append(req)
        return True

    def _retire_expired(self):
        """Release requests past their ``timeout_s`` deadline — queued or
        mid-flight — freeing slots and paged blocks."""
        if not any(
            r.timeout_s is not None
            for r in self.scheduler.queue + self.slots.reqs
            if r is not None
        ):
            return
        dead_queued, dead_live = self.scheduler.expired(
            self.scheduler.now(), self.slots.live_items()
        )
        for req in dead_queued:
            req.timed_out = True
            self.finished.append(req)
        for s, req in dead_live:
            if self.faults is not None and self.faults.fires(
                "free", uid=req.uid
            ):
                # injected mid-retirement fault: skip THIS retirement —
                # the slot stays bound and its blocks stay held, so the
                # allocator remains reconcilable (check_invariants clean)
                # and the expiry simply retries next round
                self.retire_faults += 1
                continue
            self._free_slot_blocks(s)  # blocks first (see _finish_ready)
            self.slots.release(s)
            req.timed_out = True
            self.finished.append(req)

    # ------------------------------------------------- legacy (gulp) prefill
    def _admit(self):
        """Fill free slots in scheduler policy order, then (unchunked mode)
        prefill ALL newly admitted prompts together in chunked dispatches
        (whole (num_slots, C) slices per dispatch, per-token validity for
        unequal prompt lengths).

        Paged mode reserves each request's blocks here, for its whole
        lifetime; when the free list cannot cover the policy head,
        admission stops (backpressure) until finishing requests release
        blocks."""
        with TraceAnnotation("serve.admit"):
            self._just_restored = set()
            admitted = self.scheduler.admit(self.slots.free_slots(), self._try_bind)
            newly = [s for s, _ in admitted]
            # fresh prompts need their per-slot state reset on the first
            # prefill dispatch; restored (swapped-in) slots must NOT be reset —
            # their state was just scattered back in
            self._needs_reset |= set(newly) - self._just_restored
            # slots whose gulp a dispatch fault interrupted resume here
            resumed = sorted(self._pending_prefill)
            self._pending_prefill = set()
        if self.scheduler.chunk_budget is None and (newly or resumed):
            self._prefill_full(sorted(set(newly) | set(resumed)))
        return newly

    def _prefill_full(self, targets: list[int]):
        """The pre-scheduler admission gulp: run every target slot's prompt
        to completion, emitting each request's first generated token the
        dispatch its prefill completes.

        Each slot prefills from its own cursor (``prompt_done`` — 0 for a
        fresh prompt, ``cached_tokens`` after a prefix-cache hit), so the
        round costs ceil(max_uncached_len / C) dispatches: slots whose
        prefix is resident contribute only their uncached tail. Restored
        (swapped-in) slots ride along with nothing to prefill and nothing
        to emit. An injected dispatch fault aborts the round BEFORE the
        jitted call: the unfinished slots land in ``_pending_prefill`` and
        the next admission round resumes them from their cursors."""
        with TraceAnnotation("serve.assemble"):
            task_ids = jnp.asarray(self.slots.task_ids(self._null_task))
        c = self.prefill_chunk
        vlm = self.model.cfg.input_mode == "vlm"
        while True:
            pending = [
                s for s in targets
                if self.slots.reqs[s] is not None
                and self.slots.reqs[s].prefill_remaining > 0
            ]
            if not pending:
                break
            if self.faults is not None and self.faults.fires(
                "dispatch", where="prefill"
            ):
                self._pending_prefill = set(pending)
                raise FaultError("injected prefill dispatch failure")
            with TraceAnnotation("serve.assemble"):
                tokens = np.zeros((self.num_slots, c), np.int32)
                valid = np.zeros((self.num_slots, c), bool)
                reset = np.zeros(self.num_slots, bool)
                for s in pending:
                    if s in self._needs_reset:
                        reset[s] = True
                extras = {}
                if vlm:
                    emb = np.zeros((self.num_slots, c, self.model.cfg.d_model),
                                   np.float32)
                    msk = np.zeros((self.num_slots, c), bool)
                for s in pending:
                    req = self.slots.reqs[s]
                    d = req.prompt_done
                    t = np.asarray(req.tokens, np.int32)[d : d + c]
                    tokens[s, : len(t)] = t
                    valid[s, : len(t)] = True
                    if vlm and req.extras is not None:
                        emb[s, : len(t)] = np.asarray(
                            req.extras["vision_embeds"], np.float32
                        )[d : d + len(t)]
                        msk[s, : len(t)] = np.asarray(
                            req.extras["vision_mask"], bool
                        )[d : d + len(t)]
                if vlm:
                    extras = {
                        "vision_embeds": jnp.asarray(emb),
                        "vision_mask": jnp.asarray(msk),
                    }
                args = (
                    self.params, jnp.asarray(tokens), task_ids, self.caches,
                    jnp.asarray(self.pos), jnp.asarray(valid),
                    jnp.asarray(reset), extras, self._block_tables(),
                    self._adapter_tree(),
                )
                self._count_walk(valid)
            with TraceAnnotation("serve.dispatch"):
                last, self.caches, positions = self._prefill_fn(*args)
            self.prefill_dispatches += 1
            self.prefill_tokens += int(valid.sum())
            self._consec_dispatch_faults = 0
            self._needs_reset -= set(pending)
            positions, last_np = self._fetch(positions, last)
            self.slots.set_positions(positions)
            with TraceAnnotation("serve.emit"):
                completed = []
                for s in pending:
                    req = self.slots.reqs[s]
                    if req is None:  # cancelled from a streaming callback
                        continue
                    req.prompt_done += int(valid[s].sum())
                    if req.prefill_remaining == 0:
                        completed.append((s, req))
                # the logits after each prompt's LAST token are the first
                # generated token — emit them the dispatch they appear, exactly
                # like the engine's prefill. submit() rejects empty prompts and
                # prefix matching is capped at len(prompt) - 1, so every
                # completing slot computed at least one prompt token and has
                # real last-token logits here.
                if self.quarantine and completed:
                    self._quarantine_scan(
                        {s: last_np[s] for s, _ in completed}, completed
                    )
                for s, req in completed:
                    if self.slots.reqs[s] is not req:  # quarantined/cancelled
                        continue
                    self._register_prefix(s, req)
                    if not req.out:
                        self._emit(req, row=last_np[s])

    @staticmethod
    def _fetch(*outs):
        """End of every dispatch: wait for its outputs on the device, then
        copy them to the host."""
        with TraceAnnotation("serve.device_wait"):
            jax.block_until_ready(outs)
        with TraceAnnotation("serve.fetch"):
            return [np.asarray(o) for o in outs]

    def _quarantine_scan(self, rows: dict, items: list) -> None:
        """Lane quarantine: ONE vectorized host-side finiteness check over
        the logits this tick already materialized (zero extra dispatches).
        A non-finite row fails ONLY its own request — terminal
        ``Request.failed`` with the reason, blocks freed, slot released —
        while every other lane's token stream is untouched (the clean
        lanes' tokens come out of the same dispatch, poisoned or not).

        rows: {slot: logits row (np)}; items: [(slot, request)] emitting
        this tick. The ``nan`` fault seam poisons its scripted lanes here,
        simulating a kernel writing NaN into one lane's logits."""
        if not items:
            return
        if self.faults is not None:
            for s, req in items:
                if self.faults.fires("nan", slot=s, uid=req.uid):
                    rows[s] = np.full_like(rows[s], np.nan)
        order = [s for s, _ in items]
        mat = np.stack([rows[s] for s in order])
        finite = np.isfinite(mat).all(axis=tuple(range(1, mat.ndim)))
        for (s, req), ok in zip(items, finite):
            if ok:
                continue
            self.quarantined += 1
            self._free_slot_blocks(s)  # blocks first (see _finish_ready)
            self.slots.release(s)
            req.failed = True
            req.error = (
                f"non-finite logits at tick {self.ticks} (slot {s}) — "
                "lane quarantined"
            )
            self.finished.append(req)

    def tick(self):
        """Advance every live slot one token — exactly ONE jitted dispatch
        regardless of how many slots are live or at which positions."""
        live = self.slots.live()
        if not live.any():
            return
        if self.faults is not None and self.faults.fires(
            "dispatch", where="decode"
        ):
            raise FaultError("injected decode dispatch failure")
        with TraceAnnotation("serve.assemble"):
            cb = self.model.cfg.num_codebooks
            shape = (self.num_slots,) if cb <= 1 else (self.num_slots, cb)
            tokens = np.zeros(shape, np.int32)
            for s, req in self.slots.live_items():
                tokens[s] = (
                    req.out[-1] if req.out else np.asarray(req.tokens)[-1]
                )
            args = (
                self.params, jnp.asarray(tokens),
                jnp.asarray(self.slots.task_ids(self._null_task)),
                self.caches, jnp.asarray(self.pos), jnp.asarray(live),
                self._block_tables(), self._adapter_tree(),
            )
            self._count_walk(live[:, None])
        with TraceAnnotation("serve.dispatch"):
            next_tok, step_logits, self.caches = self._tick_fn(*args)
        self.ticks += 1
        self.decode_dispatches += 1
        self._consec_dispatch_faults = 0
        self.slots.advance_live()
        if self.sample_fn is not None or self.quarantine:
            next_np, logits_np = self._fetch(next_tok, step_logits)
        else:
            (next_np,) = self._fetch(next_tok)
            logits_np = None
        with TraceAnnotation("serve.emit"):
            if self.quarantine:
                items = self.slots.live_items()
                self._quarantine_scan({s: logits_np[s] for s, _ in items}, items)
            for s, req in self.slots.live_items():
                row = logits_np[s] if logits_np is not None else None
                self._emit(req, row=row, greedy=next_np[s])

    # ------------------------------------- SLA mode: fused prefill + decode
    def _interleaved_tick(self):
        """ONE fused dispatch: decoding slots advance one token AND
        mid-prompt slots prefill their scheduler-budgeted chunk, riding the
        same (num_slots, C) slab under per-row validity. Decode rows are a
        single-valid-token chunk, numerically the decode step."""
        prefilling = [
            (s, r, r.prefill_remaining)
            for s, r in self.slots.live_items()
            if r.prefill_remaining > 0
        ]
        decoding = [
            (s, r) for s, r in self.slots.live_items()
            if r.prefill_remaining == 0
        ]
        if not prefilling and not decoding:
            return
        if self.faults is not None and self.faults.fires(
            "dispatch", where="mixed"
        ):
            raise FaultError("injected mixed dispatch failure")
        c = self.prefill_chunk
        cfg = self.model.cfg
        cb = cfg.num_codebooks
        vlm = cfg.input_mode == "vlm"
        with TraceAnnotation("serve.assemble"):
            plan = self.scheduler.plan_prefill(prefilling, c)
            tok_shape = (
                (self.num_slots, c) if cb <= 1 else (self.num_slots, c, cb)
            )
            tokens = np.zeros(tok_shape, np.int32)
            valid = np.zeros((self.num_slots, c), bool)
            reset = np.zeros(self.num_slots, bool)
            if vlm:
                emb = np.zeros((self.num_slots, c, cfg.d_model), np.float32)
                msk = np.zeros((self.num_slots, c), bool)
            for s, n in plan:
                req = self.slots.reqs[s]
                d = req.prompt_done
                tokens[s, :n] = np.asarray(req.tokens, np.int32)[d : d + n]
                valid[s, :n] = True
                reset[s] = d == 0
                if vlm and req.extras is not None:
                    emb[s, :n] = np.asarray(
                        req.extras["vision_embeds"], np.float32
                    )[d : d + n]
                    msk[s, :n] = np.asarray(req.extras["vision_mask"], bool)[d : d + n]
            for s, req in decoding:
                tokens[s, 0] = (
                    req.out[-1] if req.out else np.asarray(req.tokens)[-1]
                )
                valid[s, 0] = True
            extras = {}
            if vlm:
                extras = {
                    "vision_embeds": jnp.asarray(emb),
                    "vision_mask": jnp.asarray(msk),
                }
            args = (
                self.params, jnp.asarray(tokens),
                jnp.asarray(self.slots.task_ids(self._null_task)),
                self.caches, jnp.asarray(self.pos), jnp.asarray(valid),
                jnp.asarray(reset), extras, self._block_tables(),
                self._adapter_tree(),
            )
            self._count_walk(valid)
        with TraceAnnotation("serve.dispatch"):
            last, self.caches, positions = self._prefill_fn(*args)
        self.ticks += 1
        self.mixed_dispatches += 1
        self.prefill_tokens += sum(n for _, n in plan)
        self._consec_dispatch_faults = 0
        positions, last_np = self._fetch(positions, last)
        self.slots.set_positions(positions)
        with TraceAnnotation("serve.emit"):
            completed = []
            for s, n in plan:
                req = self.slots.reqs[s]
                if req is None:  # cancelled from a streaming callback mid-round
                    continue
                req.prompt_done += n
                if req.prefill_remaining == 0:
                    completed.append((s, req))
            if self.quarantine:
                items = completed + [
                    (s, r) for s, r in decoding if self.slots.reqs[s] is r
                ]
                self._quarantine_scan({s: last_np[s] for s, _ in items}, items)
            for s, req in completed:
                if self.slots.reqs[s] is not req:  # quarantined/cancelled
                    continue
                self._register_prefix(s, req)
                if not req.out:  # restored decode slots have already emitted
                    self._emit(req, row=last_np[s])  # first generated token
            for s, req in decoding:
                if self.slots.reqs[s] is not req:  # quarantined/cancelled
                    continue
                self._emit(req, row=last_np[s])

    # ------------------------------------------------------------ driving
    def step(self):
        """One scheduling round: retire expired requests, admit from the
        queue, then advance — the legacy admit-gulp + decode tick when
        ``chunk_budget`` is None, or one fused interleaved dispatch.

        A transient dispatch ``FaultError`` (always raised BEFORE the
        jitted call, so no state was mutated) aborts the round; the next
        round retries the same work. ``max_retries`` consecutive failures
        fail every in-flight request terminally instead of spinning."""
        with TraceAnnotation("serve.step"):
            if self.faults is not None:
                self.faults.set_tick(self.ticks)
            with TraceAnnotation("serve.retire"):
                self._retire_expired()
            try:
                self._admit()
                if self.scheduler.chunk_budget is None:
                    # prefill alone may satisfy max_new
                    with TraceAnnotation("serve.finish"):
                        self._finish_ready()
                    if self.slots.any_live():
                        self.tick()
                else:
                    self._interleaved_tick()
            except FaultError as e:
                self._note_dispatch_fault(e)
            with TraceAnnotation("serve.finish"):
                self._finish_ready()

    def _note_dispatch_fault(self, e: FaultError) -> None:
        """Tick-level dispatch fault bookkeeping: count it, and once
        ``max_retries`` CONSECUTIVE rounds have failed (any successful
        dispatch resets the streak), retire every in-flight request
        terminally failed — degraded but reconcilable, never a crash."""
        self.dispatch_faults += 1
        self._consec_dispatch_faults += 1
        if self._consec_dispatch_faults <= self.max_retries:
            return
        for s, req in self.slots.live_items():
            self._free_slot_blocks(s)
            self.slots.release(s)
            req.failed = True
            req.error = (
                f"dispatch failed {self._consec_dispatch_faults} "
                f"consecutive rounds: {e}"
            )
            self.finished.append(req)
        self._pending_prefill = set()
        self._consec_dispatch_faults = 0

    def _pending(self) -> bool:
        return bool(self.scheduler.queue) or self.slots.any_live()

    def run(self, max_ticks: int = 10_000, on_exhausted: str = "raise"):
        """Drive until all submitted requests finish (or this call has spent
        ``max_ticks`` ticks — the budget is per call, not lifetime).

        An exhausted budget with unfinished requests used to return
        silently, indistinguishable from completion. Now every unfinished
        request (queued or mid-flight) is flagged ``timed_out``, and
        ``on_exhausted`` picks the contract: ``"raise"`` (default) raises
        ``TickBudgetExceeded``; ``"flag"`` returns the finished list with
        the stragglers left in place for a later ``run`` call."""
        if on_exhausted not in ("raise", "flag"):
            raise ValueError(
                f"on_exhausted must be 'raise' or 'flag', got {on_exhausted!r}"
            )
        start = self.ticks
        stalled = 0
        exhausted = False
        while self._pending():
            if self.ticks - start + stalled >= max_ticks:
                # only work that needs dispatches counts as exhaustion —
                # a queue drained by retirement below is not
                self._retire_expired()
                if self._pending():
                    exhausted = True
                break
            before = (self.ticks, self.prefill_tokens, len(self.finished))
            self.step()
            if (self.ticks, self.prefill_tokens, len(self.finished)) == before:
                # a round that advanced nothing (injected dispatch/alloc
                # faults, backoff) burns tick budget too — otherwise a
                # permanently faulted engine would spin here forever
                # instead of raising the documented TickBudgetExceeded
                stalled += 1
                self._stalled_steps += 1
        if exhausted:
            unfinished = [r for _, r in self.slots.live_items()]
            unfinished += list(self.scheduler.queue)
            for r in unfinished:
                r.timed_out = True
            if on_exhausted == "raise":
                raise TickBudgetExceeded(
                    f"run(max_ticks={max_ticks}) exhausted its tick budget "
                    f"with {len(unfinished)} unfinished request(s) "
                    f"(uids {[r.uid for r in unfinished]}); they are flagged "
                    "Request.timed_out — pass on_exhausted='flag' to get "
                    "partial results instead of this exception"
                )
        return self.finished

    # ------------------------------------------------------ reconciliation
    def check_invariants(self) -> dict:
        """Full host-side reconciliation: slot map vs. allocator refcounts
        vs. block tables vs. prefix-trie chains vs. the scheduler queue.

        Callable between steps at any point (the chaos tests run it after
        every fault and at drain) — it is pure bookkeeping, no dispatches.
        Raises ``RuntimeError`` at the first violation; returns a summary
        dict when everything reconciles. Mid-``_try_bind`` transient COW
        pins are the one sanctioned imbalance, and they never survive the
        bind call, so between steps the counts must agree exactly."""
        live = self.slots.live_items()
        self.slots.check_consistent(self.slot_capacity)
        for s, req in live:
            if req.done or req.failed or req.cancelled:
                raise RuntimeError(
                    f"slot {s}: request {req.uid} is retired "
                    "(done/failed/cancelled) but still bound"
                )
        uids = [r.uid for r in self.scheduler.queue] + [r.uid for _, r in live]
        if len(set(uids)) != len(uids):
            raise RuntimeError(
                f"duplicate uid across queue + slots: {sorted(uids)}"
            )
        for r in self.scheduler.queue:
            if r.done or r.failed or r.cancelled or r.timed_out:
                raise RuntimeError(
                    f"queued request {r.uid} is already retired"
                )
        summary = {
            "live_slots": len(live),
            "queued": len(self.scheduler.queue),
            "finished": len(self.finished),
        }
        if self.paging is None:
            return summary
        spec = self.paging
        expected = [0] * spec.num_blocks
        for s in range(self.num_slots):
            blocks = self.slot_blocks[s]
            row = self.block_tables[s]
            if self.slots.reqs[s] is None:
                if blocks or row.any():
                    raise RuntimeError(
                        f"slot {s} is unbound but still holds blocks "
                        f"{blocks or row.nonzero()[0].tolist()} — leak"
                    )
                continue
            if not blocks:
                raise RuntimeError(
                    f"slot {s} (request {self.slots.reqs[s].uid}) is live "
                    "with no reserved blocks"
                )
            if (
                list(row[: len(blocks)]) != blocks
                or row[len(blocks):].any()
            ):
                raise RuntimeError(
                    f"slot {s}: block table row {row.tolist()} does not "
                    f"mirror the reservation {blocks}"
                )
            for b in blocks:
                if not 0 < b < spec.num_blocks:
                    raise RuntimeError(f"slot {s} maps foreign block {b}")
                expected[b] += 1
        self.allocator.check_consistent(expected)
        registered = (
            set(self.prefix._node_of_block) if self.prefix is not None else set()
        )
        for b in range(1, spec.num_blocks):
            if (
                self.allocator.refcount[b] == 0
                and b not in self.allocator._free_set
                and b not in registered
            ):
                raise RuntimeError(
                    f"block {b} leaked: refcount 0, not on the free list, "
                    "not cached in the prefix trie"
                )
        if self.prefix is not None:
            self.prefix.check_chains()
        for r in self.scheduler.queue:
            if r._swap is None and r.prompt_done > r.cached_tokens:
                # a queued non-preempted request holds no cache state, so a
                # nonzero cursor would skip prefilling real prompt tokens
                raise RuntimeError(
                    f"queued request {r.uid} has prefill cursor "
                    f"{r.prompt_done} but no slot and no swap snapshot"
                )
        summary.update({
            "free_blocks": self.allocator.free_blocks,
            "live_refs": self.allocator.live_refs,
            "cached_blocks": len(registered),
        })
        return summary
