"""Continual-learning serving engine: slot-scheduled continuous batching.

The paper's deployment story — one model serving live traffic while every
verify step trains the drafter — implemented as a **slot scheduler** around
the shared speculative block-step (``spec_block_step``):

* the decode batch is a fixed set of ``num_slots`` lanes over one persistent
  cache; each lane independently holds a request at its own committed length,
* arriving requests are prefilled individually (exact prompt, no bucket
  padding) and spliced into a free lane with ``transformer.insert_slot``,
* every engine tick dispatches ONE fused **superstep** of ``sync_every``
  speculative blocks (``spec_superstep``): EOS detection, per-lane budget
  capping, token-stream assembly, and tuple logging all run in-graph, so
  the host syncs with the device once per superstep — a compact summary
  (done mask, per-lane commit counts, token buffer) — instead of once per
  block; idle lanes ride along masked ``done`` (accept = 0, no state
  change, no tuples logged),
* the dispatch is **double-buffered**: ``step()`` first admits arrivals
  into already-free lanes (those ops queue behind the in-flight superstep
  without blocking), only then harvests the in-flight summary, so host
  bookkeeping overlaps device compute instead of serializing behind it,
* lanes retire per-request on EOS or ``max_new`` — completions stream out
  at superstep boundaries (the superstep/`sync_every` contract: admission,
  retirement, and preemption happen only at boundaries; token streams stay
  bit-identical to per-block ticking, the trade is up to ``sync_every - 1``
  blocks of extra completion latency for ~``sync_every``x fewer host
  syncs/dispatches) — and the lane is reset for reuse,
* the LoRA drafter takes an update every ``update_every`` block-steps from
  the replay buffer; the update is dispatched WITHOUT blocking the engine —
  the new ``dvi_params`` are folded in at the next superstep boundary, so
  decode proceeds with (one superstep) stale drafter weights instead of
  stalling behind the optimizer (lossless: the committed stream never
  depends on drafter quality, only acceptance does),
* per-request latency (arrival -> completion; see ``latency_percentiles``)
  and per-slot acceptance are tracked so drift and stragglers are
  observable; latencies are kept in a rolling window of the most recent
  ``latency_window`` completions so long-running engines don't grow
  unboundedly.

With ``kv_pages > 0`` the continuous scheduler runs over a **paged** KV
cache (``repro.serving.kv_pool``): full-attention KV lives in a shared page
pool, lanes hold block-table rows instead of worst-case contiguous regions,
and scheduling becomes memory-aware:

* **admission** checks the free-page watermark, not just a free lane — a
  request is admitted when the pool can cover its prompt plus one
  speculative block (later growth is on demand),
* **growth**: before every superstep each live lane is topped up to cover
  the positions that superstep can touch — ``sync_every`` blocks of K+1
  eager tokens, CAPPED by the lane's remaining ``max_new`` budget (a lane
  about to retire only gets pages for the blocks it can still run) — so
  pages are allocated only as sequences grow and short/near-done requests
  no longer pay for long ones,
* **preempt-or-queue**: when the pool runs dry mid-decode, the newest lane
  is preempted — its pages return to the pool, its progress (prompt +
  generated prefix) is re-queued at the front of the FIFO and replayed via
  prefill on re-admission, which is lossless for greedy decoding,
* retirement frees the lane's pages (``reset_slot`` just unmaps the
  block-table row; no KV bytes move).

With ``prefill_chunk > 0`` prompt prefill is **chunked and scheduled**
instead of one-shot-on-admission, so a single long prompt can no longer
stall every live lane for its whole prefill:

* admission only prefills the FIRST chunk (into a chunk-sized scratch,
  spliced with ``insert_slot`` — which accepts the partially-built cache)
  and parks the lane in a PREFILL state: ``done``-masked, it rides along
  inert through supersteps (``spec_block_step`` freezes masked lanes'
  stateful-mixer state and cache length, so the partial prefill survives
  untouched),
* every tick, ONE batched **chunk step** (``model.prefill_chunk``) advances
  all prefilling lanes by up to ``prefill_chunk`` tokens each, directly in
  the live cache (contiguous or paged) — the per-tick prefill work is
  bounded by ``num_slots * prefill_chunk`` tokens regardless of prompt
  length, and decode supersteps keep firing between chunks,
* a lane that consumes its last chunk flips live the SAME tick and enters
  that tick's superstep (its pending token is set in-graph by the chunk
  step), so chunking adds no extra tick of completion latency,
* paged mode provisions pages chunk-by-chunk (``KVPool.ensure``) instead
  of whole-prompt at admission — admission is gated on the first chunk's
  pages against the watermark; later chunks are growth-class allocations
  that, like decode page growth, may dip into the watermark headroom —
  and a mid-prefill lane is preemptible exactly like a decode lane: its
  pages are freed and its request re-queued at the FIFO front (lossless —
  no tokens were generated),
* committed token streams are bit-identical to one-shot prefill (greedy
  and sampled, both layouts — tested): the chunk step is the same decode
  math at the same positions, only scheduled differently.

With ``adaptive_k=True`` speculation depth becomes a per-lane runtime
quantity driven by the verifier's accept/reject stream (the paper's
training-aware thesis applied to the speculative machinery itself, not
just the drafter weights):

* each lane carries depth-controller state (depth ``k``, acceptance EMA,
  cooldown — see ``repro.core.schedule.DepthConfig``); the controller runs
  IN-GRAPH inside the fused superstep, so depth adapts per block with zero
  extra host syncs and changes apply only at block boundaries,
* the host mirrors the controller state per slot (harvested with the
  superstep summary, reset to ``k_init`` on admission, so a recycled lane
  never inherits the previous request's depth),
* every dispatch drafts ``K_blk = max`` over the live lanes' depth
  ceilings — when the whole batch throttles down (e.g. post-drift while
  the drafter relearns), the superstep re-specializes to a SHALLOWER draft
  scan and each block gets genuinely cheaper (this is where adaptive depth
  buys wall-clock, not just accounting; at most ``k_max`` distinct
  compilations),
* paged-pool math splits by purpose (the adaptive-depth contract, see
  ROADMAP): reservation-class computations (admission gating, pre-admission
  reserves, prompt trimming, cache capacity) use the worst-case ``k_max``;
  growth-class computations provision each lane for its LIVE depth plus the
  bounded number of rises the controller could make within one superstep
  (``schedule.max_depth_rises``), and that same bound is passed back into
  the graph as a hard ceiling ``k_cap`` — an in-graph rise can never outrun
  the pages provisioned for it, so low-acceptance lanes stop hoarding pool
  headroom without risking committed KV,
* greedy committed streams are depth-independent (speculative decoding is
  lossless for ANY k), so turning the controller on changes throughput and
  compute, never tokens; with ``adaptive_k=False`` the engine takes the
  fixed-depth code path untouched.

``scheduler="sync"`` keeps the legacy batch-synchronous path (bucket by
prompt length, decode a whole batch to completion with
``speculative_generate``) for comparison — ``benchmarks/serving_bench.py``
races the two on the same Poisson arrival trace.
"""
from __future__ import annotations

import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import online as online_mod
from repro.core import schedule as schedule_mod
from repro.core import scopes
from repro.core import spec as spec_mod
from repro.models import transformer as tfm
from repro.models.model import Model
from repro.serving.handles import QueueFull, RequestHandle, TenantQueue
from repro.serving.kv_pool import KVPool
from repro.serving.telemetry import ServingTelemetry


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (Tp,) int32
    max_new: int = 64
    tenant: str = "default"       # weighted-fair queue bucket
    priority: int = 0             # within-tenant ordering (higher first)


@dataclass
class Completion:
    uid: int
    tokens: np.ndarray            # full stream (prompt + generated)
    gen_tokens: np.ndarray        # generated tokens only
    mat: float                    # mean accepted tokens/block for this request
    wall_s: float                 # engine time attributed to this request
    # submit -> completion wall time.  Superseded by the RequestHandle
    # timestamp set (queue-wait / prefill / decode split via
    # ``handle.timings()``); kept for existing consumers of the flat value.
    latency_s: float = 0.0


@dataclass
class _Slot:
    """Host-side bookkeeping for one live lane of the decode batch."""
    uid: int
    prompt: np.ndarray
    max_new: int
    gen: List[int] = field(default_factory=list)
    blocks: int = 0
    wall_s: float = 0.0
    cache_len: int = 0            # committed cache length (paged growth)
    admit_seq: int = 0            # admission order (paged preemption picks max)
    pf_prompt: Optional[np.ndarray] = None  # trimmed replay source (chunked)
    pf_pos: Optional[int] = None  # prompt tokens prefilled; None = decoding
    handle: Optional[RequestHandle] = None  # caller-facing async view


@dataclass
class ServingEngine:
    model: Model
    params: dict
    state: online_mod.OnlineTrainerState
    scheduler: str = "sync"       # "sync" (legacy batch) | "continuous"
    num_slots: int = 8            # continuous: lanes in the decode batch
    batch_size: int = 8           # sync: requests per batch
    max_new: int = 64             # default / cap for generation length
    buckets: tuple = (16, 32, 64, 128)
    updates_per_batch: int = 1    # sync: drafter updates after each batch
    update_every: int = 4         # continuous: blocks between drafter updates
    sync_every: int = 1           # continuous: blocks fused per device sync
    latency_window: int = 4096    # rolling window of completion latencies
    learn: bool = True
    lr: float = 1e-3
    mode: str = "full"
    eos_id: int = 1
    cache_len: int = 0            # continuous cache capacity (0 = derive)
    kv_pages: int = 0             # >0: paged KV pool with this many pages
    kv_page_size: int = 16        # tokens per page (paged mode)
    kv_watermark: int = 0         # pages kept free at admission (paged mode)
    prefix_cache: bool = False    # paged: share page-aligned prompt prefixes
    prefill_chunk: int = 0        # >0: prefill in chunks of this many tokens
    adaptive_k: bool = False      # per-lane acceptance-driven depth control
    k_min: int = 1                # adaptive: depth floor
    k_max: int = 0                # adaptive: depth ceiling (0 = cfg.dvi.k_spec)
    depth_cfg: Optional[schedule_mod.DepthConfig] = None  # full override
    # monotonic clock for every elapsed-duration read (injectable so timing
    # behaviour is testable deterministically; see tests/test_telemetry.py)
    clock: Callable[[], float] = time.monotonic
    telemetry: bool = False       # lifecycle tracer on (metrics always on)
    trace_limit: int = 200_000    # tracer event cap (overflow -> dropped)
    profile_dir: Optional[str] = None  # jax.profiler capture dir (optional)
    profile_steps: int = 32       # ticks inside the capture window
    max_queue: int = 0            # admission queue bound (0 = unbounded);
                                  # submissions past it raise QueueFull
    tenant_weights: Optional[Dict[str, float]] = None  # WFQ shares (def. 1)
    _queue: Dict[int, List[Request]] = field(default_factory=dict)
    # registry-backed stats facade; built in __post_init__ from the ONE
    # canonical schema (telemetry.LEGACY_STATS) — do not pass explicitly
    stats: object = None

    def __post_init__(self):
        model, cfg = self.model, self.model.cfg
        K = cfg.dvi.k_spec
        if self.prefill_chunk and self.scheduler != "continuous":
            raise ValueError("chunked prefill requires scheduler='continuous'")
        # ring caches absorb at most RING_SLACK eager tokens beyond the live
        # window, and idle lanes see a chunk step's writes as eager garbage
        # (rolled back by length masking, like rejected speculative tokens) —
        # so the chunk is clamped to the slack the rollback rule guarantees
        self._chunk = min(max(0, int(self.prefill_chunk)), tfm.RING_SLACK)
        # adaptive depth: controller config, plus the WORST-CASE depth that
        # every reservation-class computation (cache capacity, prompt
        # trimming, admission gating, pre-admission reserves) must assume —
        # the adaptive-depth contract.  Growth-class computations use the
        # live per-lane depth instead (see _lane_growth_k).
        if self.adaptive_k and self.scheduler != "continuous":
            raise ValueError("adaptive_k requires scheduler='continuous'")
        if self.adaptive_k:
            kmax = self.k_max or K
            self._depth = self.depth_cfg or schedule_mod.DepthConfig(
                k_min=self.k_min, k_max=kmax,
                k_init=min(max(K, self.k_min), kmax))
            self._k_worst = self._depth.k_max
        else:
            self._depth = None
            self._k_worst = K
        self._cap = self.cache_len or (max(self.buckets) + self.max_new
                                       + self._k_worst + 2 + tfm.RING_SLACK)
        self._update_fn = online_mod.make_update_fn(self.model, self.mode,
                                                    self.lr)
        self._key = jax.random.PRNGKey(1234)

        # continuous state: one persistent cache, host-side slot table
        self._slots: List[Optional[_Slot]] = [None] * self.num_slots
        self._done = np.ones((self.num_slots,), bool)
        self._pending = jnp.zeros((self.num_slots,), jnp.int32)
        self._cache: Optional[dict] = None
        self._slot_accepted = np.zeros((self.num_slots,), np.int64)
        self._slot_drafted = np.zeros((self.num_slots,), np.int64)
        self._slot_committed = np.zeros((self.num_slots,), np.int64)
        self._slot_blocks = np.zeros((self.num_slots,), np.int64)
        # host mirror of the per-lane depth-controller state: uploaded at
        # dispatch, harvested with the superstep summary, reset to k_init on
        # admission (so a recycled lane starts fresh).  Kept even when the
        # controller is off (then it just pins k == k_spec in the stats).
        ki = self._depth.k_init if self._depth is not None else K
        ei = self._depth.ema_init if self._depth is not None else 0.0
        self._k_host = np.full((self.num_slots,), ki, np.int32)
        self._ema_host = np.full((self.num_slots,), ei, np.float32)
        self._cool_host = np.zeros((self.num_slots,), np.int32)
        self._submit_t: Dict[int, float] = {}
        self._blocks_since_update = 0
        # redesigned request surface: per-tenant weighted-fair admission
        # queue (single default tenant degenerates to the legacy FIFO order
        # exactly) + live handles for every accepted, unfinished request
        self._tq = TenantQueue(max_queue=self.max_queue,
                               weights=self.tenant_weights)
        self._handles: Dict[int, RequestHandle] = {}

        # telemetry: the metrics registry (and the legacy `stats` facade
        # over it) is ALWAYS on — it is pure host-side arithmetic riding
        # observations the engine already materializes; the lifecycle
        # tracer allocates only when `telemetry=True`.  The zero-host-sync
        # contract (see telemetry.py) is enforced by tests.
        self.telem = ServingTelemetry(
            num_slots=self.num_slots, k_max=self._k_worst,
            latency_window=self.latency_window, clock=self.clock,
            trace=self.telemetry, trace_limit=self.trace_limit)
        self.stats = self.telem.stats
        # host mirror of the optimizer step (drives the KL->RL schedule
        # gauges without touching the device on the hot path) and a bounded
        # history of per-update training metrics for timeline reports
        self._step_host = int(self.state.step)
        self.train_history: deque = deque(maxlen=1024)
        self._train_staged: list = []  # update metrics safe to materialize
        self._train_fold_note = None   # metrics folded THIS harvest
        self._profile_active = False
        self._profile_left = 0

        # ONE jitted generation entry point (jit shape-specializes on
        # `prompts`, so per-bucket closure caching was pure duplication);
        # max_new is threaded as a static arg, not a Python closure.
        def gen(params, dvi_params, prompts, buf, live, max_new):
            return spec_mod.speculative_generate(
                model, params, dvi_params, prompts, max_new,
                collect=True, buf=buf, live_mask=live)
        self._gen = jax.jit(gen, static_argnums=(5,))

        # the fused multi-block tick: sync_every blocks per device dispatch,
        # commit/EOS/budget handling in-graph (see spec_superstep)
        S = max(1, int(self.sync_every))
        self.sync_every = S
        eos = self.eos_id

        def superstep(params, dvi_params, pending, cache, buf, done, budget):
            return spec_mod.spec_superstep(
                model, params, dvi_params, pending, cache, steps=S,
                done=done, budget=budget, eos_id=eos, buf=buf, collect=True)
        self._superstep_fn = jax.jit(superstep)

        # adaptive-depth superstep: same fused loop, plus the in-graph depth
        # controller.  K_blk — the draft-scan width this dispatch — is a
        # STATIC arg: when every live lane has throttled down, the superstep
        # re-specializes to a shallower (cheaper) draft scan.  At most k_max
        # distinct compilations, cached by jit like chunk shapes.
        depth = self._depth

        def superstep_adaptive(params, dvi_params, pending, cache, buf, done,
                               budget, k, ema, cool, kcap, K_blk):
            return spec_mod.spec_superstep(
                model, params, dvi_params, pending, cache, steps=S,
                done=done, budget=budget, eos_id=eos, buf=buf, collect=True,
                k_spec=K_blk, k_lane=k, depth_cfg=depth, accept_ema=ema,
                k_cool=cool, k_cap=kcap)
        self._superstep_adaptive_fn = (
            jax.jit(superstep_adaptive, static_argnums=(11,))
            if depth is not None else None)
        # (SuperstepResult futures, engine-clock mark, occupied lanes)
        self._inflight: Optional[tuple] = None
        # drafter update dispatched but not yet folded into self.state
        self._update_inflight: Optional[tuple] = None
        # engine-resident clock: total time spent inside _step_continuous.
        # Per-request wall_s is attributed from THIS clock, so caller think
        # time between step() calls is never billed to lanes' compute.
        self._clock = 0.0
        self._tick_t0: Optional[float] = None

        cap = self._cap

        # paged KV pool: host-side ownership; block tables live in the cache
        self.paged = self.kv_pages > 0
        self._pool: Optional[KVPool] = None
        self._admit_seq = 0
        self._preempted: Dict[int, tuple] = {}   # uid -> (orig prompt, gen)
        if self.paged:
            if self.scheduler != "continuous":
                raise ValueError("paged KV requires scheduler='continuous'")
            self._pool = KVPool(self.kv_pages, self.kv_page_size)
            self._mps = self._pool.pages_for(cap)      # block-table width
            # host mirror of cache["tbl"]: per-tick page growth batches every
            # lane's row update into ONE device push (set_block_tables)
            # instead of one map_slot_pages dispatch per lane per allocation
            self._tbl_host = np.full((self.num_slots, self._mps), -1, np.int32)
            if self.kv_pages - self.kv_watermark < self._mps:
                raise ValueError(
                    f"kv_pages={self.kv_pages} minus watermark="
                    f"{self.kv_watermark} cannot hold one worst-case request "
                    f"({self._mps} pages of {self.kv_page_size}) — admission "
                    f"would livelock")
        # prefix caching: content-addressed sharing of page-aligned prompt
        # prefixes.  Requires the paged pool (the sharing substrate), the
        # chunked-prefill path (uncached TAILS are prefilled at offset
        # positions inside the live cache — scratch prefill always encodes
        # RoPE from 0, so it cannot build a tail), and a pure full-attention
        # stack (ring/SSM/RG-LRU segments hold per-lane state that cannot
        # be shared by prefix content).
        if self.prefix_cache:
            if not self.paged:
                raise ValueError("prefix_cache requires a paged KV pool "
                                 "(kv_pages > 0)")
            if self._chunk <= 0:
                raise ValueError("prefix_cache requires prefill_chunk > 0 — "
                                 "uncached prompt tails ride the chunked-"
                                 "prefill path")
            bad = [s.kind for s in tfm.model_segments(cfg) if s.kind != "attn"]
            if bad:
                raise ValueError(f"prefix_cache requires a pure full-"
                                 f"attention stack; got segment kinds {bad}")
        self._evict_seen = 0          # pool eviction counter folded per tick

        @jax.named_scope(scopes.PREFILL_ADMIT)
        def admit(params, cache, pending, prompt, slot):
            _, pc, _ = model.prefill(params, prompt[None, :-1], max_len=cap)
            cache = tfm.insert_slot(cfg, cache, pc, slot)
            pending = jax.lax.dynamic_update_slice_in_dim(
                pending, prompt[-1:], slot, 0)
            return pending, cache
        self._admit_fn = jax.jit(admit)

        @jax.named_scope(scopes.PREFILL_ADMIT)
        def admit_paged(params, cache, pending, prompt, slot, row):
            cache = tfm.map_slot_pages(cache, slot, row)
            # prefill scratch is prompt-sized, not worst-case-sized: the
            # splice through the block table is what lands it in the pool
            _, pc, _ = model.prefill(params, prompt[None, :-1],
                                     max_len=prompt.shape[0] - 1)
            cache = tfm.insert_slot(cfg, cache, pc, slot)
            pending = jax.lax.dynamic_update_slice_in_dim(
                pending, prompt[-1:], slot, 0)
            return pending, cache
        self._admit_paged_fn = jax.jit(admit_paged)

        @jax.named_scope(scopes.PREFILL_ADMIT)
        def admit_prefix(cache, pending, slot, row, length, cow_src, cow_dst,
                         tok, live):
            # warm admission (prefix-cache hit): the lane's cached prefix is
            # spliced in via the block TABLE only — zero prefill compute,
            # zero KV moves for full shared pages.  A partially-matched
            # cached page is COW-copied into the lane's first writable page
            # (cow_src == cow_dst == 0 makes that a null-page no-op).
            # `live`: a fully-cached prompt skips prefill entirely — its
            # pending token is set here and the lane decodes THIS tick.
            cache = tfm.copy_page(cache, cow_src, cow_dst)
            cache = tfm.map_slot_pages(cache, slot, row)
            cache = tfm.insert_slot(cfg, cache, None, slot, shared_len=length)
            cur = jax.lax.dynamic_slice_in_dim(pending, slot, 1, 0)
            pending = jax.lax.dynamic_update_slice_in_dim(
                pending, jnp.where(live, tok, cur[0])[None], slot, 0)
            return pending, cache
        self._admit_prefix_fn = jax.jit(admit_prefix)

        @jax.named_scope(scopes.PREFILL_ADMIT)
        def admit_chunk(params, cache, chunk, slot):
            # chunked admission (contiguous): prefill ONLY the first chunk
            # into a chunk-sized scratch — admission device work is O(chunk),
            # not O(prompt) — and splice the partially-built cache into the
            # (reset, hence inert-tailed) lane
            _, pc, _ = model.prefill(params, chunk[None, :],
                                     max_len=chunk.shape[0])
            return tfm.insert_slot(cfg, cache, pc, slot)
        self._admit_chunk_fn = jax.jit(admit_chunk)

        @jax.named_scope(scopes.PREFILL_CHUNK)
        def chunk_step(params, cache, pending, tokens, take, finish_tok,
                       finished):
            # ONE batched prefill-chunk step: every prefilling lane advances
            # by take[s] tokens (0 = lane rides along untouched); lanes that
            # consume their last prompt token get their pending set in-graph
            # so they can enter THIS tick's superstep
            _, cache = model.prefill_chunk(params, tokens, cache, take)
            return jnp.where(finished, finish_tok, pending), cache
        self._chunk_fn = jax.jit(chunk_step)

        self._set_tbl_fn = jax.jit(tfm.set_block_tables)
        self._reset_fn = jax.jit(
            lambda cache, slot: tfm.reset_slot(cfg, cache, slot))

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def submit_request(self, req: Request,
                       t_arrive: Optional[float] = None) -> RequestHandle:
        """Accept `req` into the admission queue and return its handle.

        The handle is the caller's async view: ``deltas()`` streams
        generated-token chunks as superstep boundaries harvest them,
        ``result()`` blocks for the Completion, ``cancel()`` requests
        retirement at the next boundary.  When the queue is bounded
        (``max_queue``) and full, the submission is REJECTED: the
        ``rejected`` counter increments, the returned-would-be handle is
        finished with outcome ``"rejected"``, and ``QueueFull`` is raised
        (it carries the handle as ``exc.handle``).

        ``t_arrive`` (engine clock): when the request reached a front end
        that handed it over from another thread.  With the tracer on, the
        request's lifecycle then starts there, with a ``submit`` phase up
        to this call."""
        now = self.clock()
        h = RequestHandle(req.uid, getattr(req, "tenant", "default"),
                          int(getattr(req, "priority", 0)), clock=self.clock)
        h.t_submit = now
        # submitted / per-tenant counters include rejected submissions, so
        # submitted == completed + cancelled + rejected + still-queued +
        # live reconciles exactly (scripts/check_metrics_schema.py)
        self.stats["submitted"] += 1
        self.telem.c_tenant.inc(h.tenant)
        if self.scheduler == "continuous":
            try:
                self._tq.push(req)
            except QueueFull as e:
                self.stats["rejected"] += 1
                h.finish(None, "rejected", t_done=now)
                e.handle = h
                raise
        else:
            b = self._bucket(len(req.prompt))
            self._queue.setdefault(b, []).append(req)
        self._handles[req.uid] = h
        self._submit_t[req.uid] = now
        tr = self.telem.tracer
        if tr is not None and self.scheduler == "continuous":
            t_in = now if t_arrive is None else t_arrive
            tr.async_begin("request", req.uid, t_in,
                           args={"prompt_len": int(len(req.prompt)),
                                 "max_new": int(req.max_new),
                                 "tenant": h.tenant})
            if t_arrive is not None:
                tr.async_begin("submit", req.uid, t_arrive)
                tr.async_end("submit", req.uid, now)
            tr.async_begin("queued", req.uid, now)
        if self.scheduler == "continuous":
            self.telem.g_queue.set(len(self._tq))
        return h

    def submit(self, req: Request) -> RequestHandle:
        """Deprecated fire-and-forget submission (pre-handle API).  Thin
        shim over ``submit_request`` — the committed token stream is
        bit-identical; only the return surface changed."""
        warnings.warn(
            "ServingEngine.submit(Request) is deprecated; use "
            "submit_request(Request) -> RequestHandle (deltas/result/"
            "cancel)", DeprecationWarning, stacklevel=2)
        return self.submit_request(req)

    def _pad(self, req: Request, bucket: int) -> np.ndarray:
        p = req.prompt[-bucket:]
        if len(p) < bucket:                      # left-pad by repeating BOS
            p = np.concatenate([np.full(bucket - len(p), p[0], p.dtype), p])
        return p

    # ------------------------------------------------------------------
    # drafter updates (shared)
    # ------------------------------------------------------------------

    def _drafter_update(self, n: int) -> None:
        for _ in range(n):
            t_disp = self.clock()
            step_u = self._step_host
            self._key, sub = jax.random.split(self._key)
            (self.state.dvi_params, self.state.opt_state,
             self.state.baseline, _m) = self._update_fn(
                self.params, self.state.dvi_params, self.state.opt_state,
                self.state.buf, self.state.baseline, self.state.step, sub)
            self.state.step = self.state.step + 1
            self.stats["updates"] += 1
            self._note_update_dispatched()
            # legacy sync path: the metrics stay device-resident; the
            # train_telemetry() accessor materializes them off the hot path
            self._train_staged = [(_m, t_disp, self.clock(), step_u)]

    def _note_update_dispatched(self) -> None:
        """Advance the host step mirror + schedule-phase gauges — pure host
        math (`schedule.phase_info`), no device touch."""
        self._step_host += 1
        ph = schedule_mod.phase_info(self._step_host, self.model.cfg.dvi)
        t = self.telem
        t.g_step.set(self._step_host)
        t.g_phase.set(ph["phase"])
        t.g_lambda_pg.set(ph["lambda_pg"])
        t.g_lambda_kl.set(ph["lambda_kl"])
        t.g_beta.set(ph["beta"])

    def _complete(self, uid: int, tokens: np.ndarray, gen_tokens: np.ndarray,
                  mat: float, wall_s: float) -> Completion:
        now = self.clock()
        lat = now - self._submit_t.pop(uid, now)
        self.stats["latencies"].append(lat)
        self.telem.h_latency.observe(lat)
        tr = self.telem.tracer
        if tr is not None and self.scheduler == "continuous":
            tr.async_end("decode", uid, now,
                         args={"gen_tokens": int(len(gen_tokens))})
            tr.async_end("request", uid, now,
                         args={"latency_s": lat, "mat": mat})
        return Completion(uid=uid, tokens=tokens, gen_tokens=gen_tokens,
                          mat=mat, wall_s=wall_s, latency_s=lat)

    # ------------------------------------------------------------------
    # handle finalization + cancellation (boundary-only)
    # ------------------------------------------------------------------

    def _finish_handle(self, uid: int, comp: Completion,
                       outcome: str = "completed") -> None:
        """Terminal handle transition: deliver any final tokens, observe
        TTFT if this is the first delivery (sync path: tokens arrive only
        at completion), stamp t_done, wake every waiter."""
        h = self._handles.pop(uid, None)
        if h is None:
            return
        if comp is not None and len(comp.gen_tokens):
            first = h.t_first_token is None
            h.feed(comp.gen_tokens)
            if first and h.t_first_token is not None:
                self.telem.h_ttft.observe(
                    h.t_first_token - (h.t_submit if h.t_submit is not None
                                       else h.t_first_token))
        h.finish(comp, outcome)

    def _finish_cancelled_queued(self, uid: int) -> None:
        """Cancel honored while the request sat in the admission queue (or
        a preemption replay): no lane, no pages — pure bookkeeping."""
        orig_prompt, gen0, blocks0, wall0, _ = self._preempted.pop(
            uid, (None, [], 0, 0.0, None))
        self._submit_t.pop(uid, None)
        self.stats["cancelled"] += 1
        now = self.clock()
        tr = self.telem.tracer
        if tr is not None and self.scheduler == "continuous":
            tr.async_end("queued", uid, now, args={"cancelled": True})
            tr.async_end("request", uid, now, args={"cancelled": True})
        h = self._handles.pop(uid, None)
        if h is not None:
            gen = np.asarray(gen0, np.int32)
            prompt = (np.asarray(orig_prompt, np.int32)
                      if orig_prompt is not None else np.zeros(0, np.int32))
            h.finish(Completion(uid=uid,
                                tokens=np.concatenate([prompt, gen]),
                                gen_tokens=gen,
                                mat=len(gen0) / max(blocks0, 1),
                                wall_s=wall0),
                     "cancelled", t_done=now)

    def _cancel_lane(self, s: int) -> None:
        """Retire live lane `s` on a cancel request — at a superstep
        boundary ONLY (the caller guarantees no superstep is in flight):
        free/decref its pages (prefix-shared included — published prefixes
        stay cached and evictable for the next tenant), unmap its row,
        reset the lane, and finish the handle with the committed-so-far
        partial stream.  Adds NO device_get: reset/unmap queue like any
        other boundary op."""
        st = self._slots[s]
        uid, mid_prefill = st.uid, st.pf_pos is not None
        if self.paged:
            self._pool.free(uid)         # decref: shared pages survive in
            self._tbl_host[s] = -1       # the prefix cache, owned ones free
        self._cache = self._reset_fn(self._cache, jnp.int32(s))
        self._slots[s] = None
        self._done[s] = True
        self._preempted.pop(uid, None)
        self._submit_t.pop(uid, None)
        self.stats["cancelled"] += 1
        now = self.clock()
        tr = self.telem.tracer
        if tr is not None:
            tr.instant(s, "cancel", now,
                       args={"uid": uid, "gen_len": len(st.gen),
                             "mid_prefill": mid_prefill})
            tr.async_end("prefill" if mid_prefill else "decode", uid, now,
                         args={"cancelled": True})
            tr.async_end("request", uid, now, args={"cancelled": True})
        h = self._handles.pop(uid, None)
        if h is not None:
            gen = np.asarray(st.gen, np.int32)
            h.finish(Completion(uid=uid,
                                tokens=np.concatenate([st.prompt, gen]),
                                gen_tokens=gen,
                                mat=len(st.gen) / max(st.blocks, 1),
                                wall_s=st.wall_s),
                     "cancelled", t_done=now)

    def _sweep_cancels(self) -> None:
        """Honor pending ``handle.cancel()`` flags.  Runs right after the
        harvest — the one point in the tick where no superstep is in
        flight, so retiring a lane (pages freed, row unmapped, cache
        reset) cannot race device work that still reads those pages.
        Queued requests are dropped from the tenant queue; live lanes
        (decoding OR mid-chunked-prefill) are retired in place.  Lanes
        untouched by the sweep keep their state byte-for-byte, so their
        committed streams stay bit-identical (tested)."""
        want = [uid for uid, h in self._handles.items()
                if h.cancel_requested and not h.finished]
        if not want:
            return
        in_slot = {st.uid: s for s, st in enumerate(self._slots)
                   if st is not None}
        queued = set(want) - set(in_slot)
        if queued:
            for req in self._tq.drop(queued):
                self._finish_cancelled_queued(req.uid)
        for uid in want:
            s = in_slot.get(uid)
            if s is not None:
                self._cancel_lane(s)

    def abort_pending(self, reason: str) -> None:
        """Fail every unfinished handle (engine thread crashed, or shutdown
        without drain): unblocks all blocked consumers with outcome
        ``"error"``.  Engine device state is NOT touched."""
        for h in list(self._handles.values()):
            h.abort(reason)
        self._handles.clear()

    # ------------------------------------------------------------------
    # sync scheduler (legacy batch path)
    # ------------------------------------------------------------------

    def _step_sync(self) -> List[Completion]:
        """Serve one batch from the fullest bucket; maybe update the drafter."""
        # cancels are honored at batch formation (the sync path's only
        # scheduling boundary): cancelled waiters never enter a batch
        for b, lst in list(self._queue.items()):
            keep = []
            for r in lst:
                hc = self._handles.get(r.uid)
                if hc is not None and hc.cancel_requested:
                    self._finish_cancelled_queued(r.uid)
                else:
                    keep.append(r)
            self._queue[b] = keep
        if not any(self._queue.values()):
            return []
        bucket = max(self._queue, key=lambda b: len(self._queue[b]))
        reqs = self._queue[bucket][:self.batch_size]
        self._queue[bucket] = self._queue[bucket][self.batch_size:]
        n_real = len(reqs)
        t_b = self.clock()
        for r in reqs:
            hb = self._handles.get(r.uid)
            if hb is not None and hb.t_admit is None:
                hb.t_admit = t_b
                self.telem.h_queue_wait.observe(
                    t_b - (hb.t_submit if hb.t_submit is not None else t_b))
        while len(reqs) < self.batch_size:       # pad batch with replays
            reqs.append(reqs[-1])
        # padded lanes are masked out of generation, tuple logging, and stats
        live = jnp.arange(self.batch_size) < n_real
        prompts = jnp.asarray(np.stack([self._pad(r, bucket) for r in reqs]))

        t0 = self.clock()
        res = self._gen(self.params, self.state.dvi_params, prompts,
                        self.state.buf, live, int(self.max_new))
        jax.block_until_ready(res.tokens)
        wall = self.clock() - t0
        self.state.buf = res.buffer

        if self.learn:
            self._drafter_update(self.updates_per_batch)

        mat = float(res.committed) / max(float(res.blocks), 1.0)
        self.stats["requests"] += n_real
        self.stats["blocks"] += int(res.blocks)
        self.stats["committed"] += int(res.committed)
        self.stats["accepted"] += int(res.accepted_drafts)
        self.stats["drafted"] += int(res.drafted)

        outs = []
        toks = np.asarray(res.tokens)
        lens = np.asarray(res.lengths)
        for i, r in enumerate(reqs[:n_real]):
            # the batch decodes to the engine-wide max_new (head-of-line cost
            # of sync scheduling) but the client only gets what it asked for
            gen = toks[i, bucket:lens[i]][:min(r.max_new, self.max_new)]
            comp = self._complete(
                r.uid, np.concatenate([toks[i, :bucket], gen]), gen,
                mat, wall / n_real)
            outs.append(comp)
            self._finish_handle(r.uid, comp)
        return outs

    # ------------------------------------------------------------------
    # continuous scheduler (slot-based)
    # ------------------------------------------------------------------

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    def _trim_prompt(self, req: Request, remaining_new: int) -> np.ndarray:
        """`remaining_new`: generation budget still outstanding — the full
        max_new for fresh requests, minus tokens already generated for
        re-queued preempted ones (whose prompt carries that prefix, so the
        worst-case capacity check must not double-count it)."""
        cfg = self.model.cfg
        prompt = np.asarray(req.prompt, np.int32)
        if len(prompt) < 2:                  # need prefill + pending
            prompt = np.concatenate(
                [np.full(2 - len(prompt), prompt[0], np.int32), prompt])
        # oversized prompts keep their suffix (mirrors the sync path's
        # `_pad` truncation) rather than crashing the serving loop.  A chunk
        # step's eager writes past a full-length idle lane's committed
        # prefix need no extra margin here: full caches CLIP out-of-capacity
        # writes (spread_write wrap=False) instead of ring-wrapping them.
        # Worst-case depth, not live depth: capacity is reservation-class.
        limit = self._cap - remaining_new - self._k_worst - 2
        if len(prompt) > limit:
            prompt = prompt[-limit:]
        return prompt

    def _first_chunk(self, prompt: np.ndarray) -> int:
        """Prompt tokens prefilled AT ADMISSION: the whole prompt (minus the
        pending token) when one-shot or when it fits one chunk; else exactly
        one chunk, with the rest scheduled tick-by-tick."""
        n = len(prompt) - 1
        return min(self._chunk, n) if self._chunk else n

    def _prefill_extent(self, st: _Slot) -> tuple:
        """(take, finishing, cache extent) for lane `st`'s next prefill
        chunk.  A finishing chunk must also provision the first-superstep
        horizon — the lane flips live THIS tick and runs the superstep on
        this provisioning alone (same rule as one-shot admission)."""
        rest = len(st.pf_prompt) - 1 - st.pf_pos
        take = min(self._chunk, rest)
        extent = st.pf_pos + take
        finishing = take == rest
        if finishing:
            extent += self._superstep_horizon(st.max_new - len(st.gen)) + 1
        return take, finishing, extent

    def _superstep_horizon(self, remaining: int, k: Optional[int] = None) -> int:
        """Cache slots one superstep can touch beyond a lane's committed
        length: ``sync_every`` blocks of K+1 eager tokens, capped by the
        lane's remaining generation budget (a lane that can only run r more
        blocks before retiring advances the cache at most r + K slots).
        The ONE formula shared by admission sizing and page growth — they
        must stay in lockstep, since lanes admitted after the tick's growth
        pass run their first superstep on admission's provisioning alone.

        `k`: the depth to assume.  Defaults to the worst case (``k_max``
        when adaptive, else ``k_spec``) — what every reservation-class
        caller must use; growth passes the lane's live depth bound
        (``_lane_growth_k``) instead, per the adaptive-depth contract."""
        K = self._k_worst if k is None else k
        return min(self.sync_every * (K + 1), remaining + K)

    def _pages_needed(self, cache_len: int, remaining: int,
                      k: Optional[int] = None) -> int:
        """Pages covering `cache_len` committed slots plus one superstep
        horizon (+1 slack slot, the pre-superstep rule since PR 3)."""
        return self._pool.pages_for(
            cache_len + self._superstep_horizon(remaining, k) + 1)

    def _lane_growth_k(self, s: int) -> int:
        """The depth bound lane `s` is provisioned for over its NEXT
        superstep: its live depth plus the (cooldown-limited) rises the
        in-graph controller could make within ``sync_every`` blocks.  This
        same bound is passed back into the superstep as ``k_cap``, so the
        provisioning and the controller's reachable depths are mutually
        consistent by construction — pages can never be outrun."""
        if self._depth is None:
            return self.model.cfg.dvi.k_spec
        rises = schedule_mod.max_depth_rises(
            self._depth, self.sync_every, int(self._cool_host[s]))
        return min(self._depth.k_max, int(self._k_host[s]) + rises)

    def _growth_reserve(self) -> int:
        """Upper bound on the pages live lanes may still need for their
        NEXT growth pass, assuming the in-flight superstep commits its full
        horizon.  Pre-admission (which runs BEFORE harvest + growth) keeps
        this many pages untouched so a new request never grabs pages that
        older live lanes immediately claw back by preempting it."""
        reserve = 0
        for st in self._slots:
            if st is None:
                continue
            if st.pf_pos is not None:    # mid-prefill: next chunk's demand
                continue                 # (counted by _prefill_reserve)
            remaining = st.max_new - len(st.gen)
            if remaining <= 0:
                continue
            inflight_cap = st.cache_len + self._superstep_horizon(remaining)
            need = self._pages_needed(inflight_cap, remaining)
            reserve += max(0, need - len(self._pool.owned(st.uid)))
        return reserve + self._prefill_reserve()

    def _prefill_reserve(self) -> int:
        """Pages mid-prefill lanes will claim for their NEXT chunk (plus the
        finishing-chunk superstep horizon).  BOTH admission sites must keep
        these untouched — ``_advance_prefill`` consumes them right after the
        post-growth admission, so admitting a request into them would only
        get it preempted by a senior prefill lane the same tick (a wasted
        admission prefill per tick for the rest of the long prefill)."""
        reserve = 0
        for st in self._slots:
            if st is None or st.pf_pos is None:
                continue
            _, _, extent = self._prefill_extent(st)
            need = self._pool.pages_for(extent)
            reserve += max(0, need - len(self._pool.owned(st.uid)))
        return reserve

    def _admit_waiting(self, reserve: int = 0) -> None:
        """Prefill-on-arrival: splice queued requests into free lanes.
        Paged mode additionally gates admission on the free-page watermark:
        the pool must cover the prompt plus the lane's FIRST superstep
        (``sync_every`` blocks of K+1 eager tokens, budget-capped) — lanes
        can be admitted after this tick's growth pass ran, so admission
        itself must provision the horizon; later growth is on demand.
        `reserve`: extra pages kept free on top of the watermark
        (pre-admission passes the live lanes' growth demand)."""
        tr = self.telem.tracer
        while self._tq and not all(s is not None for s in self._slots):
            t_a0 = self.clock()
            slot = next(i for i, s in enumerate(self._slots) if s is None)
            req = self._tq.peek()
            if req is None:
                break
            hq = self._handles.get(req.uid)
            if hq is not None and hq.cancel_requested:
                # cancelled while queued: finalize instead of admitting —
                # no lane, no pages, no prefill compute ever spent
                self._tq.take(req)
                self._finish_cancelled_queued(req.uid)
                continue
            max_new = min(req.max_new, self.max_new)
            gen_carry = len(self._preempted.get(req.uid, (None, ()))[1])
            prompt = self._trim_prompt(req, max_new - gen_carry)
            c1 = self._first_chunk(prompt)
            chunked = c1 < len(prompt) - 1   # rest scheduled tick-by-tick
            if self._cache is None:
                self._cache = (self.model.init_paged_cache(
                    self.num_slots, self.kv_pages, self.kv_page_size,
                    self._mps) if self.paged
                    else self.model.init_cache(self.num_slots, self._cap))
            hit = None
            if self.paged and self.prefix_cache:
                # longest cached prefix of the prompt (the pending token is
                # never cached).  Counted per LOOKUP — a watermark-blocked
                # admission retried next tick counts again, by design.
                hit = self._pool.acquire_prefix(req.uid, prompt[:-1])
                self.stats["prefix_lookups"] += 1
                if hit.hit_tokens > 0:
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_hit_tokens"] += hit.hit_tokens
                else:
                    self.stats["prefix_misses"] += 1
            if hit is not None and hit.hit_tokens > 0:
                # ---- warm admission: splice shared pages, prefill only the
                # uncached tail.  `warm` tokens are already resident (full
                # shared pages + a COW-copied partial page); the tail rides
                # the chunked-prefill path from position `warm`.
                warm = hit.hit_tokens
                tail = len(prompt) - 1 - warm
                need = (self._pool.pages_for(warm + min(self._chunk, tail))
                        if tail > 0
                        else self._pages_needed(len(prompt) - 1,
                                                max_new - gen_carry))
                if not self._pool.can_alloc(need - len(hit.pages),
                                            self.kv_watermark + reserve):
                    if hit.pages:            # put the shared pages back
                        self._pool.free(req.uid)
                    self.telem.c_watermark.inc()
                    if tr is not None:
                        tr.instant(self.telem.tid_engine, "pool_watermark",
                                   args={"uid": req.uid, "need": need,
                                         "free": self._pool.available_pages,
                                         "reserve": reserve})
                    break
                self._tq.take(req)
                fresh = self._pool.ensure(req.uid, need) or []
                cow_dst = fresh[0] if hit.cow_tokens else 0
                if hit.cow_tokens:
                    self.stats["prefix_cow_copies"] += 1
                owned = self._pool.owned(req.uid)
                row = np.full(self._mps, -1, np.int32)
                row[:len(owned)] = owned
                self._tbl_host[slot] = row
                self._pending, self._cache = self._admit_prefix_fn(
                    self._cache, self._pending, jnp.int32(slot),
                    jnp.asarray(row), jnp.int32(warm),
                    jnp.int32(hit.cow_page), jnp.int32(cow_dst),
                    jnp.asarray(prompt[-1]), jnp.asarray(tail == 0))
                c1, chunked = warm, tail > 0
                if not chunked:   # fully cached: nothing new to publish
                    self._pool.publish_prefix(req.uid, prompt[:-1])
            elif self.paged:
                # mid-prefill lanes only hold pages for what is actually
                # cached so far; the rest is provisioned chunk-by-chunk by
                # _advance_prefill (growth-class: like decode page growth
                # it may dip into the admission watermark's headroom)
                need = (self._pool.pages_for(c1) if chunked
                        else self._pages_needed(c1, max_new - gen_carry))
                if not self._pool.can_alloc(need,
                                            self.kv_watermark + reserve):
                    # head-of-line wait for pages (watermark/reserve hit)
                    self.telem.c_watermark.inc()
                    if tr is not None:
                        tr.instant(self.telem.tid_engine, "pool_watermark",
                                   args={"uid": req.uid, "need": need,
                                         "free": self._pool.free_pages,
                                         "reserve": reserve})
                    break
                self._tq.take(req)
                pages = self._pool.alloc(need, owner=req.uid)
                row = np.full(self._mps, -1, np.int32)
                row[:len(pages)] = pages
                self._tbl_host[slot] = row
                # chunked: prefill just prompt[:c1]; the pending it sets is
                # a placeholder, rewritten in-graph by the finishing chunk
                self._pending, self._cache = self._admit_paged_fn(
                    self.params, self._cache, self._pending,
                    jnp.asarray(prompt[:c1 + 1]), jnp.int32(slot),
                    jnp.asarray(row))
                # one-shot cold admission caches the whole prompt prefix in
                # one go — publish it for the next tenant immediately
                if self.prefix_cache and not chunked:
                    self._pool.publish_prefix(req.uid, prompt[:-1])
            else:
                self._tq.take(req)
                if chunked:
                    self._cache = self._admit_chunk_fn(
                        self.params, self._cache, jnp.asarray(prompt[:c1]),
                        jnp.int32(slot))
                else:
                    self._pending, self._cache = self._admit_fn(
                        self.params, self._cache, self._pending,
                        jnp.asarray(prompt), jnp.int32(slot))
            orig_prompt, gen0, blocks0, wall0, seq0 = self._preempted.pop(
                req.uid, (prompt, [], 0, 0.0, None))
            if seq0 is None:             # fresh request; replays keep their
                self._admit_seq += 1     # original admission seniority
                seq0 = self._admit_seq
            self._slots[slot] = _Slot(uid=req.uid, prompt=orig_prompt,
                                      max_new=max_new, gen=list(gen0),
                                      blocks=blocks0, wall_s=wall0,
                                      cache_len=c1,
                                      admit_seq=seq0,
                                      pf_prompt=prompt if chunked else None,
                                      pf_pos=c1 if chunked else None,
                                      handle=hq)
            t_adm = self.clock()
            if hq is not None:
                if hq.t_admit is None:   # FIRST admission only: a preempted
                    hq.t_admit = t_adm   # replay keeps its original wait
                    self.telem.h_queue_wait.observe(
                        t_adm - (hq.t_submit
                                 if hq.t_submit is not None else t_adm))
                if not chunked and hq.t_prefill_done is None:
                    hq.t_prefill_done = t_adm
            # fresh depth-controller state for the recycled lane: a request
            # must not inherit the previous occupant's throttled depth (or a
            # preempted replay its own pre-preemption EMA — prefix replay
            # changes positions, so stale state is not evidence)
            if self._depth is not None:
                self._k_host[slot] = self._depth.k_init
                self._ema_host[slot] = self._depth.ema_init
                self._cool_host[slot] = 0
            # a mid-prefill lane stays done-masked: it rides supersteps
            # inert until its finishing chunk flips it live
            self._done[slot] = chunked
            if tr is not None:
                now = self.clock()
                tr.span(slot, f"admit u{req.uid}", t_a0, now,
                        args={"uid": req.uid, "chunked": chunked,
                              "prefilled": c1})
                tr.async_end("queued", req.uid, now)
                tr.async_begin("prefill", req.uid, now,
                               args={"slot": slot, "chunked": chunked})
                if not chunked:    # one-shot: lane decodes from this tick
                    tr.async_end("prefill", req.uid, now)
                    tr.async_begin("decode", req.uid, now,
                                   args={"slot": slot})

    def _preempt(self, slot: int) -> None:
        """Evict lane `slot` mid-decode: free its pages, unmap its row, and
        re-queue its progress (prompt + generated prefix) at the FRONT of
        the FIFO.  Re-admission replays the prefix via prefill — the same
        tokens at the same positions produce the same KV, so greedy decoding
        continues exactly where it stopped."""
        st = self._slots[slot]
        self._pool.free(st.uid)
        self._tbl_host[slot] = -1
        # carry progress, cost attribution (blocks, wall) AND admission
        # seniority across the preemption: re-admission must not make the
        # victim the "newest" lane again, or two starved lanes ping-pong
        # preempt each other forever — preserving admit_seq makes the
        # globally oldest request strictly win every victim contest, so it
        # always progresses and the system cannot livelock
        self._preempted[st.uid] = (st.prompt, list(st.gen), st.blocks,
                                   st.wall_s, st.admit_seq)
        combined = np.concatenate(
            [st.prompt, np.asarray(st.gen, np.int32)]).astype(np.int32)
        # replays bypass fairness AND the max_queue bound: the request was
        # already admitted once; rejecting or re-queuing it fairly would
        # discard committed work / break the preemption no-livelock argument
        self._tq.push_front(Request(
            uid=st.uid, prompt=combined, max_new=st.max_new,
            tenant=st.handle.tenant if st.handle is not None else "default",
            priority=st.handle.priority if st.handle is not None else 0))
        self._cache = self._reset_fn(self._cache, jnp.int32(slot))
        tr = self.telem.tracer
        if tr is not None:
            now = self.clock()
            tr.instant(slot, "preempt", now,
                       args={"uid": st.uid, "gen_len": len(st.gen),
                             "mid_prefill": st.pf_pos is not None})
            tr.async_end("prefill" if st.pf_pos is not None else "decode",
                         st.uid, now, args={"preempted": True})
            tr.async_begin("queued", st.uid, now, args={"replay": True})
        self._slots[slot] = None
        self._done[slot] = True
        self.stats["preemptions"] += 1

    def _grow_pages(self) -> None:
        """Top every live lane up to the page capacity the NEXT superstep
        can touch: ``sync_every`` blocks each write K+1 eager tokens, so the
        horizon is ``sync_every * (K+1)`` slots — capped by the lane's
        remaining ``max_new`` budget (a lane that can only run r more blocks
        before retiring advances the cache at most r+K slots; growing it
        further would waste pool headroom under pressure).  Adaptive depth
        makes K per-lane: growth sizes each lane for its LIVE depth bound
        (``_lane_growth_k``) instead of the global worst case, so throttled
        low-acceptance lanes release pool headroom to lanes that can
        actually use it.  On pool
        exhaustion, preempt the NEWEST other lane and retry — oldest
        requests keep their pages (no livelock: admission guarantees any
        single request fits the pool).  All row updates of the tick are
        batched into ONE device push (set_block_tables) instead of a
        map_slot_pages dispatch per lane."""
        dirty = False
        for s in sorted((i for i, st in enumerate(self._slots) if st is not None),
                        key=lambda i: self._slots[i].admit_seq):
            st = self._slots[s]
            if st is None or st.pf_pos is not None:
                continue                 # gone, or grown by _advance_prefill
            remaining = st.max_new - len(st.gen)
            if remaining <= 0:           # retires at the next boundary
                continue
            while True:
                got = self._pool.ensure(
                    st.uid, self._pages_needed(st.cache_len, remaining,
                                               k=self._lane_growth_k(s)))
                if got is None:
                    victims = [i for i, v in enumerate(self._slots)
                               if v is not None and i != s]
                    if not victims:      # lone lane: admission sizing makes
                        break            # this unreachable; fail soft
                    self._preempt(max(victims,
                                      key=lambda i: self._slots[i].admit_seq))
                    dirty = True         # preemption unmapped a row
                    continue
                if got:
                    self._sync_row(s, st.uid)
                    dirty = True
                break
        if dirty:
            self._cache = self._set_tbl_fn(self._cache,
                                           jnp.array(self._tbl_host))

    def _sync_row(self, s: int, uid: int) -> None:
        """Mirror lane `s`'s pool ownership into the host block table
        (allocation order == logical order); caller batches the device push
        via ``set_block_tables`` once per tick."""
        owned = self._pool.owned(uid)
        self._tbl_host[s] = -1
        self._tbl_host[s, :len(owned)] = owned

    def _advance_prefill(self) -> None:
        """One batched chunk step: every mid-prefill lane advances by up to
        ``prefill_chunk`` prompt tokens, directly in the live cache.  Lanes
        consuming their last prompt token get their pending token set
        in-graph and flip live for THIS tick's superstep.  Paged lanes are
        provisioned incrementally (``KVPool.ensure``) right before the
        chunk's writes land; on exhaustion the newest other lane is
        preempted (oldest-first service, mirroring ``_grow_pages``).
        Per-tick prefill work is bounded: ONE device dispatch covering at
        most ``num_slots * prefill_chunk`` tokens, however long the
        prompts are."""
        lanes = [s for s, st in enumerate(self._slots)
                 if st is not None and st.pf_pos is not None]
        if not lanes:
            return
        B, T = self.num_slots, self._chunk
        tokens = np.zeros((B, T), np.int32)
        take = np.zeros((B,), np.int32)
        finish_tok = np.zeros((B,), np.int32)
        finished = np.zeros((B,), bool)
        dirty = False
        for s in sorted(lanes, key=lambda i: self._slots[i].admit_seq):
            st = self._slots[s]
            if st is None:               # preempted as a victim below
                continue
            tk, fin, extent = self._prefill_extent(st)
            if self.paged:
                while True:
                    got = self._pool.ensure(st.uid,
                                            self._pool.pages_for(extent))
                    if got is not None:
                        break
                    # a starved prefill lane may only evict STRICTLY NEWER
                    # lanes; with none it WAITS a tick instead of evicting a
                    # senior.  Evicting seniors here livelocks: mid-prefill
                    # eviction loses all prefill progress (decode eviction
                    # keeps its generated tokens, which is why _grow_pages
                    # can afford any-victim), so two long prefills sharing a
                    # tight pool would wipe each other forever at the
                    # finish line.  Seniority is a total order, so the
                    # oldest prefill lane can always clear its path, and
                    # admission sizing guarantees it fits the pool alone.
                    victims = [i for i, v in enumerate(self._slots)
                               if v is not None
                               and v.admit_seq > st.admit_seq]
                    if not victims:
                        break
                    v = max(victims, key=lambda i: self._slots[i].admit_seq)
                    self._preempt(v)
                    # victims are strictly newer and this loop runs in
                    # ascending admit_seq order, so v cannot have been
                    # staged yet — these clears are pure defense in case a
                    # future change reorders the loop or widens victimhood
                    tokens[v] = 0
                    take[v] = 0
                    finished[v] = False
                    dirty = True
                if got is None:
                    continue             # starved: retry next tick
                if got:
                    self._sync_row(s, st.uid)
                    dirty = True
            tokens[s, :tk] = st.pf_prompt[st.pf_pos:st.pf_pos + tk]
            take[s] = tk
            if fin:
                finished[s] = True
                finish_tok[s] = st.pf_prompt[-1]
        if dirty:
            self._cache = self._set_tbl_fn(self._cache,
                                           jnp.array(self._tbl_host))
        if not take.any() and not finished.any():
            return
        t_c0 = self.clock()
        self._pending, self._cache = self._chunk_fn(
            self.params, self._cache, self._pending, jnp.asarray(tokens),
            jnp.asarray(take), jnp.asarray(finish_tok), jnp.asarray(finished))
        t_c1 = self.clock()
        tick_tokens = int(take.sum())
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += tick_tokens
        self.stats["max_tick_prefill_tokens"] = max(
            self.stats["max_tick_prefill_tokens"], tick_tokens)
        tr = self.telem.tracer
        for s in lanes:
            st = self._slots[s]
            if st is None or (not take[s] and not finished[s]):
                continue
            st.pf_pos += int(take[s])
            st.cache_len += int(take[s])
            if tr is not None:
                tr.span(s, "prefill_chunk", t_c0, t_c1,
                        args={"uid": st.uid, "tokens": int(take[s]),
                              "pos": int(st.pf_pos)})
            if finished[s]:
                # the whole prompt prefix is committed in-cache now — make
                # it hittable for the next tenant sharing it
                if self.paged and self.prefix_cache:
                    self._pool.publish_prefix(st.uid, st.pf_prompt[:-1])
                st.pf_pos = None
                st.pf_prompt = None
                self._done[s] = False
                if st.handle is not None and st.handle.t_prefill_done is None:
                    st.handle.t_prefill_done = t_c1
                if tr is not None:
                    tr.async_end("prefill", st.uid, t_c1)
                    tr.async_begin("decode", st.uid, t_c1,
                                   args={"slot": s})

    def _maybe_profile_start(self):
        """Optional ``jax.profiler`` capture window (``profile_dir``): start
        at the first tick, annotate every tick as a step, stop after
        ``profile_steps`` ticks.  A requested capture that fails raises: a
        run asked to trace the device must not pass without the trace."""
        if self.profile_dir and not self._profile_active:
            jax.profiler.start_trace(self.profile_dir)
            self._profile_active = True
            self._profile_left = max(1, int(self.profile_steps))
        if not self._profile_active:
            return None
        return jax.profiler.StepTraceAnnotation(
            "tick", step_num=max(1, int(self.profile_steps))
            - self._profile_left)

    def _maybe_profile_stop(self) -> None:
        if not self._profile_active:
            return
        self._profile_left -= 1
        if self._profile_left <= 0:
            jax.profiler.stop_trace()
            self._profile_active = False
            self.profile_dir = None     # window consumed; do not restart

    def _dispatch_superstep(self) -> None:
        """Dispatch one fused superstep over the live lanes and return
        immediately — the host does NOT wait for the result (``_harvest``
        does, one engine tick later)."""
        budget = np.ones((self.num_slots,), np.int32)
        for s, st in enumerate(self._slots):
            if st is not None:
                budget[s] = st.max_new - len(st.gen)
        if self._depth is not None:
            # per-lane depth ceiling = what growth provisioned pages for;
            # the draft-scan width K_blk is the max ceiling over lanes that
            # can decode this superstep (mid-prefill lanes cannot flip live
            # mid-superstep — _advance_prefill already ran — and free lanes
            # are admitted only at boundaries, so the max over decode lanes
            # is exact, not heuristic)
            kcap = np.full((self.num_slots,), self._k_worst, np.int32)
            kblk = self._depth.k_min
            for s, st in enumerate(self._slots):
                if st is not None and st.pf_pos is None:
                    kcap[s] = self._lane_growth_k(s)
                    kblk = max(kblk, int(kcap[s]))
            # host mirrors go in as copies (jnp.array): on the CPU backend
            # jnp.asarray may alias a numpy buffer, and admission rewrites
            # these mirrors while this superstep is still queued
            res = self._superstep_adaptive_fn(
                self.params, self.state.dvi_params, self._pending,
                self._cache, self.state.buf, jnp.array(self._done),
                jnp.asarray(budget), jnp.array(self._k_host),
                jnp.array(self._ema_host), jnp.array(self._cool_host),
                jnp.asarray(kcap), kblk)
        else:
            res = self._superstep_fn(self.params, self.state.dvi_params,
                                     self._pending, self._cache,
                                     self.state.buf, jnp.array(self._done),
                                     jnp.asarray(budget))
        # engine state advances to the (not yet materialized) outputs; every
        # follow-up device op (admission, reset, next superstep) chains on
        # them without a host round-trip
        self._pending, self._cache = res.pending, res.cache
        self.state.buf = res.buffer
        lanes = [s for s, st in enumerate(self._slots) if st is not None]
        now = self.clock()
        mark = self._clock + (now - self._tick_t0)
        self._inflight = (res, mark, lanes, now)
        self.stats["dispatches"] += 1
        self.stats["peak_live_slots"] = max(self.stats["peak_live_slots"],
                                            len(lanes))

    def _harvest(self) -> List[Completion]:
        """Materialize the in-flight superstep's compact summary (the ONLY
        device->host sync on the continuous hot path), fold it into host
        bookkeeping, retire finished lanes, and manage drafter updates.

        Telemetry rides this same single ``device_get``: the in-graph
        per-block histograms travel with the summary, and a folded drafter
        update's loss metrics are materialized one harvest LATER (by then
        the superstep that consumed the new params has completed, so the
        update must have too — reading its metrics cannot block)."""
        # fold a completed drafter update FIRST — even with no in-flight
        # superstep (engine drained and is being stepped again), so a
        # trained update dispatched on the last tick of a burst is never
        # dropped; the next dispatch below then uses the fresh params
        tr = self.telem.tracer
        fold_note = None
        if self._update_inflight is not None:
            (self.state.dvi_params, self.state.opt_state,
             self.state.baseline, m_dev, t_disp_u, step_u) = \
                self._update_inflight
            self._update_inflight = None
            t_fold = self.clock()
            # update "latency" = dispatch -> fold staleness window (how long
            # the engine decoded on the pre-update drafter), a host quantity
            self.telem.h_update_span.observe(t_fold - t_disp_u)
            if tr is not None:
                tr.span(self.telem.tid_train, f"drafter_update t{step_u}",
                        t_disp_u, t_fold, args={"step": step_u}, cat="train")
            fold_note = (m_dev, t_disp_u, t_fold, step_u)
        if self._inflight is None:
            if fold_note is not None:
                self._train_staged.append(fold_note)
            return []
        inflight, self._inflight = self._inflight, None
        res = inflight[0]
        staged, self._train_staged = self._train_staged, []
        t0 = self.clock()
        fetched = self._phase(
            "sync_wait", jax.device_get, (
                (res.done, res.gen_count, res.gen_buf, res.lane_blocks,
                 res.lane_committed, res.lane_accepted, res.lane_drafted,
                 res.k_lane, res.accept_ema, res.k_cool,
                 res.accept_hist, res.depth_hist, res.buffer["count"]),
                [note[0] for note in staged]),
            label="dvi.tick.harvest.sync_wait")
        now = self.clock()
        self.stats["host_syncs"] += 1
        self.stats["sync_wait_s"] += now - t0
        self.telem.h_sync_wait.observe(now - t0)
        return self._phase("fold", self._fold, inflight, fetched, staged,
                           now, fold_note, label="dvi.tick.harvest.fold")

    def _fold(self, inflight: tuple, fetched: tuple, staged: list,
              now: float, fold_note: Optional[tuple]) -> List[Completion]:
        """Fold a harvested superstep's fetched summary (and the staged
        drafter-update metrics) into host bookkeeping: stream committed
        tokens, retire finished lanes, maybe dispatch the next drafter
        update."""
        tr = self.telem.tracer
        _, clock_mark, lanes, t_disp_wall = inflight
        main, m_host = fetched
        (done_np, cnt_np, gen_np, blocks_np, committed_np, accepted_np,
         drafted_np, k_np, ema_np, cool_np, ahist_np, dhist_np,
         buf_count) = main
        for note, m in zip(staged, m_host):
            self._fold_train_metrics(m, note[1], note[2], note[3])
        # fold the in-graph per-block histograms (length K_blk+1, which may
        # be below k_max+1 when an adaptive dispatch specialized shallower)
        for i, n in enumerate(ahist_np):
            self.telem.h_block_accept.add(int(i), int(n))
        for i, n in enumerate(dhist_np):
            self.telem.h_block_depth.add(int(i), int(n))
        # iterations the superstep actually executed (it exits early once
        # every lane is done): the longest-lived lane saw all of them
        self.stats["steps"] += int(blocks_np.max(initial=0))
        # engine-resident time since the dispatch (caller time excluded)
        wall = self._clock + (now - self._tick_t0) - clock_mark
        total_blocks = int(blocks_np.sum())
        wall_share = wall / max(total_blocks, 1)

        outs: List[Completion] = []
        k_seen: List[int] = []
        for s in lanes:                  # only lanes occupied at dispatch:
            st = self._slots[s]          # slots admitted since then (into
            if st is None:               # previously-free lanes) rode along
                continue                 # masked done and carry no results
            if st.pf_pos is not None:    # mid-prefill at dispatch: rode the
                continue                 # superstep masked done — NOT done
            nb = int(blocks_np[s])
            st.blocks += nb
            st.wall_s += wall_share * nb
            st.cache_len += int(committed_np[s])
            st.gen.extend(int(t) for t in gen_np[s, :int(cnt_np[s])])
            if st.handle is not None and int(cnt_np[s]) > 0:
                # stream the freshly committed chunk to the handle NOW (the
                # superstep boundary) — consumers see tokens per harvest,
                # not per completion; feed is monotone so replays are safe
                first = st.handle.t_first_token is None
                st.handle.feed(st.gen)
                if first and st.handle.t_first_token is not None:
                    self.telem.h_ttft.observe(
                        st.handle.t_first_token
                        - (st.handle.t_submit
                           if st.handle.t_submit is not None
                           else st.handle.t_first_token))
            self.stats["blocks"] += nb
            self.stats["committed"] += int(committed_np[s])
            self.stats["accepted"] += int(accepted_np[s])
            # EXACT draft accounting, counted in-graph: sum of the depth
            # each LIVE block actually ran at (a lane that went done early
            # rides the rest of the superstep without inflating its drafts;
            # an adaptive lane counts its per-block k, not the global K)
            self.stats["drafted"] += int(drafted_np[s])
            self._slot_accepted[s] += int(accepted_np[s])
            self._slot_drafted[s] += int(drafted_np[s])
            self._slot_committed[s] += int(committed_np[s])
            self._slot_blocks[s] += nb
            k_seen.append(int(k_np[s]))
            if tr is not None:
                tr.span(s, "superstep", t_disp_wall, now,
                        args={"uid": st.uid, "blocks": nb,
                              "committed": int(committed_np[s]),
                              "accepted": int(accepted_np[s]),
                              "k": int(k_np[s])})
                if self._depth is not None and \
                        int(k_np[s]) != int(self._k_host[s]):
                    tr.instant(
                        s, f"depth {int(self._k_host[s])}->{int(k_np[s])}",
                        now, args={"uid": st.uid, "ema": float(ema_np[s])})
            # fold the lane's post-superstep controller state into the host
            # mirror (masked lanes came back unchanged, so this is exact)
            if self._depth is not None:
                self._k_host[s] = k_np[s]
                self._ema_host[s] = ema_np[s]
                self._cool_host[s] = cool_np[s]
            if done_np[s]:               # EOS or budget, detected in-graph
                gen = np.asarray(st.gen, np.int32)
                comp = self._complete(
                    st.uid, np.concatenate([st.prompt, gen]), gen,
                    len(st.gen) / max(st.blocks, 1), st.wall_s)
                outs.append(comp)
                self._finish_handle(st.uid, comp)
                self.stats["requests"] += 1
                if self.paged:
                    self._pool.free(st.uid)   # copy-free eviction: pages
                    self._tbl_host[s] = -1    # recycle host-side
                self._cache = self._reset_fn(self._cache, jnp.int32(s))
                self._slots[s] = None
                self._done[s] = True

        if k_seen:
            km = float(np.mean(k_seen))
            self.stats["k_mean"].append(km)
            self.telem.g_depth_mean.set(km)

        # drafter update cadence: maybe dispatch the next update — WITHOUT
        # blocking on it; the engine decodes one superstep on stale
        # dvi_params while the optimizer runs (folded at the top of the
        # next harvest, i.e. the next superstep boundary)
        self._blocks_since_update += int(blocks_np.max(initial=0))
        if (self.learn and self._blocks_since_update >= self.update_every
                and int(buf_count) > 0):
            self._blocks_since_update = 0
            t_disp_u = self.clock()
            step_u = self._step_host
            self._key, sub = jax.random.split(self._key)
            new_dvi, new_opt, new_base, m_dev = self._update_fn(
                self.params, self.state.dvi_params, self.state.opt_state,
                self.state.buf, self.state.baseline, self.state.step, sub)
            self._update_inflight = (new_dvi, new_opt, new_base, m_dev,
                                     t_disp_u, step_u)
            self.state.step = self.state.step + 1
            self.stats["updates"] += 1
            self._note_update_dispatched()
            self.telem.g_buffer.set(int(buf_count))
            if tr is not None:
                tr.instant(self.telem.tid_train, "update_dispatch", t_disp_u,
                           args={"step": step_u, "buffer": int(buf_count)},
                           cat="train")
        if fold_note is not None:
            self._train_staged.append(fold_note)
        return outs

    def _phase(self, name: str, fn, *a, label: Optional[str] = None):
        """``fn(*a)``; with the tracer on, inside a phase span ``name`` on
        the engine track that is also the profiler annotation ``label``
        (``dvi.tick.<name>`` by default)."""
        tr = self.telem.tracer
        if tr is None:
            return fn(*a)
        with tr.phase(self.telem.tid_engine, name,
                      label or f"dvi.tick.{name}"):
            return fn(*a)

    def _step_continuous(self) -> List[Completion]:
        """One tick (``_tick``).  With ``profile_dir`` set, the tick is a
        step of the profiler's capture window; with the tracer on, it is
        the profiler annotation ``dvi.tick``."""
        step = self._maybe_profile_start()
        if step is None:
            return self._annotated_tick()
        try:
            with step:
                return self._annotated_tick()
        finally:
            self._maybe_profile_stop()

    def _annotated_tick(self) -> List[Completion]:
        if self.telem.tracer is None:
            return self._tick()
        with jax.profiler.TraceAnnotation("dvi.tick"):
            return self._tick()

    def _tick(self) -> List[Completion]:
        """Pre-admit arrivals into already-free lanes (their prefill
        dispatches queue behind the in-flight superstep — host work
        overlaps device compute), harvest the in-flight superstep, retire
        finished lanes, grow paged lanes (preempting if the pool runs dry),
        admit into freshly freed lanes, advance mid-prefill lanes by one
        chunk, and dispatch the next superstep."""
        self._tick_t0 = tick0 = self.clock()
        tr = self.telem.tracer
        tid_e = self.telem.tid_engine if tr is not None else 0
        try:
            # pre-admission reserves the live lanes' worst-case growth
            # demand (paged): a new request must not grab pages this tick's
            # growth pass would claw back by preempting the admitted lane
            self._phase("pre_admit", self._admit_waiting,
                        self._growth_reserve() if self.paged else 0)
            outs = self._phase("harvest", self._harvest)
            # cancellation boundary: the harvest just retired the in-flight
            # superstep, so lanes can be torn down without racing device
            # reads of their pages; queued cancels drop out of the tenant
            # queue before this tick's growth/admission see them
            self._phase("sweep_cancels", self._sweep_cancels)
            # grow BEFORE admitting: admission then sees the true residual
            # capacity, instead of grabbing pages that live lanes
            # immediately claw back by preempting the just-admitted lane.
            # Mid-prefill lanes' imminent chunk demand stays reserved even
            # here: _advance_prefill consumes it right after this admission.
            if self.paged:
                self._phase("grow_pages", self._grow_pages)
            self._phase("admit", self._admit_waiting,
                        self._prefill_reserve() if self.paged else 0)
            # chunked prefill interleaves with supersteps: one bounded
            # chunk step per tick, then the superstep over decoding lanes
            # (lanes whose prefill finished this tick included)
            self._phase("prefill_chunk", self._advance_prefill)
            if any(st is not None and st.pf_pos is None
                   for st in self._slots):
                self._phase("dispatch", self._dispatch_superstep)
        finally:
            dt = self.clock() - self._tick_t0
            self._clock += dt
            self.stats["tick_s"].append(dt)
            self.telem.h_tick.observe(dt)
            t = self.telem
            t.g_live.set(self.active_slots)
            t.g_queue.set(len(self._tq))
            if self.paged:
                # free counts evictable cached pages — what admission may
                # actually use; g_kv_cached breaks out the warm subset
                t.g_kv_used.set(self._pool.used_pages)
                t.g_kv_free.set(self._pool.available_pages)
                t.g_kv_cached.set(self._pool.cached_pages)
                ev = self._pool.evictions
                if ev != self._evict_seen:
                    self.stats["prefix_evictions"] += ev - self._evict_seen
                    self._evict_seen = ev
            if tr is not None:
                tr.span(tid_e, "tick", tick0, tick0 + dt,
                        args={"live": self.active_slots,
                              "queued": len(self._tq),
                              "annotation": "dvi.tick"})
            self._tick_t0 = None
        return outs

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------

    def step(self) -> List[Completion]:
        if self.scheduler == "continuous":
            return self._step_continuous()
        return self._step_sync()

    @property
    def busy(self) -> bool:
        # _update_inflight keeps the engine busy so the driver steps once
        # more and the final drafter update of a burst is actually folded;
        # queued-but-cancelled requests keep _tq non-empty until the sweep
        # finalizes them, so the stepping loop is guaranteed to reach them
        return (bool(self._tq) or self.active_slots > 0
                or self._inflight is not None
                or self._update_inflight is not None
                or any(self._queue.values()))

    def run(self, max_steps: int = 10**9) -> List[Completion]:
        done: List[Completion] = []
        for _ in range(max_steps):
            if not self.busy:
                break
            done.extend(self.step())
        return done

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    def reset_stats(self) -> None:
        """Zero every registry metric, rolling window, and per-slot counter
        (e.g. after a warm-up run); jit caches, drafter state, and live
        slots are untouched.  The key set comes from the ONE canonical
        schema (``telemetry.LEGACY_STATS`` + the registry declarations), so
        it can never drift from the live stats view."""
        self.telem.registry.reset()
        self.stats.reset()           # registry metrics again (idempotent)
        self.train_history.clear()   # + the deques behind the facade
        self._slot_accepted[:] = 0
        self._slot_drafted[:] = 0
        self._slot_committed[:] = 0
        self._slot_blocks[:] = 0

    def _fold_train_metrics(self, m: dict, t_disp: float, t_fold: float,
                            step_u: int) -> None:
        """Publish one materialized drafter-update metrics dict (already on
        host) into the ``dvi_train_*`` gauges + the bounded history."""
        t = self.telem

        def g(key):
            return float(m[key]) if key in m else 0.0

        t.g_loss.set(g("loss"))
        t.g_loss_kl.set(g("kl"))
        t.g_loss_ce.set(g("l_pg"))       # reward-masked CE component
        t.g_loss_pg.set(g("pg_on"))      # on-policy policy-gradient term
        t.g_lambda_pg.set(g("lam_pg"))
        t.g_lambda_kl.set(g("lam_kl"))
        t.g_beta.set(g("beta"))
        t.g_acc_batch.set(g("acc_rate"))
        t.g_ema_before.set(g("baseline_before"))
        t.g_ema_after.set(g("baseline_after"))
        t.g_buffer.set(g("buffer_count"))
        t.g_gnorm.set(g("gnorm"))
        self.train_history.append({
            "step": step_u,
            "phase": schedule_mod.phase_info(
                step_u, self.model.cfg.dvi)["phase"],
            "loss": g("loss"), "loss_kl": g("kl"), "loss_ce": g("l_pg"),
            "loss_pg": g("pg_on"), "acceptance_batch": g("acc_rate"),
            "ema_before": g("baseline_before"),
            "ema_after": g("baseline_after"),
            "buffer_count": g("buffer_count"),
            "span_s": t_fold - t_disp})

    def train_telemetry(self) -> dict:
        """DVI training-loop telemetry: schedule phase, per-component
        losses, acceptance EMA around updates, plus the bounded per-update
        ``history``.  Materializes any still-staged update metrics — may
        synchronize with the device, so call OFF the serving hot path
        (between bursts, at shutdown, in benches)."""
        staged, self._train_staged = self._train_staged, []
        for (_, t_disp, t_fold, step_u), m in zip(
                staged, jax.device_get([note[0] for note in staged])):
            self._fold_train_metrics(m, t_disp, t_fold, step_u)
        t = self.telem
        ph = schedule_mod.phase_info(self._step_host, self.model.cfg.dvi)
        return {
            "updates": int(self.stats["updates"]),
            "step": self._step_host,
            "phase": ph["phase"], "phase_name": ph["phase_name"],
            "lambda_pg": ph["lambda_pg"], "lambda_kl": ph["lambda_kl"],
            "beta": ph["beta"],
            "loss": t.g_loss.value, "loss_kl": t.g_loss_kl.value,
            "loss_ce": t.g_loss_ce.value, "loss_pg": t.g_loss_pg.value,
            "acceptance_batch": t.g_acc_batch.value,
            "acceptance_ema_before": t.g_ema_before.value,
            "acceptance_ema_after": t.g_ema_after.value,
            "buffer_count": t.g_buffer.value,
            "history": list(self.train_history),
        }

    def metrics_snapshot(self) -> dict:
        """JSON-able snapshot of every registry metric (see telemetry.py
        for the schema reference)."""
        return self.telem.snapshot()

    def render_prometheus(self) -> str:
        return self.telem.render_prometheus()

    def write_metrics(self, path: str) -> None:
        self.telem.write_metrics(path)

    def trace_dict(self) -> Optional[dict]:
        """The Chrome-trace dict (``telemetry=True`` runs only)."""
        tr = self.telem.tracer
        return tr.to_dict() if tr is not None else None

    def write_trace(self, path: str) -> None:
        tr = self.telem.tracer
        if tr is None:
            raise ValueError("tracing is off — construct the engine with "
                             "telemetry=True to record a trace")
        tr.write(path)

    @property
    def acceptance(self) -> float:
        return self.stats["accepted"] / max(self.stats["drafted"], 1)

    @property
    def slot_acceptance(self) -> np.ndarray:
        """(num_slots,) lifetime acceptance rate per lane."""
        return self._slot_accepted / np.maximum(self._slot_drafted, 1)

    def adaptive_stats(self) -> dict:
        """Depth-controller observability: the current per-slot depth /
        acceptance-EMA, per-slot depth trajectory summaries (mean depth over
        the slot's live blocks), and drafted-vs-committed efficiency — how
        many committed tokens each drafted token bought, the quantity
        adaptive depth exists to raise.  Meaningful (but still reported,
        pinned at k_spec) when ``adaptive_k=False``."""
        drafted = max(self.stats["drafted"], 1)
        recent = list(self.stats["k_mean"])
        return {
            "adaptive": self._depth is not None,
            "k_min": self._depth.k_min if self._depth else
                self.model.cfg.dvi.k_spec,
            "k_max": self._k_worst,
            "k_lane": self._k_host.copy(),
            "accept_ema": self._ema_host.copy(),
            "slot_mean_depth": self._slot_drafted
                / np.maximum(self._slot_blocks, 1),
            "slot_draft_efficiency": self._slot_committed
                / np.maximum(self._slot_drafted, 1),
            "mean_depth": self.stats["drafted"]
                / max(self.stats["blocks"], 1),
            "draft_efficiency": self.stats["committed"] / drafted,
            "k_mean_recent": float(np.mean(recent)) if recent else 0.0,
        }

    def kv_stats(self) -> dict:
        """Paged-pool observability: utilization / watermark / fragmentation
        plus scheduler-level preemption and concurrency counters."""
        if not self.paged:
            return {"paged": False}
        live_tokens = sum(st.cache_len for st in self._slots if st is not None)
        out = self._pool.utilization(live_tokens)
        out.update(paged=True, preemptions=self.stats["preemptions"],
                   peak_live_slots=self.stats["peak_live_slots"])
        return out

    def latency_percentiles(self) -> dict:
        """Percentiles over the most recent ``latency_window`` completions
        (rolling window, so long-running engines stay O(window) memory)."""
        lats = np.asarray(self.stats["latencies"], np.float64)
        if lats.size == 0:
            # well-defined empty result: all-zero percentiles + an explicit
            # count so callers can tell "no completions yet" from "fast"
            return {"p50_s": 0.0, "p95_s": 0.0, "mean_s": 0.0, "count": 0}
        return {"p50_s": float(np.percentile(lats, 50)),
                "p95_s": float(np.percentile(lats, 95)),
                "mean_s": float(np.mean(lats)),
                "count": int(lats.size)}

    def tick_percentiles(self) -> dict:
        """Engine-tick wall-time percentiles over the most recent
        ``latency_window`` ticks — the block-step cadence jitter that
        chunked prefill bounds (a one-shot prefill of a long prompt shows
        up as one fat tick; chunking spreads it)."""
        ts = np.asarray(self.stats["tick_s"], np.float64)
        if ts.size == 0:
            return {"p50_s": 0.0, "p95_s": 0.0, "max_s": 0.0, "count": 0}
        return {"p50_s": float(np.percentile(ts, 50)),
                "p95_s": float(np.percentile(ts, 95)),
                "max_s": float(ts.max()),
                "count": int(ts.size)}

    def dispatch_stats(self) -> dict:
        """Host/device interplay on the continuous hot path: how often the
        host synced with the device, how long it sat blocked, and how many
        superstep dispatches covered the executed block-steps.  `steps` is
        scheduler ITERATIONS (batch block-steps executed); `blocks` in
        `stats` is the per-live-lane count used for MAT/acceptance."""
        steps = max(self.stats["steps"], 1)
        return {
            "sync_every": self.sync_every,
            "steps": self.stats["steps"],
            "dispatches": self.stats["dispatches"],
            "host_syncs": self.stats["host_syncs"],
            "host_syncs_per_100_blocks":
                100.0 * self.stats["host_syncs"] / steps,
            "host_wait_s": self.stats["sync_wait_s"],
            "prefill_chunk": self._chunk,
            "prefill_chunks": self.stats["prefill_chunks"],
            "prefill_tokens": self.stats["prefill_tokens"],
            "max_tick_prefill_tokens":
                self.stats["max_tick_prefill_tokens"],
        }
