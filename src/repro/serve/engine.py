"""ServeEngine — the orchestration layer of a three-layer serving stack.

The paper's core result is that ONE set of system settings (KMP_AFFINITY +
taskset + all2all **cache** mode) keeps every (Nproc × Nthread)
factorization near practical peak — because the SETTINGS layer (how memory
is managed) and the WORKLOAD layer (how users factor their work) are
cleanly separable knobs, and only the former needs global tuning.  The
serving stack is now built on exactly that separation:

- **Settings layer — `serve.pool.PagePool`**: page allocation, refcounts,
  the prefix trie, copy-on-write matching, LRU eviction, and the
  byte-denominated budget (``kv_dtype``: float32 | bfloat16 | int8 — PR 4's
  memory-representation knob, the analogue of the paper's decisive memory
  mode).  Set once per engine; identical beneath every policy.
- **Workload layer — `serve.scheduler`**: admission order and pack order
  are a pluggable policy object (``scheduler=``): ``FifoScheduler``
  (default — bit-identical to the PR 1–4 engine), ``PrefixAwareScheduler``
  (bounded-window reordering so requests sharing a cached or in-flight
  prefix land in the same wave), ``SloScheduler`` (interactive-vs-batch
  classes via ``Request.priority``).  Policies return ORDERINGS only; the
  engine keeps the mechanism, so every policy inherits the
  no-mid-flight-OOM and single-trace guarantees.
- **Client layer — `serve.handle.RequestHandle`**: ``submit()`` returns an
  int-compatible streaming handle — ``handle.tokens()`` iterates tokens
  incrementally (driving ``tick()`` on starvation), ``handle.cancel()``
  releases the request's suffix pages mid-flight (refcount-safe: shared
  prefix pages survive for siblings and the cache), ``handle.done`` /
  ``handle.result()`` complete the lifecycle.  ``run()``/``tick()`` batch
  drivers are unchanged.  End-to-end client: ``examples/serve_stream.py``.

What the orchestrator itself still owns is the device contract (unchanged
from PR 1–4, and the reason any policy mix stays near peak):

- **One compiled program** (PR 2): each tick packs a fixed token budget
  ``T`` (``token_budget``) with decode tokens FIRST (a decoding slot emits
  every tick; prefill never stalls it) and prefill chunks (≤
  ``prefill_chunk`` per slot) in the leftover budget, driving a single
  jit'd ``(T,)`` ragged step (``serve_step.make_ragged_step``) with
  per-token (slot, position, validity) vectors.  The mix — and now the
  policy ordering it — is pure data, so exactly ONE program is ever traced
  (``stats["traces"]``).
- **One resident working set** (PR 3): the paged KV pool doubles as a
  refcounted, copy-on-write prefix cache — thousands of requests sharing a
  system prompt read it from resident pages instead of re-prefilling (the
  "all2all cache mode" of the engine).  Match/index/evict policy lives in
  the pool; the engine runs the control-plane programs (COW page copy,
  slot reset, and the tiered page movers below) the pool's decisions
  require.
- **A second tier under pressure** (``host_pages=`` — this PR, the paper's
  MCDRAM cache mode made literal): eviction DEMOTES refcount-0 prefix
  pages to a host-RAM tier instead of dropping them — trie entry and int8
  scale rows intact — and a prefix hit on a host-resident page PROMOTES it
  back, the scatter issued at admission so jax async dispatch overlaps the
  copy with the tick's compute.  Only a miss in BOTH tiers re-prefills.
  The pool decides which pages move (``serve.pool`` events, drained in
  chronological order before any other device mutation of the round); the
  engine owns the bytes: one jitted gather and one donated jitted scatter
  (``serve_step.make_page_gather`` / ``make_page_insert``), host storage a
  plain dict of numpy pages.  A promoted slot is packed from the NEXT tick
  (``_Slot.ready_tick``) — the overlap window — while data dependency
  through the donated state keeps any schedule correct; transcripts stay
  token-identical to the untiered engine because packing composition never
  changes sampling (packing-invariant by construction since PR 2).
- **Half-or-better bytes per resident token** (PR 4): int8 pools quantize
  at KV-write time (write-quantize → paged read-dequant → COW-with-scales),
  so the byte-denominated budget holds 2-4× the pages — more concurrent
  decoders and more resident prefix pages from the same memory.
- **More than one accepted token per page-stream** (``spec_k=`` — this PR):
  decode is memory-bound on KV bytes, so once a slot's pages stream for
  its one decode token, verifying k more tokens against that same stream
  is near-free.  The lifecycle is draft → verify → accept/rollback:
  the SpeculativeScheduler wrapper DRAFTS k continuation tokens per
  decoding slot by prompt lookup over the slot's own prompt+output
  history (no second model), the engine packs them at the slot's next
  consecutive positions in the leftover token budget (decode-first and
  prefill priority are untouched — speculation is just a packing policy)
  and VERIFIES all chains in the one forward via a (B, 1+spec_k)
  ``logit_idx`` — row j is the model's prediction given the draft prefix
  up to j.  The engine ACCEPTS the longest agreeing prefix plus the
  correction/bonus token sampled from the first disagreeing row, and for
  rejected tails ROLLS BACK kpos/slen via one more control-plane program
  (``serve_step.make_spec_rollback``) so stale rows are dead until
  overwritten.  Per-(request, position) seeded sampling keeps transcripts
  token-identical with speculation on or off at any temperature, and the
  serve-path trace count stays at exactly one.

- **Graceful degradation under pressure (``preempt=`` / ``max_queue=`` /
  ``deadline_ticks=`` / ``fault_injector=`` — this PR)**: the paper's
  headline is not one fast point but STABLE performance — every
  oversubscribed (Nproc × Nthread) mix degrades smoothly instead of
  collapsing, because the settings layer manages contention.  The serving
  analogue is a full failure-handling lifecycle over the same seams:

  * **Slot preemption** — when an admission round leaves the head
    candidate stalled on pages (or a slot) that IN-FLIGHT work holds, and
    the candidate strictly outranks a running request, the scheduler's
    ``preempt_order`` picks a victim decoding slot (default: lowest
    priority, then youngest; Slo-family policies never victimize the
    interactive class).  The victim's private pages — non-indexed,
    refcount-1: its generated tokens and prompt duplicates — PARK to the
    host tier through the same demote-gather machinery as cache demotion
    (``PagePool.park``), its shared prefix pages just drop a refcount, the
    slot frees, and the request re-queues at the head with its generated
    tokens intact.  On re-admission the parked pages promote back
    (``unpark`` — the scatter overlapping the tick like any promotion), or
    — if the park was lost or the cached prefix shrank beneath it — the
    engine RE-PREFILLS from the request's own token history (prompt, the
    position-L handoff duplicate, then every generated token but the
    last) and resumes decoding at its preempted position.  Per-(request,
    ordinal) seeded sampling makes the transcript token-identical either
    way; the movers are the PR 7 gather/scatter and the donated reset, so
    ``stats["traces"]`` stays 1.
  * **Deadlines and backpressure** — ``submit(deadline_ticks=)`` arms an
    absolute completion deadline: an expired request (queued or live)
    aborts with a typed ``DeadlineExceeded`` carrying its partial output;
    ``max_queue=`` bounds the admission queue, failing over-capacity
    submits fast with ``EngineOverloaded``; a request whose footprint can
    NEVER fit rejects at submit with ``RequestTooLarge`` (all in
    ``serve.errors``, each subclassing the builtin its untyped predecessor
    raised).
  * **Fault injection** — ``fault_injector=`` (``serve.chaos.
    FaultInjector``) draws deterministic, seed-keyed faults each tick:
    forced allocation failures (the tick admits nothing), random cancels
    (typed ``Cancelled``), host-tier eviction storms (parks survive; the
    cache tier is lost), and stalled ticks (the clock — and deadlines —
    advance; nothing runs).  Every fault degrades throughput, never
    correctness: completed requests stay token-identical and both tiers
    drain to zero leaked pages (tests/test_chaos.py holds the line).

``core.autotune.select_serve_defaults`` emits the tuned-once serving config
(token_budget × prefill_chunk × page_size × kv_dtype × scheduler).

**Tensor parallelism (``mesh=``)**: the single compiled ragged step shards
over the KV-head axis.  Pool layout: ``kp``/``vp`` pages and their int8
scale pools ``ks``/``vs`` split along their KV-head dim (device d holds
heads ``[d·kvH/N, (d+1)·kvH/N)`` of every page); block tables, positions,
and fill counts replicate.  The contract is strict layering: the HOST
bookkeeping (PagePool / Scheduler / slot state / byte budget) is global and
never sees the device count, while the DEVICE programs run under the serve
mesh rules with no device-count-dependent partial-sum order (the attention
output is replicated before the one head-contracting einsum) and the bias
add and rotation rounded once (``attention._serving_qkv``).  The
invariance suite (``tests/test_serve_tp.py``: the qwen1.5-4b smoke model,
d 64, 4 heads) finds outputs bit-identical on 1, 2 and 4 CPU devices.
That is not a promise at other sizes: a projection split by head is a
smaller matrix product, which XLA may accumulate in another order, and
on a TPU at full depth the outputs part in the last bits (PERF.md).
``stats`` reports
``kv_shards`` / ``n_devices`` / ``kv_pool_bytes_per_device``.

**Statically gated invariants**: the contracts above — one serve-path
trace, donated in-place pool updates, the page lifecycle, scheduler
protocol conformance — are also PROVEN statically by ``repro.analysis``
(``python -m repro.analysis``; see ``src/repro/analysis/README.md`` for
the rules and suppression syntax), which CI runs on every change.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelCfg
from repro.kernels.flash_attention import ragged_visits
from repro.models import model as M
from repro.serve.errors import (DeadlineExceeded, EngineOverloaded,
                                RequestTooLarge)
from repro.serve.handle import Request, RequestHandle
from repro.serve.pool import (PagePool, _PrefixNode, kv_bytes_per_token,
                              kv_page_bytes)
from repro.serve import spans
from repro.serve.scheduler import EngineView, Scheduler, make_scheduler
from repro.serve.serve_step import (STATE_DONATE_ARGNUM, make_page_copy,
                                    make_page_gather, make_page_insert,
                                    make_ragged_step, make_slot_reset,
                                    make_spec_rollback)

from repro.core.roofline import KV_ITEMSIZE

__all__ = ["ServeEngine", "kv_page_bytes", "kv_bytes_per_token"]


class _LogitsRow:
    """One (V,) logits row of a tick's serve step, left on the device.

    ``token`` is the row's greedy token, which the step took on the device
    and the tick fetched with the others; the row's logits come to the host
    only when asked for (``np.asarray(row)``), each copy counted in
    ``stats["host_logit_rows"]``.  Valid for the tick that made it."""

    __slots__ = ("token", "shape", "_logits", "_at", "_stats")

    def __init__(self, logits: jax.Array, at: tuple, token: int,
                 stats: Dict):
        self.token = token
        self.shape = logits.shape[-1:]
        self._logits, self._at, self._stats = logits, at, stats

    def __array__(self, dtype=None, copy=None):
        self._stats["host_logit_rows"] += 1
        return np.asarray(self._logits[self._at], dtype)


@dataclasses.dataclass
class _Slot:
    req: Request
    pages: List[int]
    fill: int = 0  # prompt tokens in cache (matched prefix + prefilled)
    pos: int = 0  # next absolute write position (== len(prompt) at decode)
    last_tok: int = 0
    # prefix-cache bookkeeping: the trie node matching the indexed prefix so
    # far (None = this slot's prefix is owned elsewhere, stop indexing) and
    # how many of this slot's leading pages are on that trie chain
    node: Optional[_PrefixNode] = None
    n_indexed: int = 0
    # first tick this slot may be packed: admissions that promoted host-tier
    # pages wait one tick so the promotion copy overlaps the current tick's
    # compute instead of stalling it (correctness never depends on this —
    # the data dependency through the donated state orders the scatter)
    ready_tick: int = 0
    # what prefill actually feeds the pack: the prompt, normally — or, for
    # a preempt-resume that lost its park, the request's replayed history
    # (prompt + position-L handoff duplicate + generated tokens[:-1]),
    # whose length IS the preempted write position
    prefill_tokens: Optional[np.ndarray] = None
    # decode input to resume from when prefill completes (a re-prefilled
    # preemptee resumes from its LAST generated token, not the prompt tail)
    resume_tok: Optional[int] = None

    def __post_init__(self):
        if self.prefill_tokens is None:
            self.prefill_tokens = self.req.prompt


class ServeEngine:
    def __init__(self, params, cfg: ModelCfg, *, batch_size: int = 4,
                 cache_len: int = 256, page_size: int = 16,
                 max_pages: Optional[int] = None, prefill_chunk: int = 32,
                 token_budget: int = 128, greedy: bool = True,
                 flash_decode: bool = False, prefix_cache: bool = True,
                 kv_dtype: Optional[str] = None,
                 scheduler=None, mesh=None, host_pages: int = 0,
                 spec_k: int = 0, preempt: bool = True,
                 max_queue: Optional[int] = None, fault_injector=None):
        self.params = params
        self.cfg = cfg
        # KV-head tensor parallelism (``mesh=`` — a jax.sharding.Mesh, e.g.
        # launch.mesh.make_mesh((N,), ("model",))).  The DEVICE side splits:
        # the paged KV pools (kp/vp and int8 ks/vs) shard along the KV-head
        # axis (serve_step.STATE_AXES), every compiled program runs under
        # ``use_mesh(mesh, make_serve_rules(mesh))``, and the Pallas flash
        # kernels enter through the shard_map wrappers in
        # serve.decode_attention — each device holds and attends over
        # 1/N of each pool page's heads.  The HOST side does NOT split:
        # PagePool, Scheduler, slot bookkeeping, and the page budget are
        # global and device-count-agnostic — page ids name whole logical
        # pages whose bytes happen to live N-ways split, so admission,
        # eviction, prefix sharing, and COW decisions are identical at any
        # device count (the invariance suite asserts token-identical output
        # across 1/2/4 devices).  Layers whose KV-head count the mesh does
        # not divide keep replicated pools (sanitize_spec drops the axis).
        self.mesh = mesh
        self._kv_shards = 1
        self._rules = None
        if mesh is not None:
            from repro.parallel.sharding import make_serve_rules

            self._rules = make_serve_rules(mesh)
            ax = self._rules["act_kv_heads"]
            for a in ((ax,) if isinstance(ax, str) else tuple(ax)):
                self._kv_shards *= mesh.shape[a]
        self.B = batch_size
        self.cache_len = cache_len
        self.page_size = page_size
        self.chunk = prefill_chunk
        self.budget = token_budget
        self.greedy = greedy
        # workload-policy layer: admission + pack ordering (None/"fifo" is
        # the PR 1-4 behavior, bit-identical).  Policies that keep the
        # protocol's identity orders (fifo: all three; prefix-aware: the
        # pack pair) let the hot loop skip building per-tick EngineView
        # snapshots and the O(queue) candidate/validation/rebuild work —
        # a deep backlog costs the default policy nothing extra per tick
        self.scheduler = make_scheduler(scheduler)
        # speculative decoding rides the policy layer: ``spec_k=`` wraps the
        # resolved policy in a SpeculativeScheduler (prompt-lookup drafts of
        # depth k), or pass a SpeculativeScheduler as ``scheduler=`` directly
        # — either way the engine reads the depth off the policy object
        from repro.serve.scheduler import SpeculativeScheduler

        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k and not isinstance(self.scheduler, SpeculativeScheduler):
            self.scheduler = SpeculativeScheduler(self.scheduler,
                                                  spec_k=spec_k)
        self.scheduler_name = getattr(self.scheduler, "name",
                                      type(self.scheduler).__name__)
        # the fast-path probes must see THROUGH the speculative wrapper: its
        # orderings delegate verbatim, so a wrapped default policy still
        # earns the no-EngineView hot loop
        probe = (self.scheduler.inner
                 if isinstance(self.scheduler, SpeculativeScheduler)
                 else self.scheduler)
        cls = type(probe)
        self._default_admit = (
            getattr(cls, "admission_order", None) is Scheduler.admission_order)
        self._default_pack = (
            getattr(cls, "decode_order", None) is Scheduler.decode_order
            and getattr(cls, "prefill_order", None) is Scheduler.prefill_order)
        # paged-pool storage representation: None follows the activation
        # dtype (the unquantized default); "int8" is the headline — half-or-
        # better bytes per resident token, quantized at KV-write time so
        # prefill, decode, prefix hits, and COW all share one representation
        self.kv_dtype = str(jnp.dtype(kv_dtype or cfg.dtype))
        if self.kv_dtype not in KV_ITEMSIZE:
            raise ValueError(f"unsupported kv_dtype {self.kv_dtype!r} "
                             f"(pick from {sorted(KV_ITEMSIZE)})")
        if token_budget < batch_size:
            raise ValueError(
                f"token_budget={token_budget} < batch_size={batch_size}: "
                "every decoding slot needs one pack entry per tick")
        self.pps = -(-cache_len // page_size)  # block-table width
        self._has_paged = any(
            blk.mixer == "attn" and blk.attn.window is None
            for st in cfg.stages for blk in st.pattern)
        # prefix sharing needs EVERY layer's state to live in shareable
        # pages: recurrent mixers and windowed circular buffers are per-slot
        # and cannot be inherited, so hybrids serve with sharing off
        all_global = self._has_paged and all(
            blk.mixer == "attn" and blk.attn.window is None
            for st in cfg.stages for blk in st.pattern)
        self.prefix_cache = bool(prefix_cache) and all_global
        # speculative decoding has the same applicability gate, for the
        # dual reason: rolling back a rejected draft tail is a kpos/slen
        # metadata edit for paged global attention, but recurrent state and
        # windowed circular buffers advance destructively — there is
        # nothing to roll back to.  Hybrids silently serve unspeculated
        # (same convention as prefix_cache; stats["spec_k"] reports 0).
        self._spec_k = (int(getattr(self.scheduler, "spec_k", 0))
                        if all_global else 0)
        self._draft = getattr(self.scheduler, "draft", None)
        if self._draft is None:
            self._spec_k = 0
        # preemption shares the applicability gate: re-prefill resume
        # replays history through the ragged pack, and a parked page only
        # captures the ENTIRE per-position state when every layer is paged
        # global attention (recurrent / windowed state has no page to park)
        self.preempt = bool(preempt) and all_global
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        self.fault_injector = fault_injector
        # uid -> park record for preempted requests awaiting re-admission:
        # "slots" (host slots holding pages [page0, page0+len) — None when
        # the park failed and resume must re-prefill), "pos"/"last_tok"
        # (the decode state to resume from)
        self._preempted: Dict[int, dict] = {}
        self._chaos_alloc_fail = False
        # the page budget is a BYTE budget: the default pool spends the same
        # bytes the unquantized (activation-dtype) pool would, so an int8
        # pool holds ~2-4× the pages — more concurrent requests and more
        # refcount-0 prefix-cache pages stay resident before eviction (the
        # serving analogue of fitting the working set into fast memory).
        # Floor: never BELOW the worst-case base_pages — a widening kv_dtype
        # (e.g. a float32 pool on a bfloat16 model) keeps every slot
        # admissible without queueing, at the cost of exceeding the
        # activation-dtype byte budget (visible in stats["kv_pool_bytes"])
        # the budget is priced on GLOBAL (unsharded) page bytes on purpose:
        # a sharded pool's per-device bytes shrink by the shard count, but
        # pricing pages per-device would let n_pages drift with the device
        # count and break the cross-device-count token-identity contract —
        # per-device footprint is reported in stats instead
        base_pages = batch_size * self.pps
        if max_pages is not None:
            self.n_pages = max_pages
        elif self._has_paged:
            ref = kv_page_bytes(cfg, page_size, str(jnp.dtype(cfg.dtype)))
            act = kv_page_bytes(cfg, page_size, self.kv_dtype)
            self.n_pages = max(base_pages, base_pages * ref // max(act, 1))
        else:
            self.n_pages = base_pages
        # memory-settings layer: one pool object owns every page policy.
        # ``host_pages`` > 0 adds the host-RAM tier (the paper's cache
        # mode): eviction demotes instead of dropping, prefix hits promote
        # back; the tier only matters with the prefix cache on — without an
        # index there is nothing matchable to keep warm one tier down
        self.host_pages = host_pages if self.prefix_cache else 0
        self.pool = PagePool(self.n_pages, page_size,
                             index_enabled=self.prefix_cache,
                             host_pages=self.host_pages)
        self._host_store: Dict[int, Dict] = {}  # host tier page bytes
        self.queue: deque = deque()
        self.slots: List[Optional[_Slot]] = [None] * batch_size
        self._uid = 0
        self.completion_order: List[int] = []
        self._state = None  # persistent: the pool doubles as the prefix cache
        self._stats = {"ragged_ticks": 0, "ticks": 0, "packed_tokens": 0,
                       "traces": 0,
                       # the ragged kernel's work: runs and KV tiles one
                       # layer's call visits, summed over ragged ticks
                       "attn_runs": 0, "attn_kv_tiles": 0,
                       "pages_in_use_peak": 0, "admissions": 0,
                       "prefix_hits": 0, "prefix_tokens_reused": 0,
                       "cow_copies": 0, "cancelled": 0,
                       # tiered-cache accounting: admissions that hit the
                       # HOST tier (between warm and cold), and how many
                       # pages those hits promoted back to the device tier
                       "host_hits": 0, "host_pages_promoted": 0,
                       "host_pool_pages": self.host_pages,
                       "scheduler": self.scheduler_name,
                       # speculative-decoding accounting: draft depth in
                       # effect (0 = off/gated-off), draft tokens packed
                       # into verify rows, accepted vs rejected split,
                       # rollback dispatches, and how many (slot, tick)
                       # sampling opportunities there were — emitted tokens
                       # divided by sampled_slot_ticks is the accepted-
                       # tokens-per-tick headline (> 1 only via drafts)
                       "spec_k": self._spec_k, "spec_drafted": 0,
                       "spec_accepted": 0, "spec_rejected": 0,
                       "spec_rollbacks": 0, "sampled_slot_ticks": 0,
                       # logits rows copied to the host: one per token
                       # drawn at a temperature (greedy tokens come from
                       # the step's own argmax)
                       "host_logit_rows": 0,
                       # robustness accounting: slot preemptions and how
                       # their resumes went (park promoted back vs history
                       # re-prefilled), deadline aborts, backpressure
                       # rejections, and injected-fault counts
                       "preemptions": 0, "resumes": 0,
                       "resume_park_hits": 0, "resume_reprefills": 0,
                       "preempt_pages_parked": 0, "deadline_expired": 0,
                       "overload_rejections": 0, "chaos_alloc_fails": 0,
                       "chaos_cancels": 0, "chaos_evict_storms": 0,
                       "chaos_stalled_ticks": 0,
                       # memory-representation accounting: bytes of paged KV
                       # one token occupies (streams per context token at
                       # decode) and the pool's byte footprint at this dtype
                       "kv_dtype": self.kv_dtype,
                       "kv_bytes_per_token": kv_bytes_per_token(
                           cfg, self.kv_dtype),
                       "kv_pool_bytes": self.n_pages * kv_page_bytes(
                           cfg, page_size, self.kv_dtype),
                       # tensor-parallel accounting: shard count of the
                       # paged pools' KV-head axis and one device's share
                       # of the pool bytes (== kv_pool_bytes at 1 device)
                       "kv_shards": self._kv_shards,
                       "n_devices": (mesh.devices.size
                                     if mesh is not None else 1),
                       "kv_pool_bytes_per_device":
                           self.n_pages * kv_page_bytes(
                               cfg, page_size, self.kv_dtype,
                               self._kv_shards)}
        # per-token / per-tick logs for the latency benchmark:
        # token_log rows are (uid, tick index, spans.now()); tick_log rows
        # are (had outstanding prefill at tick start, spans.now() at tick end)
        self.token_log: List[tuple] = []
        self.tick_log: List[tuple] = []

        def _count_traces(fn):
            @functools.wraps(fn)  # keeps the program's name
            def counted(*a):
                self._stats["traces"] += 1  # python body runs at trace time
                return fn(*a)
            return counted

        # donate the state (serve_step.STATE_DONATE_ARGNUM): the KV page
        # pools, int8 scale pools, and recurrent-state carries dominate the
        # pytree and must be updated in place, not copied, on every tick of
        # the hot loop (no-copy contract asserted by pointer identity in
        # tests/test_kv_quant.py)
        donate = (STATE_DONATE_ARGNUM,)
        # width = most tokens one slot contributes to a pack: a prefill
        # chunk plus its handoff decode token, or a decode token plus its
        # spec_k draft chain — whichever is wider (compile-time constant)
        self._ragged_step = jax.jit(
            _count_traces(make_ragged_step(
                cfg, width=max(prefill_chunk + 1, 1 + self._spec_k),
                flash_decode=flash_decode)),
            donate_argnums=donate)
        # under ``mesh=`` the decode state is built by one program, in place
        # on its shards: a pool larger than one device's free memory never
        # sits whole on one device
        self._sharded_state = (jax.jit(self._fresh_state)
                               if mesh is not None else None)
        # counted on the host from each pack, with the kernel's own run cut
        # and tile sizes at the first paged layer's per-device shapes
        self._kernel_visits = None
        if flash_decode and self._has_paged:
            a = next(blk.attn for st in cfg.stages for blk in st.pattern
                     if blk.mixer == "attn" and blk.attn.window is None)
            kvH = a.num_kv_heads
            if kvH % self._kv_shards == 0:  # else the pools stay replicated
                kvH //= self._kv_shards
            self._kernel_visits = functools.partial(
                ragged_visits, kvH=kvH, G=a.num_heads // a.num_kv_heads,
                hd=a.head_dim, page=page_size, pps=self.pps,
                kv_dtype=self.kv_dtype)
        # control-plane programs (admission reset, COW page copy) — separate
        # from the serve path, each traced at most once
        self._reset = jax.jit(make_slot_reset(cfg), donate_argnums=(0,))
        self._copy = jax.jit(make_page_copy(cfg), donate_argnums=(0,))
        # tiered page movers: demotion gather (state stays live) and
        # promotion scatter (state donated, pools update in place); page id
        # is data, so each traces at most once for the engine's lifetime
        self._gather_page = jax.jit(make_page_gather(cfg))
        self._insert_page = jax.jit(make_page_insert(cfg),
                                    donate_argnums=(0,))
        # speculative rejection: invalidate kpos/slen for rolled-back draft
        # tails (pools/scales untouched); dispatched only on ticks that
        # rejected drafts, traced at most once like the other movers
        self._spec_rollback = jax.jit(make_spec_rollback(cfg),
                                      donate_argnums=(0,))

    # -- public surface ---------------------------------------------------
    def submit(self, prompt, max_tokens: int = 16, eos_id=None, *,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               seed: Optional[int] = None,
               priority: int = 0,
               deadline_ticks: Optional[int] = None) -> RequestHandle:
        """Queue one request; returns a streaming ``RequestHandle`` (an
        ``int`` subclass carrying the uid, so legacy id-keyed drivers are
        unchanged).  ``priority`` is the scheduling class read by
        ``SloScheduler`` (>= 1 interactive, 0 batch; FIFO ignores it).

        ``deadline_ticks`` arms a completion deadline that many engine
        ticks from now: a request still unfinished when it expires aborts
        with a typed ``DeadlineExceeded`` (partial output attached) raised
        from its handle.  A request whose footprint can NEVER fit rejects
        immediately with ``RequestTooLarge``; with ``max_queue=`` set, an
        over-capacity submit rejects with ``EngineOverloaded`` instead of
        growing the backlog unboundedly (both in ``serve.errors``)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        if prompt.size + max_tokens > self.cache_len:
            raise RequestTooLarge(
                f"len(prompt)+max_tokens = {prompt.size + max_tokens} "
                f"exceeds cache_len={self.cache_len}")
        if temperature is None:
            temperature = 0.0 if self.greedy else 1.0
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if deadline_ticks is not None and deadline_ticks < 1:
            raise ValueError(
                f"deadline_ticks must be >= 1, got {deadline_ticks}")
        if (self.max_queue is not None
                and len(self.queue) >= self.max_queue):
            self._stats["overload_rejections"] += 1
            raise EngineOverloaded(
                f"admission queue full ({len(self.queue)} >= "
                f"max_queue={self.max_queue}): shed load or retry later")
        self._uid += 1
        req = Request(self._uid, prompt, max_tokens, eos_id,
                      temperature=temperature, top_k=top_k, seed=seed,
                      priority=priority, submitted_at=spans.now())
        if deadline_ticks is not None:
            req.deadline_tick = self._stats["ticks"] + deadline_ticks
        # admission reserves only the unmatched suffix on a prefix hit, but
        # cache contents churn before this request reaches the head of the
        # queue — validate against the cold-start worst case
        need = self._pages_needed(req)
        if need > self.n_pages:
            raise RequestTooLarge(
                f"request needs {need} pages but the pool has only "
                f"{self.n_pages} (raise max_pages or shrink the request)")
        self.queue.append(req)
        return RequestHandle(req, self)

    def cancel(self, handle_or_uid, *,
               error: Optional[Exception] = None) -> bool:
        """Stop a request and release what it holds.  Queued: dequeued
        before it ever takes pages (a preempted request's parked host pages
        are dropped with it).  Admitted: its slot is freed and its page
        references dropped — shared prefix pages survive for siblings and
        for the cache (refcounted), its own indexed prompt pages stay
        resident as cache, and everything else returns to the free list.
        Returns False (no-op) for finished or unknown requests.

        ``error`` marks an ENGINE-initiated abort (fault injection, an
        administrative kill): it lands on the request record and is raised
        by ``result()``/``tokens()``.  A client ``handle.cancel()`` passes
        no error and keeps the historical partial-return contract."""
        uid = int(handle_or_uid)
        for i, req in enumerate(self.queue):
            if req.uid == uid:
                del self.queue[i]
                self._drop_park_record(uid)
                self._finish_cancel(req, error)
                return True
        for b, s in enumerate(self.slots):
            if s is not None and s.req.uid == uid:
                self._finish_cancel(s.req, error)
                self._release_slot(b)
                return True
        return False

    def _finish_cancel(self, req: Request,
                       error: Optional[Exception]) -> None:
        req.cancelled = req.done = True
        if error is not None:
            if hasattr(error, "tokens") and not error.tokens:
                error.tokens = list(req.out_tokens)
            req.error = error
        self._stats["cancelled"] += 1

    def _drop_park_record(self, uid: int) -> None:
        """Forget a preempted request's park (cancel / deadline / drain):
        free its host slots and, since hevicts need no device state, drain
        them right away when nothing else is pending."""
        rec = self._preempted.pop(uid, None)
        if rec is None or rec["slots"] is None:
            return
        self.pool.drop_parked(rec["slots"])
        if self.pool.events and all(
                ev[0] == "hevict" for ev in self.pool.events):
            for ev in self.pool.drain_events():
                self._host_store.pop(ev[1], None)

    @property
    def stats(self) -> Dict:
        """Engine counters merged with the pool's (read-only snapshot)."""
        return {**self._stats, **self.pool.stats}

    # -- pool passthroughs (PR 1-4 surface; tests and drivers use these) --
    @property
    def _ref(self) -> np.ndarray:
        return self.pool._ref

    @property
    def _free(self) -> List[int]:
        return self.pool._free

    @property
    def cached_pages(self) -> int:
        """Pages currently held by the prefix index."""
        return self.pool.cached_pages

    @property
    def reclaimable_pages(self) -> int:
        """Free pages plus refcount-0 cached pages — the allocator can hand
        all of these out; equals ``n_pages`` whenever no request is live."""
        return self.pool.reclaimable_pages

    def drop_prefix_cache(self) -> int:
        """Discard every refcount-0 cached page in BOTH tiers (A/B runs,
        tests).  Returns the number of device pages returned to the free
        list."""
        n = self.pool.drop_cache()
        for ev in self.pool.drain_events():  # hevicts only: free host bytes
            self._host_store.pop(ev[1], None)
        return n

    def _apply_pool_events(self, state):
        """Apply the pool's tier-traffic log to device state IN ORDER,
        before any other device mutation of the admission round: a demoted
        page's bytes are gathered out BEFORE its freed device page can be
        reused (the free-then-realloc chain inside one round is resolved by
        chronology), a promoted page's bytes scatter into its newly
        allocated device page, an hevicted slot's host bytes are dropped.
        The gather is materialized to numpy (host RAM IS the tier); the
        scatter donates the state, so pools update in place and jax's async
        dispatch overlaps the copy with the tick that follows."""
        with spans.span(spans.MOVERS):
            for ev in self.pool.drain_events():
                if ev[0] == "demote":
                    _, page, slot = ev
                    rows = self._gather_page(state, np.int32(page))
                    self._host_store[slot] = jax.tree.map(np.asarray, rows)
                elif ev[0] == "promote":
                    _, slot, page = ev
                    state = self._insert_page(
                        state, self._host_store.pop(slot), np.int32(page))
                else:  # ("hevict", slot)
                    self._host_store.pop(ev[1], None)
        return state

    # -- admission --------------------------------------------------------
    def _pages_needed(self, req: Request, matched_pages: int = 0) -> int:
        """Pages the request must RESERVE: its full footprint minus the
        ``matched_pages`` shared prefix pages it maps instead of allocating."""
        if not self._has_paged:
            return 0
        total = -(-(len(req.prompt) + req.max_tokens) // self.page_size)
        return total - matched_pages

    def _view(self, include_queue: bool = True) -> EngineView:
        # pack-order consultations get an empty queue (documented on
        # EngineView): packing is a slots concern, and copying a deep
        # backlog every tick would tax the hot loop for nothing
        return EngineView(
            queue=tuple(self.queue) if include_queue else (),
            slot_requests=tuple(s.req if s is not None else None
                                for s in self.slots),
            slot_fill=tuple(s.fill if s is not None else 0
                            for s in self.slots),
            budget=self.budget, chunk=self.chunk, page_size=self.page_size,
            match_len=self.pool.probe_prefix_len,
            match_split=self.pool.probe_prefix_split)

    def _pack_order(self, order, slots_in: List[int],
                    fn_name: str) -> List[int]:
        """A pack order must PERMUTE the engine-computed slot list: a
        duplicate would pack (and sample) a slot twice, an omission would
        stall a decoding slot — both break invariants every policy
        inherits, so they fail loudly here instead of corrupting output."""
        order = list(order)
        if sorted(order) != sorted(slots_in):
            raise ValueError(
                f"{self.scheduler_name}: {fn_name} must permute "
                f"{slots_in}, got {order}")
        return order

    def _admission_candidates(self) -> List[Request]:
        """Consult the scheduler for this round's candidate order (indices
        into the queue snapshot), validated to a duplicate-free in-range
        sequence.  The policy proposes; admission still disposes: the
        engine walks candidates in the returned order and STOPS at the
        first whose page demand exceeds supply, so no policy can overcommit
        the pool or bypass the reservation discipline."""
        view = self._view()
        order = list(self.scheduler.admission_order(view))
        n = len(view.queue)
        if len(set(order)) != len(order) or any(
                not (0 <= i < n) for i in order):
            raise ValueError(
                f"{self.scheduler_name}: admission_order returned "
                f"{order!r} for a {n}-deep queue")
        return [view.queue[i] for i in order]

    def _admit_round(self, state):
        """Admit scheduler-ordered queue candidates into free slots while
        the pages each actually needs — its unmatched suffix, after the
        longest-cached-prefix match — fit in free + evictable pages (no
        mid-flight OOM, no starving the admission round on pages a prefix
        hit would never use).  FIFO order reproduces the PR 1-4 strict
        head-of-line behavior bit for bit.

        A PREEMPTED candidate (a ``_preempted`` record exists) re-admits
        one of two ways: its park promotes back — trie pages cover the
        front, unparked pages the middle, fresh pages the tail, and decode
        resumes at the recorded position on the next tick — or, when the
        park was lost or the cached prefix shrank beneath it, it
        re-prefills its replayed history (``_Slot.prefill_tokens``) and
        resumes from its last generated token.  Either way the transcript
        continues token-identically (per-(request, ordinal) seeded
        sampling)."""
        if not self.queue or all(s is not None for s in self.slots):
            return state  # nothing to admit: the policy is not consulted
        mask = np.zeros(self.B, bool)
        rows = np.full((self.B, self.pps), self.n_pages, np.int32)
        plen = np.zeros(self.B, np.int32)
        # unused COW pairs keep the n_pages sentinel: kernels.ops.copy_pages
        # turns them into self-copy no-ops, so the op is one fixed-width trace
        cow_src = np.full(self.B, self.n_pages, np.int32)
        cow_dst = np.full(self.B, self.n_pages, np.int32)
        cow_pins: List[int] = []
        n_cow = 0
        # default (FIFO) admission peeks the queue head and poplefts in
        # O(1) — the PR 1-4 loop verbatim; only a reordering policy pays
        # for the candidate snapshot, validation, and queue rebuild
        cands = None if self._default_admit else self._admission_candidates()
        admitted: set = set()
        ci = 0
        for b in range(self.B):
            if self.slots[b] is not None:
                continue
            if cands is None:
                if not self.queue:
                    continue
                req = self.queue[0]
            else:
                if ci >= len(cands):
                    continue
                req = cands[ci]
            rec = self._preempted.get(req.uid)
            node, mpages, matched, cow = self.pool.match_prefix(req.prompt)
            if rec is not None:
                cow = None  # a resumed request never COWs: its coverage is
                # already decided by the park or its own replayed history
            # a HOST-tier hit is the third candidate class between warm and
            # cold: the pages are matchable but each costs one device page
            # to promote, so they count as demand, not as supply
            n_host = sum(1 for p in mpages if self.pool.is_host(p))
            parked = rec["slots"] if rec is not None else None
            resume_hit = (parked is not None
                          and len(mpages) >= rec["page0"])
            if resume_hit:
                # promote-resume: trie pages cover [0, mp), the park covers
                # [page0, page0+len(parked)) — drop the overlap (the trie's
                # copy wins: no promotion copy for pages both tiers hold),
                # unpark the rest, allocate out to the full footprint
                mp = len(mpages)
                keep = parked[mp - rec["page0"]:]
                ncover = rec["page0"] + len(parked)
                need = -(-(len(req.prompt) + req.max_tokens)
                         // self.page_size) - ncover
                demand = need + len(keep) + n_host
            else:
                need = self._pages_needed(req, matched_pages=len(mpages))
                demand = need + n_host
            if cow is not None and need + n_host > self.pool.available(
                    mpages + [cow[0]]):
                cow = None  # pinning the COW source would leave the pool
                # short one page: forgo the partial-page reuse (it is an
                # optimization; the full-page match alone always fits)
            if demand > self.pool.available(mpages):
                break  # stop at the first infeasible candidate: the pool's
                # reservation discipline outranks any policy's ordering
            if cands is None:
                self.queue.popleft()
            else:
                ci += 1
                admitted.add(req.uid)
            if req.admitted_at is None:  # a resumed preemptee keeps its first
                req.admitted_at = spans.now()
            mpages = self.pool.acquire(mpages)  # +1 ref each; promotes
            # host hits (events drained into device state in the epilogue)
            if n_host:
                self._stats["host_hits"] += 1
                self._stats["host_pages_promoted"] += n_host
            if rec is not None:
                self._preempted.pop(req.uid)
                self._stats["resumes"] += 1
            if resume_hit:
                if len(keep) < len(parked):
                    self.pool.drop_parked(parked[:len(parked) - len(keep)])
                pages = mpages + self.pool.unpark(keep) \
                    + self.pool.alloc(need)
                rows[b, :len(pages)] = pages
                plen[b] = rec["pos"]
                self.slots[b] = _Slot(
                    req, pages, fill=len(req.prompt), pos=rec["pos"],
                    last_tok=rec["last_tok"], node=node,
                    n_indexed=len(mpages),
                    # the unpark scatters overlap this tick's compute,
                    # exactly like a host-tier prefix promotion
                    ready_tick=self._stats["ticks"] + 1)
                mask[b] = True
                self._stats["admissions"] += 1
                self._stats["resume_park_hits"] += 1
                if matched:
                    self._stats["prefix_hits"] += 1
                    self._stats["prefix_tokens_reused"] += matched
                continue
            ptoks, rtok = req.prompt, None
            if rec is not None:
                # park lost (or a hole opened between the trie match and
                # the park): abandon what is left and RE-PREFILL the
                # request's replayed history — prompt, the position-L
                # handoff duplicate, then every generated token but the
                # last, whose turn as decode input comes at resume.  Its
                # length IS the preempted write position, so the handoff
                # below lands exactly where the uninterrupted run was.
                if parked is not None:
                    self.pool.drop_parked(parked)
                ptoks = np.concatenate(
                    [req.prompt, req.prompt[-1:],
                     np.asarray(req.out_tokens[:-1], np.int32)])
                rtok = int(rec["last_tok"])
                self._stats["resume_reprefills"] += 1
            if cow is not None:
                self.pool.share([cow[0]])  # pin the COW source vs eviction
                cow_pins.append(cow[0])
            alloc = self.pool.alloc(need)  # arrives refcounted
            if cow is not None:
                cow_src[b], cow_dst[b] = cow[0], alloc[0]
                matched += cow[1]
                n_cow += 1
            pages = mpages + alloc
            rows[b, :len(pages)] = pages
            plen[b] = matched
            s = _Slot(req, pages, fill=matched, node=node,
                      n_indexed=len(mpages),
                      prefill_tokens=ptoks, resume_tok=rtok,
                      # a promotion's scatter overlaps this tick's compute:
                      # hold the slot out of the pack until the next tick
                      ready_tick=(self._stats["ticks"] + 1 if n_host
                                  else self._stats["ticks"]))
            if matched >= len(ptoks):
                # whole prompt cached: straight to decode, same resume
                # scheme as a completed prefill (last token, position L)
                s.pos = len(ptoks)
                s.last_tok = int(ptoks[-1])
            self.slots[b] = s
            mask[b] = True
            self._stats["admissions"] += 1
            if matched:
                self._stats["prefix_hits"] += 1
                self._stats["prefix_tokens_reused"] += matched
        if mask.any():
            if admitted:
                self.queue = deque(r for r in self.queue
                                   if r.uid not in admitted)
            self._stats["pages_in_use_peak"] = max(
                self._stats["pages_in_use_peak"], self.pool.pages_in_use)
            # tier traffic first: demote gathers must read pages before the
            # COW copy / reset / tick can overwrite them, promote scatters
            # must land in the state the tick consumes
            state = self._apply_pool_events(state)
            if n_cow:
                # device-side ordering is by data dependency (copy feeds the
                # reset feeds the tick), so the host may unpin right away
                state = self._copy(state, cow_src, cow_dst)
                self._stats["cow_copies"] += n_cow
            self.pool.release(cow_pins)
            state = self._reset(state, self._template, mask, rows, plen)
        return state

    # -- preemption -------------------------------------------------------
    def _admit(self, state):
        """Admission with a preemption backstop: when a round leaves the
        head candidate stalled on pages (or a slot) that IN-FLIGHT work
        holds, and the candidate STRICTLY outranks a running victim, the
        victim is preempted and the round re-runs.  Strict priority is the
        anti-thrash rule — equal classes never preempt each other, so two
        starved peers cannot swap one slot forever; it also means the
        default priority-0 world never preempts at all, keeping the PR 1-8
        behavior bit-identical unless the workload opts into classes."""
        if self._chaos_alloc_fail:
            return state  # injected allocation failure: the tick admits
            # nothing (and preempts nothing — a fault starves progress,
            # never correctness)
        state = self._admit_round(state)
        if not self.preempt:
            return state
        for _ in range(self.B):  # each pass frees one slot at most
            cand = self._stalled_candidate()
            if cand is None:
                break
            b = self._pick_victim(cand)
            if b is None:
                break
            state = self._preempt_slot(b, state)
            state = self._admit_round(state)
        return state

    def _stalled_candidate(self) -> Optional[Request]:
        """The first admission candidate left in the queue after a round —
        the request a preemption would be FOR.  None when the queue is
        empty (a non-empty queue after a round means the round could not
        place its head: no free slot, or infeasible page demand)."""
        if not self.queue:
            return None
        if self._default_admit:
            return self.queue[0]
        cands = self._admission_candidates()
        return cands[0] if cands else None

    def _pick_victim(self, cand: Request) -> Optional[int]:
        """A decoding slot whose preemption would let ``cand`` admit.

        Eligible victims decode (mid-prefill work is all still prompt —
        nothing worth parking) and strictly UNDERRANK the candidate; the
        policy's ``preempt_order`` ranks them (and may exempt slots — Slo
        policies drop the interactive class entirely); the first ranked
        victim whose freed pages close the candidate's gap wins.  The
        priority filter runs before any EngineView is built, so workloads
        that never use classes pay O(batch) per stalled tick, not
        O(queue)."""
        tick = self._stats["ticks"]
        victims = [b for b, s in enumerate(self.slots)
                   if s is not None and s.ready_tick <= tick
                   and s.fill >= len(s.prefill_tokens)
                   and s.req.priority < cand.priority]
        if not victims:
            return None
        po = getattr(self.scheduler, "preempt_order", None)
        view = self._view()
        order = list(po(view, victims) if po is not None
                     else Scheduler.preempt_order(self.scheduler, view,
                                                  victims))
        if len(set(order)) != len(order) or any(
                b not in victims for b in order):
            raise ValueError(
                f"{self.scheduler_name}: preempt_order returned {order!r} "
                f"for victims {victims}")
        for b in order:
            if self._admits_after(cand, self.slots[b]):
                return b
        return None

    def _admits_after(self, req: Request, s: _Slot) -> bool:
        """Would preempting ``s`` make ``req`` admissible?  Counts only
        the pages the victim holds as SOLE owner (shared prefix pages
        survive its release) against the candidate's demand, probed
        without touching LRU state.  Slightly conservative — never
        optimistic enough to preempt a victim for nothing."""
        _, mpages, _ = self.pool._walk_full_pages(req.prompt, touch=False)
        gain = sum(1 for p in s.pages if self.pool.ref(p) == 1)
        n_host = sum(1 for p in mpages if self.pool.is_host(p))
        rec = self._preempted.get(req.uid)
        if (rec is not None and rec["slots"] is not None
                and len(mpages) >= rec["page0"]):
            keep = len(rec["slots"]) - (len(mpages) - rec["page0"])
            ncover = rec["page0"] + len(rec["slots"])
            demand = (-(-(len(req.prompt) + req.max_tokens)
                        // self.page_size) - ncover) + keep + n_host
        else:
            demand = self._pages_needed(
                req, matched_pages=len(mpages)) + n_host
        return demand <= self.pool.available(mpages) + gain

    def _preempt_slot(self, b: int, state):
        """Preempt decoding slot ``b``: park its private pages (the
        coverage of positions [0, pos) beyond its indexed prefix) to the
        host tier, release the rest, and requeue the request AT THE HEAD
        with its generated tokens intact.  The park's demote gathers apply
        immediately — the freed device pages may be reallocated by the
        very next admission round."""
        s = self.slots[b]
        req = s.req
        ncover = -(-s.pos // self.page_size)
        ps = s.n_indexed
        if req.out_tokens:
            parked = self.pool.park(s.pages[ps:ncover])
            self._preempted[req.uid] = {
                "slots": parked, "page0": ps, "pos": s.pos,
                "last_tok": s.last_tok}
            if parked is not None:
                self._stats["preempt_pages_parked"] += len(parked)
                self.pool.release(s.pages[:ps] + s.pages[ncover:])
            else:
                self.pool.release(s.pages)  # host tier absent or full:
                # the record alone still resumes via re-prefill
        else:
            # nothing generated yet: a plain requeue re-admits through the
            # normal path (its prompt pages stay cached for the re-prefill)
            self.pool.release(s.pages)
        self.slots[b] = None
        self.queue.appendleft(req)
        self._stats["preemptions"] += 1
        return self._apply_pool_events(state)

    # -- deadlines / fault injection --------------------------------------
    def _expire_deadlines(self) -> None:
        """Abort every queued or live request whose deadline tick has
        passed: a typed ``DeadlineExceeded`` (partial output attached)
        lands on the request record, raised by its handle's
        ``result()``/``tokens()``.  Parked state is dropped — an expired
        request never resumes."""
        tick = self._stats["ticks"]

        def expire(req: Request) -> None:
            req.error = DeadlineExceeded(
                f"request {req.uid} missed its deadline "
                f"(tick {tick} >= {req.deadline_tick})",
                tokens=req.out_tokens)
            req.done = True
            self._stats["deadline_expired"] += 1

        for req in [r for r in self.queue
                    if r.deadline_tick is not None
                    and tick >= r.deadline_tick]:
            self.queue.remove(req)
            self._drop_park_record(req.uid)
            expire(req)
        for b, s in enumerate(self.slots):
            if (s is not None and s.req.deadline_tick is not None
                    and tick >= s.req.deadline_tick):
                self._release_slot(b)
                expire(s.req)

    def _chaos_tick(self) -> bool:
        """Draw and apply this tick's injected faults (deterministic in
        (seed, tick) — see ``serve.chaos.FaultInjector``).  Returns True
        for a STALLED tick: the engine does nothing but let the clock —
        and with it every deadline — advance."""
        live = ([s.req.uid for s in self.slots if s is not None]
                + [r.uid for r in self.queue])
        f = self.fault_injector.faults(self._stats["ticks"], live)
        if f.get("cancel") is not None:
            from repro.serve.errors import Cancelled

            if self.cancel(f["cancel"], error=Cancelled(
                    f"request {f['cancel']} cancelled by fault injection")):
                self._stats["chaos_cancels"] += 1
        if f.get("evict_storm"):
            self.pool.storm_host_cache()
            self._state = self._apply_pool_events(self._state)
            self._stats["chaos_evict_storms"] += 1
        if f.get("alloc_fail"):
            self._chaos_alloc_fail = True
            self._stats["chaos_alloc_fails"] += 1
        if f.get("stall"):
            self._stats["chaos_stalled_ticks"] += 1
            return True
        return False

    # -- slot lifecycle ---------------------------------------------------
    def _release_slot(self, b: int) -> None:
        s = self.slots[b]
        self.pool.release(s.pages)
        self.slots[b] = None

    def _index_filled_pages(self, s: _Slot) -> None:
        """Insert this slot's freshly completed PROMPT pages into the trie.

        Called whenever ``fill`` advances: every full page now covered by
        prefilled (or inherited) tokens extends the slot's chain, unless an
        equivalent page already exists — then the existing page keeps
        ownership of the prefix and this slot's private duplicate simply
        never enters the index (freed at completion).  Decode tokens never
        advance ``fill``, so generated pages are never indexed — and a
        preempt-resume re-prefill, whose ``prefill_tokens`` replay history
        PAST the prompt, caps indexing at the pure-prompt pages."""
        if s.node is None or not self.prefix_cache:
            return
        P = self.page_size
        limit = min(s.fill, len(s.req.prompt))
        while (s.n_indexed + 1) * P <= limit:
            j = s.n_indexed
            key = tuple(int(t) for t in s.req.prompt[j * P:(j + 1) * P])
            s.node = self.pool.index_page(s.node, key, s.pages[j])
            if s.node is None:
                return
            s.n_indexed += 1

    # -- sampling / bookkeeping -------------------------------------------
    def _sample(self, req: Request, row: _LogitsRow, ordinal: int) -> int:
        """One token from a (V,) logits row: greedy argmax at temperature 0,
        seeded temperature/top-k sampling otherwise.

        ``row`` is a handle on the row, which stays on the device: at
        temperature 0 the token is the step's own argmax (``row.token``, the
        first maximum of the same logits) and nothing is copied; otherwise
        the row is copied to the host and sampled there.

        ``ordinal`` is the emission index within the request (==
        ``len(req.out_tokens)`` at draw time), and the RNG is keyed
        per-(request seed, ordinal) — NOT a per-request sequential stream.
        A sequential generator is packing-invariant only while every slot
        emits exactly one token per tick; speculative acceptance emits a
        whole chain in one tick, and keying each draw by its position in
        the output keeps emission m's randomness identical whether it was
        sampled alone, as a verify row, or re-drawn as the correction after
        a rejected draft.  Consequence: transcripts are token-identical
        with speculation on or off at ANY temperature, not just greedy."""
        if req.temperature == 0.0:
            return row.token
        logit = np.asarray(row, np.float64) / req.temperature
        if req.top_k is not None and req.top_k < logit.size:
            kth = np.partition(logit, -req.top_k)[-req.top_k]
            logit = np.where(logit >= kth, logit, -np.inf)
        logit = logit - logit.max()
        p = np.exp(logit)
        p /= p.sum()
        base = req.seed if req.seed is not None else req.uid
        rng = np.random.default_rng((base, ordinal))
        return int(rng.choice(logit.size, p=p))

    def _finish_token(self, b: int, tok: int, results: Dict) -> None:
        """Book one sampled token for slot ``b``: emit, advance, retire the
        request (releasing its page refs) on EOS / max_tokens."""
        s = self.slots[b]
        req = s.req
        t = spans.now()
        if not req.out_tokens:
            req.first_token_at = t
        req.out_tokens.append(tok)
        s.pos += 1
        self.token_log.append((req.uid, self._stats["ticks"], t))
        if (len(req.out_tokens) >= req.max_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            req.done = True
            results[req.uid] = req.out_tokens
            self.completion_order.append(req.uid)
            self._release_slot(b)
        else:
            s.last_tok = tok

    # -- ragged path ------------------------------------------------------
    def _ragged_tick(self, state):
        """Pack one token budget and run the single ragged program.

        Decode first (no decoding slot ever stalls), then prefill chunks
        until the budget runs out; WITHIN each section the scheduler's
        pack order decides which slot's tokens take the budget first (FIFO:
        slot-index order, bit-identical to PR 2-4).  A slot whose prompt
        completes in this pack appends its first decode token right behind
        it.  Slots admitted on a full prefix hit enter the decode section
        on their very first tick — the whole prefill phase is skipped.

        With speculation on (``spec_k`` > 0) a THIRD section follows:
        leftover budget takes each decoding slot's prompt-lookup draft
        chain at its next consecutive positions, and ``logit_idx`` widens
        to (B, 1+spec_k) so the one forward returns a verify row per draft.
        Decode-first and prefill keep strict priority over drafts — drafts
        are speculative work and only ever consume budget nothing else
        claimed, so non-speculative packing is bit-identical with the
        feature on.  After the step the engine accepts the longest
        agreeing draft prefix (plus the correction/bonus token) and rolls
        back kpos/slen for rejected tails."""
        with spans.span(spans.PACK):
            T, W = self.budget, max(self.chunk + 1, 1 + self._spec_k)
            R = 1 + self._spec_k
            tokens = np.zeros(T, np.int32)
            slot = np.zeros(T, np.int32)
            q_pos = np.zeros(T, np.int32)
            seq_idx = np.full(T, W, np.int32)
            valid = np.zeros(T, bool)
            logit_idx = np.full((self.B, R) if self._spec_k else (self.B,),
                                T, np.int32)
            n = 0
            sampling: List[int] = []
            tick = self._stats["ticks"]
            ready = [b for b, s in enumerate(self.slots)
                     if s is not None and s.ready_tick <= tick
                     and s.fill >= len(s.prefill_tokens)]
            filling = [b for b, s in enumerate(self.slots)
                       if s is not None and s.ready_tick <= tick
                       and s.fill < len(s.prefill_tokens)]
            if self._default_pack:
                decode_order, prefill_order = ready, filling
            else:
                view = self._view(include_queue=False)
                decode_order = self._pack_order(
                    self.scheduler.decode_order(view, ready), ready,
                    "decode_order")
                prefill_order = self._pack_order(
                    self.scheduler.prefill_order(view, filling), filling,
                    "prefill_order")
            for b in decode_order:
                s = self.slots[b]
                tokens[n] = s.last_tok
                slot[n] = b
                q_pos[n] = s.pos
                seq_idx[n] = 0
                valid[n] = True
                if self._spec_k:
                    logit_idx[b, 0] = n
                else:
                    logit_idx[b] = n
                sampling.append(b)
                n += 1
            for b in prefill_order:
                if n >= T:
                    break
                s = self.slots[b]
                L = len(s.prefill_tokens)
                c = min(self.chunk, L - s.fill, T - n)
                tokens[n:n + c] = s.prefill_tokens[s.fill:s.fill + c]
                slot[n:n + c] = b
                q_pos[n:n + c] = s.fill + np.arange(c)
                seq_idx[n:n + c] = np.arange(c)
                valid[n:n + c] = True
                n += c
                s.fill += c
                self._index_filled_pages(s)
                if s.fill >= L:
                    # decode resumes from the last prompt token at position L
                    # (same scheme as the reference engine, for token identity)
                    # — or, for a re-prefilled preemptee, from its last
                    # generated token at its preempted position (L here is the
                    # replayed-history length, which IS that position)
                    s.pos = L
                    s.last_tok = (s.resume_tok if s.resume_tok is not None
                                  else int(s.prefill_tokens[-1]))
                    if n < T:
                        tokens[n] = s.last_tok
                        slot[n] = b
                        q_pos[n] = s.pos
                        seq_idx[n] = c
                        valid[n] = True
                        if self._spec_k:
                            logit_idx[b, 0] = n
                        else:
                            logit_idx[b] = n
                        sampling.append(b)
                        n += 1
            # draft section: leftover budget takes each decoding slot's prompt-
            # lookup chain at its next consecutive positions.  The drafted dict
            # is the tick's DRAFT LEDGER: slot -> proposed tokens, with the
            # verify rows at logit_idx[b, 1:1+k].  Drafts never displace decode
            # or prefill tokens and a lookup miss packs nothing, so this
            # section is free for non-repetitive traffic.
            drafted: Dict[int, List[int]] = {}
            if self._spec_k:
                for b in decode_order:
                    if n >= T:
                        break
                    s = self.slots[b]
                    req = s.req
                    # cap by remaining output (drafting past max_tokens-1 can
                    # never be accepted) and by leftover budget
                    room = min(self._spec_k,
                               req.max_tokens - len(req.out_tokens) - 1, T - n)
                    if room < 1:
                        continue
                    hist = (np.concatenate([req.prompt, np.asarray(
                                req.out_tokens, np.int32)])
                            if req.out_tokens else req.prompt)
                    d = self._draft(hist, room)
                    if not d:
                        continue
                    k = len(d)
                    if __debug__:
                        # the rejected-tail contract (see PagePool.is_indexed):
                        # draft rows land beyond the prompt, in pages the slot
                        # privately owns — never in indexed prefix pages
                        for pi in range((s.pos + 1) // self.page_size,
                                        (s.pos + k) // self.page_size + 1):
                            assert not self.pool.is_indexed(s.pages[pi]), \
                                (b, pi, s.pages[pi])
                    tokens[n:n + k] = d
                    slot[n:n + k] = b
                    q_pos[n:n + k] = s.pos + 1 + np.arange(k)
                    seq_idx[n:n + k] = 1 + np.arange(k)
                    valid[n:n + k] = True
                    logit_idx[b, 1:1 + k] = n + np.arange(k)
                    drafted[b] = d
                    self._stats["spec_drafted"] += k
                    n += k
        results: Dict[int, List[int]] = {}
        if n == 0:
            return state, results
        with spans.span(spans.DISPATCH):
            logits, greedy, state = self._ragged_step(
                self.params, state, tokens, slot, q_pos, seq_idx, valid,
                logit_idx)
        self._stats["ragged_ticks"] += 1
        self._stats["packed_tokens"] += n
        if self._kernel_visits is not None:
            runs, tiles = self._kernel_visits(
                slot, np.where(valid, q_pos + 1, 0))
            self._stats["attn_runs"] += runs
            self._stats["attn_kv_tiles"] += tiles
        if sampling:
            with spans.span(spans.WAIT):
                jax.block_until_ready(greedy)
            with spans.span(spans.FETCH):
                toks = np.asarray(greedy)  # (B,), or (B, R) with spec

            def row(*at):
                return _LogitsRow(logits, at, int(toks[at]), self._stats)

            with spans.span(spans.SAMPLE):
                self._stats["sampled_slot_ticks"] += len(sampling)
                accepted: Dict[int, int] = {}
                for b in sampling:
                    req = self.slots[b].req
                    drafts = drafted.get(b, ())
                    # verify in one pass: row j holds the model's prediction
                    # given the draft prefix d_1..d_j, so sampling row j both
                    # CHECKS draft j+1 and, on mismatch or exhaustion, IS the
                    # correction/bonus token — the chain always emits >= 1
                    j = 0
                    tok = self._sample(req, row(b, 0) if self._spec_k
                                       else row(b), len(req.out_tokens))
                    while True:
                        self._finish_token(b, tok, results)
                        if (self.slots[b] is None or j >= len(drafts)
                                or tok != drafts[j]):
                            break
                        j += 1
                        self._stats["spec_accepted"] += 1
                        tok = self._sample(req, row(b, j),
                                           len(req.out_tokens))
                    accepted[b] = j
                if drafted:
                    # roll back rejected tails: drop kpos/slen for positions at
                    # and beyond the slot's new write position.  Released slots
                    # skip it — admission's reset wipes the whole row anyway.
                    mask = np.zeros(self.B, bool)
                    new_len = np.zeros(self.B, np.int32)
                    for b, d in drafted.items():
                        j = accepted.get(b, 0)
                        if j < len(d):
                            self._stats["spec_rejected"] += len(d) - j
                            s = self.slots[b]
                            if s is not None:
                                mask[b] = True
                                new_len[b] = s.pos
                    if mask.any():
                        state = self._spec_rollback(state, mask, new_len)
                        self._stats["spec_rollbacks"] += int(mask.sum())
        return state, results

    # -- driving ----------------------------------------------------------
    @property
    def idle(self) -> bool:
        """No live slot and nothing queued."""
        return all(s is None for s in self.slots) and not self.queue

    def _ctx(self):
        """Ambient mesh + serve rules for every trace/execute of the
        compiled programs (no-op without ``mesh=``).  Every device program
        — the serve step, slot reset, COW copy, page movers and spec
        rollback — must trace under the SAME context so the lshard
        constraints in the model and the shard_map kernel wrappers see the
        KV-head rule; the sharded state then keeps every program's layout
        consistent via input propagation."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.parallel.sharding import use_mesh

        return use_mesh(self.mesh, self._rules)

    def _ensure_state(self):
        """Decode state is created once and persists for the engine's whole
        life: freeing it between runs would throw away the prefix cache (the
        pool's pages ARE the cached data).

        Under ``mesh=`` the freshly built state is committed to its
        NamedShardings (serve_step.decode_state_specs: pools split on the
        KV-head axis, per-slot bookkeeping replicated) and the params are
        committed replicated; every jit'd program then inherits the layout
        from its committed operands — no per-call in_shardings needed, and
        donation keeps the sharded pools updating in place."""
        if self._state is None:
            if self.mesh is None:
                self._state = self._fresh_state(self.params)
            else:
                with self._ctx():
                    self._state = self._sharded_state(self.params)
                self.params = jax.device_put(
                    self.params, NamedSharding(self.mesh, P()))
            # the reset template must not alias the (donated) live state;
            # it holds only per-slot leaves, since the reset never reads the
            # shared pools from it (a copy would double the pool's bytes)
            self._template = {"layers": [
                [{n: jax.numpy.copy(x) for n, x in blk.items()
                  if n not in ("kp", "vp", "ks", "vs")} for blk in stage]
                for stage in self._state["layers"]]}

    def _fresh_state(self, params):
        """The engine's empty decode state (pools, block tables, lengths).
        Under ``mesh=`` it is traced inside ``_sharded_state``, which gives
        every leaf its NamedSharding."""
        state = M.init_paged_state(
            params, self.cfg, self.B, self.cache_len,
            page_size=self.page_size, n_pages=self.n_pages,
            window_extra=self.chunk, kv_dtype=self.kv_dtype)
        if self.mesh is None:
            return state
        from repro.serve.serve_step import decode_state_specs

        specs = decode_state_specs(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))
        return jax.lax.with_sharding_constraint(state, jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))

    def tick(self) -> Dict[int, List[int]]:
        """One scheduling tick: admit from the queue, pack, run one program
        step.  Returns the requests that finished this tick ({uid: tokens}).
        Public so continuous-arrival drivers (``bench/drive.py``) and
        ``RequestHandle.tokens()`` iterators can interleave ``submit`` with
        serving instead of draining a batch."""
        with spans.step(spans.TICK, step_num=self._stats["ticks"]), \
                self._ctx():
            self._ensure_state()
            self._expire_deadlines()
            self._chaos_alloc_fail = False
            if self.fault_injector is not None and self._chaos_tick():
                # stalled tick: the clock advanced, nothing ran
                self._stats["ticks"] += 1
                self.tick_log.append((False, spans.now()))
                return {}
            if self.pool.events:
                # expiry / cancellation may have dropped parked pages with
                # no admission round behind them to drain the hevicts
                self._state = self._apply_pool_events(self._state)
            with spans.span(spans.ADMIT):
                self._state = self._admit(self._state)
            had_prefill = any(s is not None
                              and s.fill < len(s.prefill_tokens)
                              for s in self.slots)
            self._state, results = self._ragged_tick(self._state)
            self._stats["ticks"] += 1
            self.tick_log.append((had_prefill, spans.now()))
            return results

    def run(self, max_ticks: int = 4096) -> Dict[int, List[int]]:
        """Drain the queue; returns {uid: generated tokens}."""
        results: Dict[int, List[int]] = {}
        for _ in range(max_ticks):
            if self.idle:
                break
            results.update(self.tick())
        # drain partials on tick-budget exhaustion, releasing slots/pages so
        # the engine stays reusable (no page leak, no stale decode state);
        # never-admitted requests report their (empty) partials too, so every
        # submitted uid is present in the result
        for b, s in enumerate(self.slots):
            if s is not None:
                s.req.done = True
                results[s.req.uid] = s.req.out_tokens
                self._release_slot(b)
        while self.queue:
            req = self.queue.popleft()
            self._drop_park_record(req.uid)
            req.done = True
            results[req.uid] = req.out_tokens
        return results
