"""Online continuous-batching serving engine over a paged device KV cache.

Flood (serving/flood.py) is the *offline* half of the paper's §2.4 story:
a fixed request set, dense (B, seq_len) caches, host-side segment
bookkeeping.  This module is the *online* half the ROADMAP north star
asks for — requests arrive over time, join a running batch, and stream
tokens out — built from three pieces:

  * **Fixed-shape jitted serve steps.**  `max_slots` request slots; one
    paged decode tick over all slots (`api.Runner.make_paged_decode_step`)
    and one chunked-prefill step for a single request
    (`api.Runner.make_paged_prefill`).  Slot membership, sequence
    lengths, and page bindings are *data* (int32/bool arrays of fixed
    shape), so admitting, finishing, or preempting a request never
    recompiles — a test drives churn across >= 3x max_slots requests and
    asserts exactly one prefill + one decode XLA compile.

  * **Paged device KV.**  KV lives in slot-agnostic pools
    (n_pages, page_size, KV, hd) — the in-page offset dim sharded 1/tp —
    indexed by per-slot page tables.  `segment_cache.PageAllocator` owns
    the physical pages: admission, `ensure_capacity` growth, refcounted
    prefix-page sharing, preempt-and-requeue on exhaustion.

  * **The scheduler.**  An arrival queue with FCFS admission into free
    slots; each tick runs at most ONE prefill chunk (the oldest admitted
    request with unprefilled prompt) plus one decode tick for every
    decode-ready slot, so a long prompt costs the running batch one
    chunk of latency per tick instead of a full-prompt stall.  On pool
    exhaustion the youngest admitted request is preempted (pages freed,
    request requeued at the arrival-queue head) and re-prefills its
    prompt *plus* its already-emitted tokens on re-admission — emitted
    tokens are never re-sampled, so preemption is invisible in the
    output stream.  A **policy layer** rides on top: `OnlineConfig.
    policy` picks the tick ordering ("fcfs" | "decode-priority" |
    "prefill-priority" — see `tick`), `max_queue` + `overload` form a
    saturation-aware admission gate (bounded queue, shed-or-defer), and
    `tenant_budgets` caps each tenant's admitted tokens.  All of it is
    host bookkeeping over the same compiled steps — switching policies
    never recompiles.

  * **The radix prefix cache** (`radix_cache=True`, the default).
    `PageAllocator` keeps a trie keyed by page-aligned token blocks:
    admission walks it with the request's exact prefill tokens and
    attaches every matching refcounted KV page automatically — repeated
    system prompts cost zero prefill with no caller-supplied
    `prefix_key`.  Full pages publish into the trie when prefill
    completes, when a request releases, and when it is preempted;
    unreferenced cached pages are LRU-evicted (leaf-first) only when an
    allocation would otherwise fail, so caching never causes an OOM an
    uncached run would not hit.  Cache on/off is bitwise-invisible in
    the token streams (greedy and seeded sampling alike).

The per-slot decode batch shares every MoE decode constraint with the
offline engine: `max_slots` and `prefill_chunk` must satisfy
`quantize_microbatch(n, tp) == n` (the EP all-to-all path slices token
ownership over tp), checked at construction.

Two newer layers ride the same fixed-shape contract:

  * **Real sampling.**  Per-slot temperature / top-p / top-k / seed are
    (B,)-shaped DATA into the jitted steps; draws use the counter-based
    (seed, position, stream) key schedule in `models.embedding`, so
    token streams are reproducible across preemption replay and match
    the offline engine's `make_decode_step(sample=True)` for equal
    seeds.  Temperature 0 is bitwise-equal to greedy argmax.

  * **Speculative decoding** (`spec_k > 0` + a `serving.draft` drafter).
    Each spec tick: the drafter proposes k tokens per slot (scan of
    sampled decode steps over its OWN paged pools, same page ids as the
    target), ONE target pass shaped like a k+1-query paged prefill
    scores all candidate positions, and standard spec-sampling
    accept/reject commits `n_acc + 1` tokens host-side —
    `PageAllocator.trim` rewinds rejected tail pages (LIFO, so regrow
    reacquires identical pages).  Greedy streams stay token-exact
    versus non-speculative decode; acceptance only changes *speed*
    (ticks per token), never the distribution.

`run_poisson_load` is the load generator: Poisson arrivals at a given
rate, per-request TTFT / inter-token latency / throughput percentiles —
`launch/serve.py --online` reports them into BENCH_serve_online.json.

**Telemetry** (docs/observability.md): every engine owns a
`telemetry.MetricsRegistry` (TTFT/ITL/tick histograms, churn counters,
occupancy gauges + per-tick counter-track series), a
`telemetry.RequestLog` recording the full request lifecycle
(enqueue -> admit -> prefill chunks -> first token -> decode ->
preempt/requeue -> complete/shed, with tick indices and timestamps),
and an `XPUTimer` spanning the scheduler phases of every tick (also
`engine.*` annotations on the profiler's clock) — all of it host-side
bookkeeping under the zero-host-sync contract, exportable
to Perfetto via `telemetry.write_chrome_trace`.  `overload="slo"`
closes the loop: a `telemetry.SLOTracker` over the windowed histograms
vetoes admission when the configured TTFT/ITL p99 deadlines would be
breached (shedding at submit time keeps the *admitted* p99 inside the
deadline past the knee).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.models import layers as L
from repro.models import model as M
from repro.serving.flood import quantize_microbatch
from repro.serving.segment_cache import PageAllocator
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.request_log import RequestLog
from repro.telemetry.slo import SLOConfig, SLOTracker
from repro.telemetry.xputimer import XPUTimer


POLICIES = ("fcfs", "decode-priority", "prefill-priority")
OVERLOAD = ("defer", "shed", "slo")


@dataclasses.dataclass
class OnlineConfig:
    """Engine geometry + default sampling/speculation knobs.

    `max_context` bounds prompt+generation per request; `n_pages` sizes
    the shared pool (default: every slot can hold a full context, +1
    scratch page — shrink it to exercise preemption).  The sampling
    fields are per-request DEFAULTS (an `OnlineRequest` can override any
    of them); temperature 0 is exact greedy.  `spec_k > 0` turns on
    speculative decoding (propose->verify->commit ticks) and requires a
    drafter at engine construction; the page-table width then carries
    `spec_k` extra positions of slack because the verify pass writes
    k+1 candidate KV rows before the host commits.

    `radix_cache` turns on the cross-request content-addressed prefix
    cache (docs/serving.md): matching KV pages attach at admission with
    no caller-supplied `prefix_key`, full pages publish into the trie on
    prefill completion / release / preemption, and unreferenced cached
    pages LRU-evict only when an allocation would otherwise fail.  The
    scheduler knobs are pure host data — `policy` picks the tick
    ordering ("fcfs" | "decode-priority" | "prefill-priority"),
    `max_queue` bounds the arrival queue (`overload` picks shed vs defer
    when it is full), and `tenant_budgets` caps each tenant's admitted
    prompt+max_new tokens — none of them change any jitted step shape,
    so switching policies at runtime never recompiles."""
    max_slots: int
    max_context: int
    page_size: int = 16
    n_pages: Optional[int] = None
    prefill_chunk: int = 8
    donate: bool = True
    eos_id: Optional[int] = None
    # sampling defaults (per-request overridable)
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    seed: int = 0          # request seed defaults to (seed + rid) % 2**31
    # speculative decoding
    spec_k: int = 0
    # cross-request radix prefix cache
    radix_cache: bool = True
    # scheduler policy layer
    policy: str = "fcfs"
    max_queue: Optional[int] = None     # bounded arrival queue (None = inf)
    overload: str = "defer"             # gate response: defer | shed | slo
    tenant_budgets: Optional[Dict[str, int]] = None
    # SLO-aware admission (overload="slo"): shed at submit time when the
    # windowed latency view says admitting would breach a deadline
    # (telemetry.slo.SLOTracker — backward p99 + forward TTFT estimate)
    slo: Optional[SLOConfig] = None
    # per-request lifecycle log ring entries (telemetry.request_log)
    trace_ring: int = 65536
    # debug contracts (analysis.contracts): run every tick under a
    # device->host transfer_guard.  Default comes from REPRO_DEBUG_GUARDS
    # so CI legs can arm it without touching call sites.  None = env.
    debug_guards: Optional[bool] = None

    @property
    def max_pages(self) -> int:
        return -(-(self.max_context + self.spec_k) // self.page_size)

    def pool_pages(self) -> int:
        if self.n_pages is not None:
            return self.n_pages
        return self.max_slots * self.max_pages + 1


@dataclasses.dataclass
class OnlineRequest:
    rid: int
    prompt: np.ndarray
    max_new: int
    prefix_key: Optional[str] = None
    prefix_len: int = 0              # tokens to auto-publish under prefix_key
    tenant: Optional[str] = None     # admission-budget accounting key
    arrival_t: float = 0.0
    # sampling overrides (None -> the OnlineConfig default); the seed is
    # fixed per request, so preemption replay re-derives identical draws
    temperature: Optional[float] = None
    top_p: Optional[float] = None
    top_k: Optional[int] = None
    seed: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    state: str = "queued"        # queued | prefill | decode | done | shed
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    n_preempted: int = 0
    n_decode_ticks: int = 0          # decode/spec ticks this slot rode
    # scheduler scratch (valid while the request holds a slot)
    fed: Optional[np.ndarray] = None   # tokens to prefill (prompt + out[:-1])
    prefill_pos: int = 0

    @property
    def done(self) -> bool:
        return self.state == "done"


class OnlineEngine:
    """Continuous-batching scheduler around the fixed-shape paged steps.

    `prefill_traces` / `decode_traces` count python re-traces of the
    jitted steps (== XLA compiles, the `StagedTrainStep` trace-counter
    pattern); the engine contract is that both stay at 1 across arbitrary
    admission / completion / preemption churn.
    """

    def __init__(self, runner, params, cfg: OnlineConfig, drafter=None,
                 registry: Optional[MetricsRegistry] = None,
                 request_log: Optional[RequestLog] = None,
                 timer: Optional[XPUTimer] = None):
        M.check_paged_support(runner.cfg)
        env = runner.env
        tp = env.tp
        if env.dp != 1:
            raise ValueError(f"online serving runs on a (1, tp) mesh; "
                             f"got dp={env.dp}")
        if quantize_microbatch(cfg.max_slots, tp) != cfg.max_slots:
            raise ValueError(
                f"max_slots={cfg.max_slots} violates the EP decode batch "
                f"constraint (max_slots % tp == 0 for tp={tp}); round up "
                f"with serving.flood.quantize_microbatch(max_slots, tp) = "
                f"{quantize_microbatch(cfg.max_slots, tp)}")
        if quantize_microbatch(cfg.prefill_chunk, tp) != cfg.prefill_chunk:
            raise ValueError(
                f"prefill_chunk={cfg.prefill_chunk} must satisfy "
                f"chunk % tp == 0 (tp={tp}) — the chunk rides the same "
                f"MoE dispatch path as the decode batch")
        if cfg.page_size % tp:
            raise ValueError(f"page_size={cfg.page_size} must be divisible "
                             f"by tp={tp} (in-page offset sharding)")
        n_pages = cfg.pool_pages()
        if n_pages - 1 < cfg.max_pages:
            raise ValueError(
                f"pool of {n_pages} pages (1 reserved) cannot hold even "
                f"one max_context={cfg.max_context} request "
                f"({cfg.max_pages} pages)")
        if cfg.policy not in POLICIES:
            raise ValueError(f"policy={cfg.policy!r} not in {POLICIES}")
        if cfg.overload not in OVERLOAD:
            raise ValueError(f"overload={cfg.overload!r} not in {OVERLOAD}")
        if cfg.max_queue is not None and cfg.max_queue < 1:
            raise ValueError(f"max_queue={cfg.max_queue} must be >= 1")
        if cfg.overload == "slo" and cfg.slo is None:
            raise ValueError(
                'overload="slo" needs deadlines: set OnlineConfig.slo to a '
                "telemetry.SLOConfig(ttft_p99_ms=...)")
        self.cfg = cfg
        self.runner = runner
        # matmul weights in the compute dtype, cast once here rather than
        # in every step; the engine keeps no reference to their originals
        params = runner.serve_params(params)
        self.params = params
        # resolved paged-attention backend (RunFlags.paged_attn "auto"
        # settles at engine build time) — surfaced in load reports so a
        # bench row records which path it measured
        self.paged_attn = L.resolve_paged_attn(runner.flags.paged_attn)
        self.alloc = PageAllocator(n_pages, cfg.page_size)
        self.pools = runner.init_paged_pools(n_pages, cfg.page_size)

        # speculative decoding: build the drafter model over its OWN page
        # pools (same page ids / page size / pool count as the target, so
        # admission, growth, preemption, prefix sharing, and trim all
        # transfer to the drafter KV via the shared tables)
        self.spec = cfg.spec_k > 0
        self.drafter = drafter
        if self.spec:
            if drafter is None:
                raise ValueError(
                    f"spec_k={cfg.spec_k} > 0 requires a drafter (e.g. "
                    f"serving.draft.SelfDrafter(draft_layers=...))")
            if cfg.max_slots * (cfg.spec_k + 1) % tp:
                raise ValueError(
                    f"max_slots*(spec_k+1)={cfg.max_slots * (cfg.spec_k + 1)}"
                    f" must be divisible by tp={tp} (the verify pass rides "
                    f"the EP dispatch path with B*(k+1) tokens)")
            self.drunner, self.dparams = drafter.build(runner, params)
            if self.drunner.cfg.vocab_size != runner.cfg.vocab_size:
                raise ValueError(
                    f"drafter vocab_size={self.drunner.cfg.vocab_size} != "
                    f"target vocab_size={runner.cfg.vocab_size}")
            self.dpools = self.drunner.init_paged_pools(n_pages,
                                                        cfg.page_size)
        else:
            self.drunner = self.dparams = self.dpools = None

        # trace-time compile counting (analysis.contracts.CompileCounter):
        # the engine contract — exactly one compile per step family across
        # arbitrary churn — is asserted with contracts.compile_guard()
        # over these labels; prefill_traces/... stay as properties
        self.compiles = contracts.CompileCounter()
        self.debug_guards = (contracts.env_debug_guards()
                             if cfg.debug_guards is None
                             else cfg.debug_guards)
        self.spec_proposed = 0        # drafted tokens offered to verify
        self.spec_accepted = 0        # drafted tokens accepted
        # the engine always runs the *sampled* step variants — knobs are
        # (B,) data, temperature 0 is bitwise greedy, so one compiled
        # step serves greedy and stochastic slots alike
        raw_dec = runner.make_paged_decode_step(cfg.page_size, sample=True)
        raw_pre = runner.make_paged_prefill(cfg.page_size, sample=True)

        donate = cfg.donate
        self._decode = self.compiles.jit(
            "decode", raw_dec, donate_argnums=(1,) if donate else ())

        if self.spec:
            # fused prefill: one jitted step writes the chunk into BOTH
            # the target and drafter pools, preserving the "exactly one
            # prefill compile" contract in spec mode.  The drafter leg
            # is the plain (unsampled) prefill — only its KV writes
            # matter; its next-token output is discarded.
            raw_dpre = self.drunner.make_paged_prefill(cfg.page_size)

            def pre_fn(params, dparams, pools, dpools, tokens, base,
                       n_valid, table_row, seed, temp, top_p, top_k):
                nxt, pools = raw_pre(params, pools, tokens, base, n_valid,
                                     table_row, seed, temp, top_p, top_k)
                _, dpools = raw_dpre(dparams, dpools, tokens, base,
                                     n_valid, table_row)
                return nxt, pools, dpools

            self._prefill = self.compiles.jit(
                "prefill", pre_fn, donate_argnums=(2, 3) if donate else ())

            raw_draft = self.drunner.make_paged_draft_propose(
                cfg.page_size, cfg.spec_k)
            raw_verify = runner.make_paged_verify_step(
                cfg.page_size, cfg.spec_k)

            self._draft = self.compiles.jit(
                "draft", raw_draft, donate_argnums=(1,) if donate else ())
            self._verify = self.compiles.jit(
                "verify", raw_verify, donate_argnums=(1,) if donate else ())
        else:
            self._prefill = self.compiles.jit(
                "prefill", raw_pre, donate_argnums=(1,) if donate else ())

        # host-side slot state (device copies are cut fresh every call —
        # same shapes/dtypes, so never a recompile)
        S = cfg.max_slots
        self.slot_rid = np.full((S,), -1, np.int64)
        self.table = np.zeros((S, cfg.max_pages), np.int32)
        self.lens = np.zeros((S,), np.int32)
        self.active = np.zeros((S,), bool)
        self.tok = np.zeros((S,), np.int32)
        self.slot_seq = np.zeros((S,), np.int64)   # admission counter
        self._seq = 0
        # per-slot sampling knobs — DATA to the jitted steps, so mixing
        # greedy and stochastic requests in one batch never recompiles
        self.seeds = np.zeros((S,), np.int32)
        self.temps = np.zeros((S,), np.float32)
        self.topps = np.ones((S,), np.float32)
        self.topks = np.zeros((S,), np.int32)

        self.queue: Deque[int] = deque()
        self.reqs: Dict[int, OnlineRequest] = {}
        self.admission_log: List[int] = []
        self.ticks = 0
        self.n_preemptions = 0
        self.policy = cfg.policy
        self.n_shed = 0                  # saturation-gate rejections
        self.n_budget_skips = 0          # admissions deferred over budget

        # -- telemetry (docs/observability.md) ----------------------------
        # Everything below reads host scalars the scheduler already holds
        # (zero-host-sync contract): no metric call touches a jax value,
        # and the contract tests run ticks under compile_guard +
        # transfer_guard with all of this enabled.
        self.registry = registry if registry is not None else MetricsRegistry()
        self.rlog = (request_log if request_log is not None
                     else RequestLog(cfg.trace_ring))
        self.timer = (timer if timer is not None
                      else XPUTimer(registry=self.registry, prefix="engine."))
        if self.timer.registry is None:
            self.timer.registry = self.registry
        # SLOTracker first: it sizes the shared latency-histogram windows
        # (get-or-create below then returns the same children)
        self.slo = (SLOTracker(cfg.slo, self.registry)
                    if cfg.slo is not None else None)
        reg = self.registry
        self._m_ttft = reg.histogram(
            "serve_ttft_ms", "time to first token (admitted requests)")
        self._m_itl = reg.histogram(
            "serve_itl_ms", "inter-token latency (decode steps)")
        self._m_tick = reg.histogram(
            "serve_tick_ms", "engine tick wall time")
        self._m_enq = reg.counter(
            "serve_enqueued_total", "requests accepted into the queue")
        self._m_admit = reg.counter(
            "serve_admitted_total", "requests bound to a slot")
        self._m_done = reg.counter(
            "serve_completed_total", "requests finished")
        self._m_shed = reg.counter(
            "serve_shed_total", "requests rejected by the admission gate")
        self._m_preempt = reg.counter(
            "serve_preemptions_total", "slot preempt-and-requeue events")
        self._m_tokens = reg.counter(
            "serve_tokens_total", "tokens emitted across all requests")
        self._m_evict = reg.counter(
            "serve_cache_evictions_total", "radix-cache pages evicted")
        self._g_queue = reg.gauge("serve_queue_depth", "arrival queue depth")
        self._g_pages = reg.gauge(
            "serve_pages_in_use", "KV pool pages held (requests+cache)")
        self._g_slots = reg.gauge("serve_slots_active", "occupied slots")
        by_dtype: Dict[str, int] = {}
        for a in jax.tree.leaves(self.params):
            by_dtype[str(a.dtype)] = by_dtype.get(str(a.dtype), 0) + a.nbytes
        for dt, n in by_dtype.items():
            reg.gauge("serve_weight_bytes", "bytes of the weights the serve "
                      "steps read, by dtype", dtype=dt).set(n)
        pool_bytes = sum(a.nbytes for a in jax.tree.leaves(self.pools))
        reg.gauge("serve_kv_bytes_per_token", "cache bytes one token holds "
                  "over all layers: K and V, or one latent row",
                  layout="latent" if runner.cfg.mla is not None else "kv"
                  ).set(pool_bytes // (n_pages * cfg.page_size))
        # per-tick samples -> Perfetto counter tracks (trace_export)
        self._s_pages = reg.series("page_pool_occupancy")
        self._s_queue = reg.series("queue_depth")
        self._s_radix = reg.series("radix_hit_rate")
        self._s_accept = reg.series("spec_acceptance") if self.spec else None
        self._admitted_tokens = 0        # prefill tokens ever admitted
        self.alloc.on_evict = self._on_evict

    def _on_evict(self, page: int):
        """PageAllocator hook: one radix-cache page evicted."""
        self._m_evict.inc()
        self.rlog.record("evict", -1, tick=self.ticks, arg=page)

    def set_policy(self, policy: str):
        """Switch the tick-ordering policy at runtime.  Pure host state —
        the jitted steps are untouched, so this never recompiles (the
        policy tests assert it)."""
        if policy not in POLICIES:
            raise ValueError(f"policy={policy!r} not in {POLICIES}")
        self.policy = policy

    # -- submission -----------------------------------------------------------
    def submit(self, req: OnlineRequest) -> bool:
        """Enqueue a request.  With a bounded queue (`max_queue`) a full
        queue triggers the saturation gate: "shed" marks the request
        shed and drops it (state="shed", counted in `n_shed`), "defer"
        returns False without touching it so the caller can retry after
        the engine drains.  With ``overload="slo"`` the SLOTracker also
        vetoes admission whenever its windowed latency view says this
        request could not meet the TTFT/ITL deadlines (a full queue
        sheds too).  Returns True when enqueued."""
        total = len(req.prompt) + req.max_new
        if total > self.cfg.max_context:
            raise ValueError(f"request {req.rid}: prompt+max_new={total} "
                             f"exceeds max_context={self.cfg.max_context}")
        if req.max_new < 1:
            raise ValueError("max_new must be >= 1")
        if len(req.prompt) < 1:
            raise ValueError("prompt must hold at least one token")
        old = self.reqs.get(req.rid)
        if old is not None and not old.done:
            raise ValueError(f"rid {req.rid} is still in flight "
                             f"(state={old.state}); rids must be unique "
                             f"among live requests")
        if req.arrival_t <= 0.0:
            req.arrival_t = time.perf_counter()
        if self.cfg.overload == "slo":
            queued_tokens = (sum(len(self.reqs[q].prompt)
                                 for q in self.queue) + len(req.prompt))
            reason = self.slo.should_shed(queued_tokens,
                                          self.cfg.prefill_chunk)
            if reason is not None:
                return self._shed(req)
        if (self.cfg.max_queue is not None
                and len(self.queue) >= self.cfg.max_queue):
            if self.cfg.overload in ("shed", "slo"):
                return self._shed(req)
            return False
        self.reqs[req.rid] = req
        self.queue.append(req.rid)
        self._m_enq.inc()
        self.rlog.record("enqueue", req.rid, tick=self.ticks)
        return True

    def _shed(self, req: OnlineRequest) -> bool:
        req.state = "shed"
        self.n_shed += 1
        self._m_shed.inc()
        if self.slo is not None:
            self.slo.on_shed()
        self.rlog.record("shed", req.rid, tick=self.ticks)
        return False

    def submit_many(self, reqs: Sequence[OnlineRequest]):
        for r in reqs:
            if not self.submit(r):
                raise RuntimeError(
                    f"rid {r.rid} rejected by the saturation gate "
                    f"(queue full at max_queue={self.cfg.max_queue}); "
                    f"submit_many is for unbounded batches — use submit "
                    f"and handle the False return")

    def register_prefix(self, rid: int, key: str, n_tokens: int):
        """Publish a live request's leading full pages for prefix reuse;
        later submissions carrying `prefix_key=key` skip prefilling the
        shared tokens (contract: their prompt starts with the same
        tokens)."""
        self.alloc.register_prefix(rid, key, n_tokens)

    # -- scheduling helpers ---------------------------------------------------
    def _free_slots(self) -> List[int]:
        return [int(s) for s in np.flatnonzero(self.slot_rid < 0)]

    def _busy_slots(self) -> List[int]:
        return [int(s) for s in np.flatnonzero(self.slot_rid >= 0)]

    def _tenant_usage(self) -> Dict[str, int]:
        usage: Dict[str, int] = {}
        for s in self._busy_slots():
            r = self.reqs[int(self.slot_rid[s])]
            if r.tenant is not None:
                usage[r.tenant] = (usage.get(r.tenant, 0)
                                   + len(r.prompt) + r.max_new)
        return usage

    def _admit(self, now: float):
        budgets = self.cfg.tenant_budgets or {}
        usage = self._tenant_usage() if budgets else {}
        skipped: List[int] = []
        for slot in self._free_slots():
            rid = None
            while self.queue:
                cand = self.queue.popleft()
                c = self.reqs[cand]
                budget = (budgets.get(c.tenant)
                          if c.tenant is not None else None)
                cost = len(c.prompt) + c.max_new
                if (budget is not None
                        and usage.get(c.tenant, 0) + cost > budget):
                    # over the tenant's admitted-token budget: hold it
                    # back (FCFS order preserved) and try the next rid
                    skipped.append(cand)
                    self.n_budget_skips += 1
                    continue
                rid = cand
                break
            if rid is None:
                break
            r = self.reqs[rid]
            if r.tenant is not None and budgets:
                usage[r.tenant] = (usage.get(r.tenant, 0)
                                   + len(r.prompt) + r.max_new)
            # re-prefill prompt + already-emitted tokens minus the last,
            # which becomes the next decode input (never re-sampled)
            r.fed = (np.concatenate([r.prompt,
                                     np.asarray(r.out[:-1], np.int32)])
                     if r.out else np.asarray(r.prompt, np.int32)
                     ).astype(np.int32)
            if self.cfg.radix_cache:
                # content-addressed attach: walk the radix trie with the
                # exact tokens this request will prefill (on re-admission
                # after a preempt that includes its own emitted tokens,
                # so a published victim re-attaches nearly everything)
                shared = self.alloc.admit(rid, tokens=r.fed)
            else:
                # legacy keyed attach, capped at the request's ORIGINAL
                # prompt: generated tokens diverge from the publisher's
                # continuation, and shared pages must never receive this
                # request's writes
                shared = self.alloc.admit(rid, prefix_key=r.prefix_key,
                                          prompt_len=len(r.prompt))
            r.prefill_pos = min(shared, max(len(r.fed) - 1, 0))
            r.state = "prefill"
            r.admit_t = now
            self.slot_rid[slot] = rid
            self.slot_seq[slot] = self._seq
            self._seq += 1
            self.table[slot] = self.alloc.table_row(rid, self.cfg.max_pages)
            self.lens[slot] = 0
            self.active[slot] = False
            self.tok[slot] = 0
            # resolve sampling knobs: request override > engine default.
            # The seed is a pure function of (cfg.seed, rid), so a
            # preempted request re-derives the identical draw stream
            cfg = self.cfg
            self.seeds[slot] = (r.seed if r.seed is not None
                                else (cfg.seed + rid) % (2 ** 31))
            self.temps[slot] = (r.temperature if r.temperature is not None
                                else cfg.temperature)
            self.topps[slot] = (r.top_p if r.top_p is not None
                                else cfg.top_p)
            self.topks[slot] = (r.top_k if r.top_k is not None
                                else cfg.top_k)
            self.admission_log.append(rid)
            self._m_admit.inc()
            self._admitted_tokens += len(r.fed)
            self.rlog.record("admit", rid, slot=slot, tick=self.ticks,
                             arg=len(r.fed))
        # over-budget holds return to the queue head in FCFS order
        for cand in reversed(skipped):
            self.queue.appendleft(cand)

    def _clear_slot(self, slot: int):
        self.slot_rid[slot] = -1
        self.table[slot] = 0
        self.lens[slot] = 0
        self.active[slot] = False
        self.tok[slot] = 0
        self.seeds[slot] = 0
        self.temps[slot] = 0.0
        self.topps[slot] = 1.0
        self.topks[slot] = 0

    def _written_tokens(self, slot: int) -> np.ndarray:
        """The token each written KV row holds, in row order — the
        invariant `row i holds KV of (prompt + out)[i]` is maintained by
        prefill (feeds prompt + out[:-1]), decode (feeds out[-1] at row
        `lens`), and spec commit (lens grows only over accepted rows).
        During prefill only `prefill_pos` rows are written."""
        rid = int(self.slot_rid[slot])
        r = self.reqs[rid]
        written = (r.prefill_pos if r.state == "prefill"
                   else int(self.lens[slot]))
        seq = (np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
               if r.out else np.asarray(r.prompt, np.int32))
        return seq[:written].astype(np.int32)

    def _finish(self, slot: int, now: float):
        rid = int(self.slot_rid[slot])
        r = self.reqs[rid]
        if self.cfg.radix_cache:
            # publish-on-release: the request's full pages (prompt AND
            # generated tokens) enter the trie instead of recycling
            self.alloc.release(rid, tokens=self._written_tokens(slot))
        else:
            self.alloc.release(rid)
        r.state = "done"
        r.finish_t = now
        r.fed = None
        self._m_done.inc()
        self.rlog.record("complete", rid, slot=slot, tick=self.ticks,
                         arg=len(r.out))
        self._clear_slot(slot)

    def _preempt_slot(self, slot: int):
        """Free a victim's pages and requeue it at the queue head (FCFS
        re-admission: when several are preempted youngest-first, each
        appendleft puts the older one ahead).  With the radix cache the
        victim's full pages are published first — unless the sweep has
        to evict them, its re-prefill collapses to a cache hit."""
        rid = int(self.slot_rid[slot])
        r = self.reqs[rid]
        if self.cfg.radix_cache:
            self.alloc.preempt(rid, tokens=self._written_tokens(slot))
        else:
            self.alloc.preempt(rid)
        r.state = "queued"
        r.n_preempted += 1
        r.fed = None
        self.queue.appendleft(rid)
        self._clear_slot(slot)
        self.n_preemptions += 1
        self._m_preempt.inc()
        self.rlog.record("preempt", rid, slot=slot, tick=self.ticks)
        self.rlog.record("requeue", rid, tick=self.ticks)

    def _make_room(self, rid: int, n_tokens: int,
                   allow_preempt: bool = True) -> bool:
        """ensure_capacity with preempt-and-requeue: evict the youngest
        other resident until the grow fits (the allocator has already
        LRU-evicted unreferenced cached pages before reporting failure —
        eviction always precedes preemption).  Failing with no victims
        left means this request is the sole resident and STILL cannot
        fit — nothing will ever free (only pinned prefix pages and its
        own remain), so raise instead of letting the scheduler thrash
        through endless self-preemption.  With `allow_preempt=False`
        (decode-priority prefill) a grow that would need a victim
        returns False instead — the caller defers to a later tick."""
        while not self.alloc.ensure_capacity(rid, n_tokens):
            victims = [s for s in self._busy_slots()
                       if int(self.slot_rid[s]) != rid]
            if not victims:
                pinned = sum(len(p) for p in
                             self.alloc.prefix_index.values())
                raise RuntimeError(
                    f"request {rid} needs {n_tokens} tokens "
                    f"({-(-n_tokens // self.cfg.page_size)} pages) but the "
                    f"pool cannot satisfy it even empty: {self.alloc.n_free}"
                    f" free, {pinned} page refs pinned by registered "
                    f"prefixes (drop_prefix to release)")
            if not allow_preempt:
                return False
            self._preempt_slot(max(victims, key=lambda s: self.slot_seq[s]))
        return True

    # -- prefill --------------------------------------------------------------
    def _prefill_target(self) -> Optional[int]:
        """Oldest admitted slot with unprefilled tokens."""
        cands = [s for s in self._busy_slots()
                 if self.reqs[int(self.slot_rid[s])].state == "prefill"]
        if not cands:
            return None
        return min(cands, key=lambda s: self.slot_seq[s])

    def _prefill_tick(self, now: float) -> bool:
        """Run one prefill chunk for the oldest prefilling slot; returns
        True when it made progress (False: nothing to prefill, or the
        grow deferred under decode-priority)."""
        with self._span("prefill.prepare"):
            slot = self._prefill_target()
            if slot is None:
                return False
            rid = int(self.slot_rid[slot])
            r = self.reqs[rid]
            C = self.cfg.prefill_chunk
            n_valid = min(C, len(r.fed) - r.prefill_pos)
            # decode-priority: prefill never steals pages from in-flight
            # decode slots — if eviction can't cover the grow, defer the
            # chunk until decodes release naturally
            if not self._make_room(rid, r.prefill_pos + n_valid,
                                   allow_preempt=(self.policy
                                                  != "decode-priority")):
                return False
            self.table[slot] = self.alloc.table_row(rid, self.cfg.max_pages)
            chunk = np.zeros((C,), np.int32)
            chunk[:n_valid] = r.fed[r.prefill_pos:r.prefill_pos + n_valid]
            step_args = (jnp.asarray(chunk), jnp.int32(r.prefill_pos),
                         jnp.int32(n_valid), jnp.asarray(self.table[slot]),
                         jnp.int32(self.seeds[slot]),
                         jnp.float32(self.temps[slot]),
                         jnp.float32(self.topps[slot]),
                         jnp.int32(self.topks[slot]))
        with self._span("prefill.dispatch"):
            if self.spec:
                nxt, self.pools, self.dpools = self._prefill(
                    self.params, self.dparams, self.pools, self.dpools,
                    *step_args)
            else:
                nxt, self.pools = self._prefill(self.params, self.pools,
                                                *step_args)
        with self._span("prefill.commit"):
            return self._commit_prefill(slot, n_valid, nxt)

    def _commit_prefill(self, slot: int, n_valid: int, nxt) -> bool:
        """Bookkeeping of the chunk just dispatched; when it completes the
        prompt, the first token (its `device_get` is the phase
        `prefill.sync`)."""
        rid = int(self.slot_rid[slot])
        r = self.reqs[rid]
        r.prefill_pos += n_valid
        self.rlog.record("prefill_chunk", rid, slot=slot, tick=self.ticks,
                         arg=n_valid)
        if r.prefill_pos < len(r.fed):
            return True                 # more chunks to go
        # prompt (+ replayed tokens) fully written: enter decode state
        t = time.perf_counter()
        self.lens[slot] = len(r.fed)
        self.active[slot] = True
        r.state = "decode"
        self.rlog.record("prefill_done", rid, slot=slot, tick=self.ticks,
                         arg=len(r.fed))
        if self.cfg.radix_cache:
            # publish-on-prefill: the prompt's full pages enter the trie
            # the moment they are written, so concurrent arrivals with
            # the same prefix hit while this request is still decoding.
            # Content addressing dedupes same-prefix racers — no
            # prefix_key coordination, no double-publish
            self.alloc.publish_radix(rid, r.fed)
        elif (r.prefix_key and r.prefix_len > 0
                and r.prefix_key not in self.alloc.prefix_index):
            # legacy keyed auto-publish: first finisher wins; a same-key
            # racer's identical pages stay private (content-dedup needs
            # the radix path) and recycle on its release
            self.alloc.register_prefix(rid, r.prefix_key,
                                       min(r.prefix_len, len(r.prompt)))
        if not r.out:
            with self._span("prefill.sync"):
                tok = int(jax.device_get(nxt))
            r.out.append(tok)
            r.first_token_t = t
            r.token_times.append(t)
            self._m_tokens.inc()
            self.rlog.record("first_token", rid, slot=slot, tick=self.ticks)
            if r.arrival_t > 0.0:
                self._m_ttft.observe((t - r.arrival_t) * 1e3)
            if len(r.out) >= r.max_new or tok == self.cfg.eos_id:
                self._finish(slot, t)
                return True
        self.tok[slot] = r.out[-1]
        return True

    # -- decode ---------------------------------------------------------------
    def _grow_slots(self, extra: int):
        """Grow every decode slot to hold `extra` more positions, oldest
        first (the youngest is the preferred preemption victim, so
        growing in age order never evicts a slot already grown this
        tick)."""
        for slot in sorted(np.flatnonzero(self.active),
                           key=lambda s: self.slot_seq[s]):
            slot = int(slot)
            if not self.active[slot]:
                continue                # preempted by an earlier grow
            rid = int(self.slot_rid[slot])
            self._make_room(rid, int(self.lens[slot]) + extra)
            self.table[slot] = self.alloc.table_row(rid, self.cfg.max_pages)

    def _decode_tick(self, now: float):
        with self._span("decode.prepare"):
            self._grow_slots(1)
            if not self.active.any():
                return
            args = (jnp.asarray(self.tok), jnp.asarray(self.lens),
                    jnp.asarray(self.table), jnp.asarray(self.active),
                    jnp.asarray(self.seeds), jnp.asarray(self.temps),
                    jnp.asarray(self.topps), jnp.asarray(self.topks))
        with self._span("decode.dispatch"):
            nxt, self.pools = self._decode(self.params, self.pools, *args)
        with self._span("decode.sync"):
            nxt = np.asarray(jax.device_get(nxt))
        with self._span("decode.commit"):
            self._commit_decode(nxt)

    def _commit_decode(self, nxt: np.ndarray):
        t = time.perf_counter()
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            rid = int(self.slot_rid[slot])
            r = self.reqs[rid]
            tok = int(nxt[slot])
            if r.token_times:
                self._m_itl.observe((t - r.token_times[-1]) * 1e3)
            r.out.append(tok)
            r.token_times.append(t)
            r.n_decode_ticks += 1
            self.lens[slot] += 1
            self.tok[slot] = tok
            self._m_tokens.inc()
            self.rlog.record("decode", rid, slot=slot, tick=self.ticks,
                             arg=1)
            if len(r.out) >= r.max_new or tok == self.cfg.eos_id:
                self._finish(slot, t)

    # -- speculative decode (propose -> verify -> commit) ----------------------
    def _spec_tick(self, now: float):
        """One speculative tick over the slot batch: the drafter proposes
        k tokens per slot (its KV advancing through its own pools), one
        target verify pass scores all k+1 positions, and the host commits
        `n_acc + 1` emitted tokens per slot — page-table tails rewound
        with `PageAllocator.trim` so rejected drafts hand their surplus
        pages straight back (LIFO: a regrow reacquires the identical
        pages, keeping page tables deterministic)."""
        K = self.cfg.spec_k
        # the decode.* phases: propose + verify is the dispatch
        with self._span("decode.prepare"):
            self._grow_slots(K + 1)     # each slot's k+1 candidate rows
            if not self.active.any():
                return
            sample_args = (jnp.asarray(self.seeds), jnp.asarray(self.temps),
                           jnp.asarray(self.topps), jnp.asarray(self.topks))
            table = jnp.asarray(self.table)
            active = jnp.asarray(self.active)
            pos0 = jnp.asarray(self.lens)
            tok = jnp.asarray(self.tok)
        with self._span("decode.dispatch"):
            drafts, dprobs, self.dpools = self._draft(
                self.dparams, self.dpools, tok, pos0, table, active,
                *sample_args)
            tokens = jnp.concatenate(
                [tok[:, None], drafts.astype(jnp.int32)],
                axis=1)                 # (B, k+1): pending token + drafts
            n_acc, out, self.pools = self._verify(
                self.params, self.pools, tokens, pos0, table, active,
                dprobs, *sample_args)
        with self._span("decode.sync"):
            n_acc = np.asarray(jax.device_get(n_acc))
            out = np.asarray(jax.device_get(out))
        with self._span("decode.commit"):
            self._commit_spec(n_acc, out)

    def _commit_spec(self, n_acc: np.ndarray, out: np.ndarray):
        K = self.cfg.spec_k
        t = time.perf_counter()
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            rid = int(self.slot_rid[slot])
            r = self.reqs[rid]
            na = int(n_acc[slot])
            self.spec_proposed += K
            self.spec_accepted += na
            r.n_decode_ticks += 1
            if r.token_times:
                self._m_itl.observe((t - r.token_times[-1]) * 1e3)
            # emit the accepted drafts + the bonus/residual token, cut
            # short by max_new / eos exactly like the plain decode path
            done = False
            kept = 0
            for tok in out[slot, :na + 1]:
                tok = int(tok)
                r.out.append(tok)
                r.token_times.append(t)
                kept += 1
                if len(r.out) >= r.max_new or tok == self.cfg.eos_id:
                    done = True
                    break
            self._m_tokens.inc(kept)
            self.rlog.record("decode", rid, slot=slot, tick=self.ticks,
                             arg=kept)
            if done:
                self._finish(slot, t)
                continue
            # commit: the pending token + na accepted drafts are now
            # written KV (kept == na + 1 rows starting at the old len);
            # the new pending token's KV lands next tick
            self.lens[slot] += kept
            self.tok[slot] = r.out[-1]
            self.alloc.trim(rid, int(self.lens[slot]))
            self.table[slot] = self.alloc.table_row(rid, self.cfg.max_pages)

    def pop_done(self) -> List[OnlineRequest]:
        """Remove and return finished requests.  The engine retains
        completed `OnlineRequest` objects (token streams + latency
        timestamps) until the caller collects them — a long-lived server
        loop must call this periodically or host memory grows with every
        request ever served."""
        done = [r for r in self.reqs.values() if r.done]
        for r in done:
            del self.reqs[r.rid]
        return done

    # -- driver ---------------------------------------------------------------
    # compile-count views over the shared CompileCounter (the names the
    # tests/benches have always used; the counter itself is the API for
    # contracts.compile_guard)
    @property
    def prefill_traces(self) -> int:
        return self.compiles["prefill"]

    @property
    def decode_traces(self) -> int:
        return self.compiles["decode"]

    @property
    def draft_traces(self) -> int:
        return self.compiles["draft"]

    @property
    def verify_traces(self) -> int:
        return self.compiles["verify"]

    @property
    def idle(self) -> bool:
        return not self.queue and not self._busy_slots()

    def tick(self, now: Optional[float] = None):
        """One engine step under the active scheduling policy:

        * ``fcfs`` — admission -> one prefill chunk -> one decode (or
          speculative propose/verify/commit) tick.  The balanced
          default: long prompts cost the batch one chunk per tick.
        * ``decode-priority`` — decode first, then at most one prefill
          chunk, and prefill growth never preempts a decoding slot
          (it defers until decodes release pages): in-flight requests
          are never starved or evicted by arriving prompts.
        * ``prefill-priority`` — drain EVERY pending prefill chunk
          before decoding, preempting decode slots for room if needed:
          the head-of-queue request reaches its first token within one
          tick of admission, bounding TTFT at the cost of decode ITL.

        All three drive the same compiled steps — switching policies
        never recompiles."""
        now = time.perf_counter() if now is None else now
        self.ticks += 1
        t_start = time.perf_counter()
        with self._span("tick"), self._tick_guard():
            with self._span("admit"):
                self._admit(now)
            step = self._spec_tick if self.spec else self._decode_tick
            if self.policy == "decode-priority":
                step(now)
                self._prefill_tick(now)
            elif self.policy == "prefill-priority":
                while self._prefill_tick(now):
                    pass
                step(now)
            else:                            # fcfs
                self._prefill_tick(now)
                step(now)
            with self._span("counters"):
                self._sample_counters()
        self._m_tick.observe((time.perf_counter() - t_start) * 1e3)

    def _span(self, name: str):
        """A timer span of this tick's phase `name`, tagged with the tick
        index that its `RequestLog` events carry."""
        return self.timer.span(name, tick=self.ticks)

    def _sample_counters(self):
        """Per-tick host-scalar samples -> gauges + Perfetto counter
        tracks.  Every value is bookkeeping the scheduler already holds
        (allocator free-list length, queue length, cumulative stats) —
        nothing here can touch the device."""
        t_us = int(time.perf_counter() * 1e6)
        in_use = self.alloc.pages_in_use
        self._g_pages.set(in_use)
        self._s_pages.sample(in_use, t_us)
        depth = len(self.queue)
        self._g_queue.set(depth)
        self._s_queue.sample(depth, t_us)
        self._g_slots.set(int((self.slot_rid >= 0).sum()))
        hit_rate = (self.alloc.stats["radix_hit_tokens"]
                    / max(self._admitted_tokens, 1))
        self._s_radix.sample(hit_rate, t_us)
        if self._s_accept is not None:
            self._s_accept.sample(
                self.spec_accepted / max(self.spec_proposed, 1), t_us)

    def _tick_guard(self):
        """debug_guards mode: the whole tick runs under a device->host
        transfer_guard, so any sync the engine did not announce with an
        explicit jax.device_get is an error on guarded backends (TPU/GPU
        — the CPU backend never fires transfer guards)."""
        if self.debug_guards:
            return contracts.transfer_guard("disallow")
        return contextlib.nullcontext()

    def run(self, max_ticks: int = 100_000):
        """Drive ticks until every submitted request is done."""
        for _ in range(max_ticks):
            if self.idle:
                return
            self.tick()
        raise RuntimeError(f"engine did not drain in {max_ticks} ticks "
                           f"(queue={len(self.queue)}, "
                           f"busy={self._busy_slots()})")


# ---------------------------------------------------------------------------
# Poisson load generator
# ---------------------------------------------------------------------------


def _pctl(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def run_poisson_load(engine: OnlineEngine, *, rate: float, n_requests: int,
                     prompt_len: int, max_new: int, vocab_size: int,
                     seed: int = 0, max_ticks: int = 1_000_000,
                     shared_prefix_len: int = 0,
                     prefix_key: Optional[str] = None,
                     tenants: Optional[Sequence[str]] = None
                     ) -> Dict[str, Any]:
    """Open-loop Poisson arrivals at `rate` req/s against a live engine.

    Requests are submitted when their scheduled arrival time passes on
    the wall clock (the engine keeps ticking in between — arrivals join
    the running batch), so TTFT includes genuine queueing delay.
    Returns TTFT p50/p99, pooled inter-token latency p50/p99, sustained
    tok/s, and churn counters.

    With ``shared_prefix_len > 0`` every prompt starts with the same
    `shared_prefix_len`-token system prompt followed by a random suffix
    (the chat-serving hot-prefix shape).  With the radix cache on, the
    hits need **no coordination**: the first request to finish prefill
    publishes its full pages into the trie and later arrivals attach by
    content — the report's `prefix_hits` / `prefix_hit_rate` count how
    many did.  With the cache off the legacy `prefix_key` registry
    carries the sharing instead.  The cache is flushed before returning
    so repeated loads on one engine start cold.

    A bounded-queue engine may defer (submission retried while the
    arrival is late) or shed (request dropped, counted in `n_shed`)
    under overload; `tenants` round-robins the given tenant names onto
    requests so per-tenant admission budgets can be exercised."""
    rs = np.random.RandomState(seed)
    gaps = rs.exponential(1.0 / rate, size=n_requests)
    arrivals = np.cumsum(gaps)
    shared_prefix_len = min(shared_prefix_len, prompt_len)
    use_key = shared_prefix_len > 0 and not engine.cfg.radix_cache
    if use_key and prefix_key is None:
        prefix_key = f"poisson-load-{seed}"
    shared = rs.randint(0, vocab_size, shared_prefix_len).astype(np.int32)
    prompts = [np.concatenate([
        shared,
        rs.randint(0, vocab_size,
                   prompt_len - shared_prefix_len).astype(np.int32)])
        for _ in range(n_requests)]
    base = (max(engine.reqs) + 1) if engine.reqs else 0   # engine reuse
    ticks0, preempts0 = engine.ticks, engine.n_preemptions
    hits0 = engine.alloc.stats["prefix_hits"]
    hit_tok0 = engine.alloc.stats["radix_hit_tokens"]
    evict0 = engine.alloc.stats["evictions"]
    shed0, budget_skips0 = engine.n_shed, engine.n_budget_skips
    proposed0, accepted0 = engine.spec_proposed, engine.spec_accepted
    reqs = [OnlineRequest(rid=base + i, prompt=prompts[i], max_new=max_new,
                          prefix_key=(prefix_key if use_key else None),
                          prefix_len=(shared_prefix_len if use_key else 0),
                          tenant=(tenants[i % len(tenants)]
                                  if tenants else None))
            for i in range(n_requests)]
    t0 = time.perf_counter()
    submitted = 0
    budget = max_ticks
    while submitted < n_requests or not engine.idle:
        budget -= 1
        if budget < 0:
            raise RuntimeError(f"load run did not drain in {max_ticks} "
                               f"ticks ({submitted}/{n_requests} submitted)")
        now = time.perf_counter()
        while (submitted < n_requests
               and arrivals[submitted] <= now - t0):
            r = reqs[submitted]
            r.arrival_t = t0 + arrivals[submitted]
            if engine.submit(r):
                submitted += 1
            elif r.state == "shed":
                submitted += 1           # gate dropped it; move on
            else:
                break                    # deferred: retry next loop
        if engine.idle and submitted < n_requests:
            time.sleep(min(arrivals[submitted] - (now - t0), 0.01))
            continue
        engine.tick(now)
    t_end = time.perf_counter()

    served = [r for r in reqs if r.state != "shed"]
    n_shed = len(reqs) - len(served)
    assert all(r.done for r in served)
    engine.pop_done()              # keep the engine bounded across loads
    if prefix_key is not None and prefix_key in engine.alloc.prefix_index:
        engine.alloc.drop_prefix(prefix_key)
    engine.alloc.flush_radix()     # repeated loads start cache-cold
    ttft = [r.first_token_t - r.arrival_t for r in served]
    itl: List[float] = []
    for r in served:
        itl.extend(b - a for a, b in zip(r.token_times, r.token_times[1:]))
    n_tokens = sum(len(r.out) for r in served)
    # decode economics: the first token rides prefill, every later token
    # rides a decode/spec tick — speculative acceptance pushes
    # ticks-per-token below 1
    decode_ticks = sum(r.n_decode_ticks for r in served)
    decoded = sum(max(len(r.out) - 1, 0) for r in served)
    proposed = engine.spec_proposed - proposed0
    accepted = engine.spec_accepted - accepted0
    # SLO gate view (overload="slo"): windowed percentiles + deadlines at
    # end of run — ttft_p50/p99_ms above already cover ADMITTED requests
    # only (shed ones never reach a first token), which is the population
    # the deadline is defined over
    slo_view = engine.slo.snapshot() if engine.slo is not None else None
    return {
        "rate_req_s": rate,
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "policy": engine.policy,
        "radix_cache": engine.cfg.radix_cache,
        "paged_attn": engine.paged_attn,
        "wall_s": t_end - t0,
        "tokens_out": n_tokens,
        "tok_s": n_tokens / max(t_end - t0, 1e-9),
        "ttft_p50_ms": 1e3 * _pctl(ttft, 50),
        "ttft_p99_ms": 1e3 * _pctl(ttft, 99),
        "itl_p50_ms": 1e3 * _pctl(itl, 50),
        "itl_p99_ms": 1e3 * _pctl(itl, 99),
        "ticks": engine.ticks - ticks0,
        "preemptions": engine.n_preemptions - preempts0,
        "shed": engine.n_shed - shed0,
        "budget_skips": engine.n_budget_skips - budget_skips0,
        "prefill_compiles": engine.prefill_traces,
        "decode_compiles": engine.decode_traces,
        "draft_compiles": engine.draft_traces,
        "verify_compiles": engine.verify_traces,
        "shared_prefix_len": shared_prefix_len,
        "prefix_hits": engine.alloc.stats["prefix_hits"] - hits0,
        "prefix_hit_rate": (engine.alloc.stats["prefix_hits"] - hits0)
        / max(n_requests, 1),
        "prefix_hit_tokens": (engine.alloc.stats["radix_hit_tokens"]
                              - hit_tok0),
        "cache_evictions": engine.alloc.stats["evictions"] - evict0,
        "spec_k": engine.cfg.spec_k,
        "acceptance_rate": accepted / max(proposed, 1),
        "decode_ticks_per_token": decode_ticks / max(decoded, 1),
        "allocator": dict(engine.alloc.stats),
        "overload": engine.cfg.overload,
        "slo": slo_view,
    }
