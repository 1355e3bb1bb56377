"""Batched serving engine with two schedulers over the MX-quantized
prefill/decode steps (the paper's deployment mode: LATMiX-folded weights +
online T3 + quantized matmuls).

Schedulers (``Engine(..., scheduler=...)``, see ``docs/serving.md``):

``"wave"``
    Static batching: up to B requests prefill together (prompts left-padded
    to a common chunk-bucketed length) and the whole wave decodes until its
    *slowest* member finishes. Simple, minimal host/device traffic — but on
    mixed-length traffic most decode slot-steps are spent on requests that
    already finished.

``"continuous"``
    Continuous batching: a fixed pool of B decode slots backed by one
    persistent KV cache allocated at (B, max_len). Slots are recycled
    ring-style — the step a slot's request emits EOS (or exhausts its
    budget) the next queued request is chunk-prefilled into the freed lane
    while the other lanes keep decoding. Prefill is *chunked*: every prompt
    is processed in fixed attn_chunk-wide pieces with traced start/length
    indices, so all prompt lengths share ONE jit signature and slot swaps
    never recompile. Decode runs with per-slot positions ((B,) ``cur_len``
    vector) and is value-identical per lane to the wave engine's step, so
    each request's tokens are bit-identical across schedulers.

Common posture:
  * cache allocated once at (B, max_len) rounded to the attention chunk,
  * greedy (argmax) sampling by default; per-request temperature /
    top-k / top-p with a replayable seed via ``Request.sampling``
    (``docs/sampling.md``) — temperature 0 stays bit-identical to the
    greedy closures. Per-slot sampling state is (last token, position,
    remaining budget, emitted count = the RNG step index),
  * optional self-drafting speculative decoding (``Engine(spec=...)``,
    continuous scheduler): prompt-lookup drafts + one batched verify
    step per engine step; rejected drafts roll back by rewinding lane
    positions (paged rollback is a pointer rewind — pages were
    preallocated at admission and stale rows stay masked),
  * optional ``eos_id`` — outputs stop at (and include) the first EOS,
  * per-request latency + decode-utilization accounting for the serving
    benchmark (``benchmarks/serving_bench.py``).

Telemetry (``docs/observability.md``): every engine counter lives in a
``repro.obs.MetricsRegistry`` (pass one via ``Engine(metrics=...)`` to
share/export it, else a private one is created) — :meth:`Engine.stats`
is a view over it, including TTFT/TPOT latency histograms. Request
lifecycle and engine-step spans are recorded when a ``repro.obs.Tracer``
is passed (``Engine(tracer=...)``) and exported as Chrome trace-event
JSON; the engine-step spans also land on a running profiler's host
trace. With no tracer the hot loop records no span.

Clocks: *intervals* (TTFT/TPOT, throughput, span timestamps) are always
measured with ``time.perf_counter()`` (monotonic — wall clock can step
backwards under NTP); ``time.time()`` survives only as the *absolute*
``Request.t_submit``/``t_first``/``t_done`` timestamps.

Lifecycle (``docs/robustness.md``): every request ends in exactly one
terminal :class:`~repro.serving.policy.RequestState` — ``FINISHED``,
``CANCELLED`` (:meth:`Engine.cancel`), ``TIMED_OUT`` (per-request TTFT /
end-to-end deadlines, checked while queued and between decode bursts),
``FAILED`` (the per-lane non-finite-logit guard, or a request that can
*never* fit the pool), or ``PREEMPTED`` (evicted under pressure with the
retry budget exhausted). Admission is priority-ordered; under pool
exhaustion the lowest-priority running request is preempted (pages
deref'd, request requeued with bounded retries + exponential backoff —
re-prefill is cheap under the paged prefix cache) instead of blocking
admission behind it. All of it is driven by a
:class:`~repro.serving.policy.SchedulingPolicy` and observable through
terminal-state counters and lifecycle trace spans; a seeded
:class:`~repro.serving.faults.FaultInjector` (``Engine(faults=...)``)
can deterministically force every one of these paths for chaos tests.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import hashlib
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.quantize import KVCacheQuant, QuantMode
from repro.models import api
from repro.obs import MetricsRegistry, Tracer
from repro.serving.faults import FaultInjector
from repro.serving.policy import (RequestQueue, RequestState,
                                  SchedulingPolicy, ShedError, SpecConfig,
                                  TERMINAL_STATES, pick_victim)
from repro.serving.sampling import GREEDY, SamplingParams, propose_ngram
from repro.serving import sampling

SCHEDULERS = ("wave", "continuous")
KV_LAYOUTS = ("contiguous", "paged")


class BlockAllocator:
    """Ref-counted allocator over the paged KV pool's page ids.

    Pages ids live in [reserved, n_pages) (ids below ``reserved`` are
    engine scrap pages that dead lanes park their block tables on). A
    page is in exactly one of three states:

      * **free** — on the free list, content garbage;
      * **referenced** — ``ref > 0`` block tables point at it;
      * **cached** — ``ref == 0`` but registered under a prefix hash
        (:meth:`register`): its KV bytes are a reusable prompt-prefix
        page, parked in an LRU and reclaimed (evicted + unregistered)
        only when the free list runs dry.

    :meth:`alloc` hands out ``ref == 1`` pages, preferring free pages and
    LRU-evicting cached ones under pressure; it returns ``None`` when
    even eviction cannot cover the request (the engine's admission
    backpressure). :meth:`lookup`/:meth:`incref` revive a cached page
    into the referenced state — that is the prefix *hit* path. All
    bookkeeping is host-side and O(1) per page transition."""

    def __init__(self, n_pages: int, page_size: int, reserved: int = 0):
        if n_pages - reserved < 1:
            raise ValueError(f"pool needs at least one allocatable page "
                             f"(n_pages={n_pages}, reserved={reserved})")
        self.n_pages, self.page_size = n_pages, page_size
        self.reserved = reserved
        self._free = collections.deque(range(reserved, n_pages))
        self._ref = {p: 0 for p in range(reserved, n_pages)}
        self._page_of: dict = {}                # prefix hash -> page id
        self._hash_of: dict = {}                # page id -> prefix hash
        self._lru: collections.OrderedDict = collections.OrderedDict()
        self.evicted = 0                        # cumulative LRU evictions

    @property
    def capacity(self) -> int:
        """Total allocatable pages."""
        return self.n_pages - self.reserved

    @property
    def available(self) -> int:
        """Pages obtainable right now (free + evictable cached)."""
        return len(self._free) + len(self._lru)

    @property
    def in_use(self) -> int:
        """Pages referenced by at least one block table."""
        return self.capacity - self.available

    @property
    def cached(self) -> int:
        """Pages parked for prefix reuse (ref == 0, registered)."""
        return len(self._lru)

    @property
    def free(self) -> int:
        """Pages on the free list (content garbage)."""
        return len(self._free)

    @property
    def resident(self) -> int:
        """Pages holding live KV bytes (referenced or cached)."""
        return self.capacity - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages at ref == 1, or None (caller applies
        backpressure). Eviction order is least-recently-cached first."""
        if n > self.available:
            return None
        out = []
        for _ in range(n):
            if self._free:
                p = self._free.popleft()
            else:
                p, _ = self._lru.popitem(last=False)
                del self._page_of[self._hash_of.pop(p)]
                self.evicted += 1
            self._ref[p] = 1
            out.append(p)
        return out

    def incref(self, p: int) -> None:
        if self._ref[p] == 0:
            self._lru.pop(p, None)              # cached -> referenced
        self._ref[p] += 1

    def decref(self, p: int) -> None:
        if self._ref[p] <= 0:
            raise ValueError(f"decref of unreferenced page {p}")
        self._ref[p] -= 1
        if self._ref[p] == 0:
            if p in self._hash_of:
                self._lru[p] = True             # cached: evictable
            else:
                self._free.append(p)

    def register(self, h, p: int) -> Optional[int]:
        """Publish page ``p`` as the cached copy of prefix hash ``h``.
        First registration wins: if ``h`` is already served by another
        page (or ``p`` already carries a hash) nothing changes and the
        existing mapping is returned."""
        if h in self._page_of or p in self._hash_of:
            return self._page_of.get(h)
        self._page_of[h] = p
        self._hash_of[p] = h
        return p

    def lookup(self, h) -> Optional[int]:
        """Page cached under prefix hash ``h`` (refreshing its LRU
        recency), or None."""
        p = self._page_of.get(h)
        if p is not None and self._ref[p] == 0:
            self._lru.move_to_end(p)
        return p

    def flush_cache(self) -> int:
        """Evict every cached (unreferenced, registered) page back to
        the free list; returns how many were reclaimed. The forced-
        eviction chaos hook (``FaultInjector`` point ``evict_cache``) —
        referenced pages are untouched."""
        n = 0
        while self._lru:
            p, _ = self._lru.popitem(last=False)
            del self._page_of[self._hash_of.pop(p)]
            self._free.append(p)
            self.evicted += 1
            n += 1
        return n

    def check(self) -> dict:
        """Verify the allocator's internal invariants; raises
        AssertionError on any violation, else returns the accounting
        ``{"free", "cached", "in_use", "evicted"}``. The chaos /
        property tests call this after every interleaved operation:
        free + cached + referenced must partition [reserved, n_pages)
        exactly — a page leak or double-free shows up here."""
        if any(r < 0 for r in self._ref.values()):
            raise AssertionError("negative refcount")
        fs = set(self._free)
        cs = set(self._lru)
        rs = {p for p, r in self._ref.items() if r > 0}
        if len(fs) != len(self._free):
            raise AssertionError("duplicate page on the free list")
        for a, b, what in ((fs, cs, "free/cached"), (fs, rs, "free/ref"),
                           (cs, rs, "cached/ref")):
            if a & b:
                raise AssertionError(f"page in two states: {what} "
                                     f"{sorted(a & b)}")
        allp = set(range(self.reserved, self.n_pages))
        if fs | cs | rs != allp:
            raise AssertionError(
                f"pages unaccounted for: missing {sorted(allp - fs - cs - rs)}"
                f" extra {sorted((fs | cs | rs) - allp)}")
        for p in cs:
            if p not in self._hash_of:
                raise AssertionError(f"cached page {p} has no hash")
        if len(self._page_of) != len(self._hash_of):
            raise AssertionError("hash<->page maps out of sync")
        for h, p in self._page_of.items():
            if self._hash_of.get(p) != h:
                raise AssertionError(f"hash map mismatch on page {p}")
        if self.in_use + self.free + self.cached != self.capacity:
            raise AssertionError(
                f"in_use {self.in_use} + free {self.free} + cached "
                f"{self.cached} != capacity {self.capacity}")
        return {"free": self.free, "cached": self.cached,
                "in_use": self.in_use, "evicted": self.evicted}


@dataclasses.dataclass(eq=False)       # identity eq/hash: a request is
class Request:                         # a handle, not a value
    """One generation request.

    prompt: (S,) int32 token ids. max_new: decode budget (the output is
    shorter only if ``Engine(eos_id=...)`` is hit first). ``on_token`` is
    an optional streaming callback invoked with each emitted int token as
    it becomes available (per step under the continuous scheduler; at wave
    end under the wave scheduler). ``out`` is filled with the emitted
    int32 token array when the request completes.

    Timestamps: ``t_submit``/``t_first``/``t_done`` are *absolute* wall
    clock (``time.time()``, for logs); the ``m_*`` mirrors are
    ``time.perf_counter()`` readings — monotonic, the ones every
    duration (TTFT = ``m_first - m_submit``, TPOT =
    ``(m_done - m_first)/(len(out) - 1)``) is computed from. Under the
    wave scheduler all tokens are delivered at wave end, so
    ``m_first == m_done`` and only TTFT (== wave latency) is
    meaningful.

    Lifecycle (``docs/robustness.md``): ``state`` walks
    QUEUED -> RUNNING -> one terminal :class:`RequestState`; ``error``
    carries the human-readable reason for any non-FINISHED end.
    ``priority`` orders admission (higher first) and gates preemption —
    only strictly lower-priority running requests can be evicted for
    this one. ``deadline_ms`` (submit -> done) and ``ttft_deadline_ms``
    (submit -> first token) override the engine policy's defaults; None
    defers to the policy. ``request_id`` keys :meth:`Engine.cancel`
    (auto-assigned at submit when None). ``retries``/``preemptions``/
    ``not_before`` are preemption bookkeeping (engine-managed), and
    ``_gen`` accumulates emitted tokens across preemptions so a resumed
    request re-prefills prompt+_gen and continues bit-identically."""

    prompt: np.ndarray                  # (S,) int32
    max_new: int = 16
    out: Optional[np.ndarray] = None
    t_submit: float = 0.0               # wall clock (absolute)
    t_first: float = 0.0
    t_done: float = 0.0
    m_submit: float = 0.0               # perf_counter (durations)
    m_first: float = 0.0
    m_done: float = 0.0
    on_token: Optional[Callable[[int], None]] = None
    trace_track: Optional[str] = None   # tracer track name (engine-set)
    # --- lifecycle (docs/robustness.md) ---
    priority: int = 0                   # higher admits (and evicts) first
    deadline_ms: Optional[float] = None          # submit -> done TTL
    ttft_deadline_ms: Optional[float] = None     # submit -> first token
    request_id: Optional[str] = None    # cancel() handle (engine-set)
    state: RequestState = RequestState.QUEUED
    error: Optional[str] = None         # reason for a non-FINISHED end
    retries: int = 0                    # re-admissions after preemption
    preemptions: int = 0                # times evicted from a lane
    not_before: float = 0.0             # backoff hold (perf_counter)
    # sampling: None (or temperature<=0) decodes greedily, bit-identical
    # to an engine without sampling at all. Otherwise temperature/top-k/
    # top-p with a per-request seed: token i is drawn from
    # PRNGKey(seed) folded with its emission index, so a run is
    # replayable and a preemption-resume re-seeds from len(_gen) and
    # replays its own tail deterministically (docs/sampling.md).
    sampling: Optional[SamplingParams] = None
    _gen: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _Slot:
    """Per-slot decode state (continuous scheduler)."""

    req: Request
    toks: List[int]          # emitted tokens (greedy sampling state)
    pos: int                 # cache fill == next write position
    remaining: int           # decode budget left


class Engine:
    """Serving engine over ``api.prefill``/``api.decode``.

    ``params`` may hold dense arrays or packed-HBM ``PackedWeight``
    leaves (artifact serving, see :meth:`from_artifact`): the quantized
    execution path dequantizes packed weights lazily inside the compiled
    prefill/decode steps — or, with ``backend='fused'``, consumes the
    packed layout directly in the Pallas MX GEMM kernels (see
    ``core.quantize``). Both schedulers work with both backends.

    Streaming API: :meth:`submit` enqueues a request, :meth:`step` runs
    one scheduler step and returns the requests completed by it,
    :meth:`drain` steps until idle. :meth:`generate` = submit-all + drain,
    returning the input list (mutated in place, original order).
    """

    # counters that reset_stats() windows; compile counters are
    # deliberately absent (cumulative for the engine lifetime — the jit
    # cache never resets)
    _WINDOW_KEYS = ("admitted", "decode_steps", "slot_steps",
                    "useful_decode_tokens", "prefill_chunk_steps",
                    "prefill_batched_steps", "prefill_lane_steps",
                    "prefix_hit_tokens", "blocks_evicted",
                    "spec_proposed_tokens", "spec_accepted_tokens",
                    "admit_seconds")

    def __init__(self, params, cfg: ArchConfig, qm: QuantMode,
                 batch_size: int = 4, max_len: int = 256,
                 backend: str | None = None,
                 bucket_prompts: bool = True,
                 scheduler: str = "wave",
                 eos_id: Optional[int] = None,
                 kv_cache: "str | KVCacheQuant | None" = None,
                 kv_layout: str = "contiguous",
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 policy: Optional[SchedulingPolicy] = None,
                 faults: Optional[FaultInjector] = None,
                 spec: Optional[SpecConfig] = None):
        """bucket_prompts=True rounds prompt lengths up to the attention
        chunk so distinct lengths reuse one prefill compile (wave) / keep
        the chunk grid aligned (continuous). Bucketed pads are left-pad
        tokens and are attended like the engine's existing ragged-wave
        pads (static batching, no per-row masks) — pass False for
        unpadded, per-length compiles.

        scheduler='continuous' requires a token-embedding KV-cache family
        (dense/moe); recurrent families (hybrid/ssm) serve with 'wave'.

        kv_cache: 'mxfp8' | 'mxint8' | 'mxfp4' | 'mxint4' stores the KV
        cache MX-quantized (codes + E8M0 scale bytes per 32-block along
        kv_dim; see ``docs/kv-cache.md``) — keys/values are quantized at
        append time and decode attention reads the packed bytes (the
        Pallas flash-decode kernel under ``backend='fused'``, decode-in-
        place otherwise). Greedy outputs match the dense cache within a
        small tolerance; 'none'/None (default) keeps the dense fp cache
        bit-identical to previous behavior. Attention-cache families
        only (dense/moe/hybrid), and kv_dim must divide into 32-blocks.

        kv_layout: 'contiguous' (default) reserves one (max_len, kv_dim)
        lane per slot; 'paged' allocates one pool of fixed-size pages
        addressed through per-request block tables, with ref-counted
        hash-based prefix caching — a shared prompt prefix is prefilled
        once and reused by reference (see ``docs/paged-kv.md``). Paged
        serving requires scheduler='continuous' and a KV-cache family
        (dense/moe); it places prompts unpadded at position 0 (prompt
        bucketing does not apply — identical token placement is what
        makes prefixes shareable). page_size (tokens per page; default
        the smallest multiple of attn_chunk >= 64) must be a multiple of
        32 (the MX block) and of cfg.attn_chunk (so prefix-resume
        positions stay chunk-aligned); n_pages sizes the pool (default:
        one scrap page + batch_size * ceil(max_len/page_size), the same
        budget as the contiguous pool).

        metrics: a ``repro.obs.MetricsRegistry`` to report into (shared
        across engines / exported by the caller); None creates a private
        one. The registry is always on — counter updates cost what the
        plain attributes they replaced cost. tracer: a
        ``repro.obs.Tracer`` recording request-lifecycle and engine-step
        spans (Chrome trace-event export, ``docs/observability.md``);
        None (default) records nothing — no timestamps or host syncs are
        added to the serving loop.

        policy: a ``repro.serving.policy.SchedulingPolicy`` — default
        deadlines, the preemption switch, retry budget/backoff
        (``docs/robustness.md``); None uses the policy defaults (no
        deadlines, preemption on). faults: a seeded
        ``repro.serving.faults.FaultInjector`` whose rules fire at the
        engine's injection points (chaos tests only; None — the
        default — adds zero work to the serving loop).

        spec: a ``repro.serving.policy.SpecConfig`` turns on
        self-drafting speculative decoding (``docs/sampling.md``):
        every engine step proposes up to ``spec.k`` draft tokens per
        lane by prompt lookup and verifies them in one batched
        multi-token forward; rejected drafts roll back by rewinding the
        lane's position (paged: a pointer rewind inside the pages the
        request already owns). Continuous scheduler + KV-cache families
        only. Outputs are unchanged: greedy spec decoding is
        token-bit-identical to non-spec greedy and sampled spec
        preserves the sampling distribution — spec trades draft +
        verify cost against tokens per step."""
        if cfg.family == "encoder":
            raise ValueError("encoder archs are not served autoregressively")
        if scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {scheduler!r} "
                             f"(expected one of {SCHEDULERS})")
        if kv_layout not in KV_LAYOUTS:
            raise ValueError(f"unknown kv_layout {kv_layout!r} "
                             f"(expected one of {KV_LAYOUTS})")
        if kv_layout == "paged":
            # checked before the generic scheduler/family gating so the
            # error names the actual conflict (ring buffers cannot page)
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"kv_layout='paged' pages an attention KV cache "
                    f"through block tables; family {cfg.family!r} keeps "
                    f"recurrent ring-buffer state (griffin/ssm hybrids) "
                    f"that cannot be paged — serve it with "
                    f"kv_layout='contiguous'")
            if scheduler != "continuous":
                raise ValueError(
                    "kv_layout='paged' requires scheduler='continuous'; "
                    "the wave scheduler keeps the existing contiguous "
                    "per-wave cache")
        if scheduler == "continuous" and (
                cfg.family not in ("dense", "moe") or not cfg.embed_inputs):
            raise ValueError(
                "continuous scheduler requires a token-embedding KV-cache "
                "family (dense/moe); recurrent-state families must use "
                "scheduler='wave'")
        if spec is not None and scheduler != "continuous":
            raise ValueError(
                "speculative decoding (spec=...) requires "
                "scheduler='continuous': drafts are proposed per slot "
                "from each request's own emitted tokens")
        self.policy = policy if policy is not None else SchedulingPolicy()
        self.spec = spec
        self._faults = faults
        self.kv_quant = KVCacheQuant.parse(kv_cache)
        if self.kv_quant is not None:
            if cfg.family == "ssm":
                raise ValueError("kv_cache quantization requires an "
                                 "attention KV cache; ssm serves with "
                                 "kv_cache='none'")
            if cfg.kv_dim % 32 != 0:
                raise ValueError(
                    f"kv_cache quantization needs kv_dim % 32 == 0 (one "
                    f"E8M0 scale per 32-block along the cache feature "
                    f"axis), got kv_dim={cfg.kv_dim} for {cfg.name!r} — "
                    f"serve this model with kv_cache='none', or pick an "
                    f"arch whose n_kv_heads*head_dim is a multiple of 32")
        if backend is not None:
            qm = qm.with_backend(backend)
        self.params, self.cfg, self.qm = params, cfg, qm
        self.B = batch_size
        self.bucket_prompts = bucket_prompts
        self.scheduler = scheduler
        self.eos_id = eos_id
        chunk = cfg.attn_chunk
        self.max_len = (max_len + chunk - 1) // chunk * chunk

        self.kv_layout = kv_layout
        self.page_size = 0
        self.pages_per_slot = 0
        self._alloc: Optional[BlockAllocator] = None
        if kv_layout == "paged":
            if page_size is None:
                page_size = chunk * max(1, -(-64 // chunk))
            if page_size % 32 != 0:
                raise ValueError(
                    f"page_size must be a multiple of the MX 32-block "
                    f"(a page is a fixed run of MX blocks), got "
                    f"{page_size}")
            if page_size % chunk != 0:
                raise ValueError(
                    f"page_size must be a whole number of attention "
                    f"chunks so prefix-resume positions stay "
                    f"chunk-aligned; got page_size={page_size}, "
                    f"attn_chunk={chunk}")
            self.page_size = page_size
            self.pages_per_slot = -(-self.max_len // page_size)
            if n_pages is None:
                n_pages = 1 + self.B * self.pages_per_slot
            if n_pages < 1 + self.pages_per_slot:
                raise ValueError(
                    f"n_pages={n_pages} cannot hold one scrap page plus "
                    f"a full-length request "
                    f"({self.pages_per_slot} pages for max_len="
                    f"{self.max_len})")
            # page 0 is the scrap page: dead lanes' block tables park on
            # it, so their idle decode writes never touch live pages
            self._alloc = BlockAllocator(n_pages, page_size, reserved=1)
            self._tables = np.zeros((self.B, self.pages_per_slot),
                                    np.int32)
            self._tables_dev = None
            self._slot_pages: List[Optional[List[int]]] = [None] * self.B

        # --- telemetry: every counter lives in the metrics registry;
        # stats() is a view over it (docs/observability.md has the
        # catalog). Compile accounting: one prefill compile per distinct
        # (B, S) wave shape (bucketing in _wave keeps this set small);
        # the continuous scheduler's chunked prefill and vector decode
        # each compile once.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer
        reg = self.metrics
        self._prefill_shapes: set = set()
        self._chunk_shapes: set = set()
        self._decode_shapes: set = set()
        self._c_compiles = {
            kind: reg.counter("serving_compiles_total", {"step": kind},
                              help="jit signatures compiled (cumulative "
                                   "over the engine lifetime; never "
                                   "reset — the jit cache is an "
                                   "engine-lifetime property)")
            for kind in ("prefill", "prefill_chunk", "decode")}
        self._c_admitted = reg.counter(
            "serving_requests_admitted_total",
            help="requests admitted into a scheduler lane")
        self._c_decode_steps = reg.counter(
            "serving_decode_steps_total",
            help="batched decode steps dispatched")
        self._c_slot_steps = reg.counter(
            "serving_slot_steps_total",
            help="decode steps x lanes (utilization denominator)")
        self._c_useful = reg.counter(
            "serving_useful_decode_tokens_total",
            help="decoded tokens that made it into a request's output")
        self._c_chunk_steps = reg.counter(
            "serving_prefill_chunk_steps_total",
            help="chunked-prefill invocations (drops under prefix hits)")
        self._c_prefill_batched = reg.counter(
            "serving_prefill_batched_steps_total",
            help="chunked-prefill invocations that carried >=2 lanes "
                 "(paged batched admission — "
                 "policy.max_prefill_lanes_per_step)")
        self._c_prefill_lane_steps = reg.counter(
            "serving_prefill_lane_steps_total",
            help="chunked-prefill invocations x active lanes; "
                 "lane_steps / chunk_steps is the mean prefill batch "
                 "occupancy (1.0 == strictly serial admission)")
        self._h_prefill_batch = reg.histogram(
            "serving_prefill_batch_size", unit="lanes",
            help="active lanes per chunked-prefill invocation (serial "
                 "admission observes 1 per chunk)")
        self._c_prefix_hit_toks = reg.counter(
            "serving_prefix_hit_tokens_total", unit="tokens",
            help="prompt tokens served from cached prefix pages")
        self._c_prefix_hits = reg.counter(
            "serving_prefix_cache_hits_total",
            help="paged admissions that reused >=1 cached prefix page")
        self._c_prefix_misses = reg.counter(
            "serving_prefix_cache_misses_total",
            help="paged admissions with no cached prefix page")
        self._c_evicted = reg.counter(
            "serving_blocks_evicted_total",
            help="cached prefix pages reclaimed by LRU eviction")
        self._c_admit_s = reg.counter(
            "serving_admit_seconds_total", unit="s",
            help="host seconds in the admission phase of continuous "
                 "steps (prefix match, page allocation, prefill, first "
                 "token); no lane decodes meanwhile")
        self._g_blocks_in_use = reg.gauge(
            "serving_blocks_in_use", unit="pages",
            help="pages referenced by live block tables")
        self._g_blocks_cached = reg.gauge(
            "serving_blocks_cached", unit="pages",
            help="unreferenced pages parked for prefix reuse")
        self._g_queue_depth = reg.gauge(
            "serving_queue_depth", unit="requests",
            help="requests waiting for a lane")
        self._h_ttft = reg.histogram(
            "serving_ttft_seconds", unit="s",
            help="time to first token (submit -> first token available; "
                 "wave scheduler: == wave latency, tokens are delivered "
                 "at wave end)")
        self._h_tpot = reg.histogram(
            "serving_tpot_seconds", unit="s",
            help="time per output token after the first (continuous "
                 "scheduler only — the wave scheduler delivers all "
                 "tokens at once)")
        self._h_latency = reg.histogram(
            "serving_request_latency_seconds", unit="s",
            help="submit -> done")
        self._h_queue_wait = reg.histogram(
            "serving_queue_wait_seconds", unit="s",
            help="submit -> admission start (continuous scheduler)")
        self._c_submitted = reg.counter(
            "serving_requests_submitted_total",
            help="requests accepted by submit()")
        self._c_terminal = {
            s: reg.counter("serving_requests_terminal_total",
                           {"state": s.value},
                           help="requests reaching this terminal "
                                "lifecycle state (docs/robustness.md); "
                                "the series sum equals submitted "
                                "requests at quiescence")
            for s in (RequestState.FINISHED, RequestState.CANCELLED,
                      RequestState.TIMED_OUT, RequestState.FAILED,
                      RequestState.PREEMPTED, RequestState.SHED)}
        self._c_preempt = reg.counter(
            "serving_preemptions_total",
            help="running requests evicted from a lane (priority "
                 "inversion or page pressure); each is requeued with "
                 "backoff until its retry budget runs out")
        self._c_nan = reg.counter(
            "serving_nan_guard_trips_total",
            help="requests failed by the per-lane non-finite-logit "
                 "guard (the rest of the decode batch continues)")
        self._c_never_fit = reg.counter(
            "serving_rejected_never_fit_total",
            help="requests rejected at admission because prompt+budget "
                 "can never fit the pool (terminal FAILED, not requeued)")
        self._c_shed = reg.counter(
            "serving_requests_shed_total",
            help="requests rejected by admission control at submit() "
                 "(queue depth / per-priority / token-budget caps — "
                 "docs/server.md); terminal SHED, never requeued")
        self._c_spec_proposed = reg.counter(
            "serving_spec_proposed_total", unit="tokens",
            help="draft tokens proposed by the prompt-lookup drafter "
                 "and scored by a verify step")
        self._c_spec_accepted = reg.counter(
            "serving_spec_accepted_total", unit="tokens",
            help="proposed draft tokens accepted by the verify step "
                 "(acceptance rate = accepted / proposed)")
        self._evicted_seen = 0       # allocator.evicted -> counter delta
        # windowed-vs-cumulative split (see stats()/reset_stats())
        self._window_base = {k: 0 for k in self._WINDOW_KEYS}

        def prefill(params, toks):
            return api.prefill(params, cfg, toks, qm, max_len=self.max_len,
                               kv_quant=self.kv_quant)

        def prefill_chunk(params, cache, toks, start, last_idx):
            return api.prefill_chunk(params, cfg, cache, toks, start,
                                     last_idx, qm)

        def decode(params, cache, toks, cur_len, poison_lane):
            logits, cache = api.decode(params, cfg, cache, toks, cur_len, qm)
            # per-lane NaN/Inf guard: `ok` rides back with the sampled
            # tokens (fetched in the existing burst sync — no extra host
            # round trip). poison_lane is the nan_logits chaos hook; -1
            # (the always case outside chaos tests) makes the where a
            # bitwise identity.
            lanes = jnp.arange(logits.shape[0], dtype=jnp.int32)
            logits = jnp.where((lanes == poison_lane)[:, None],
                               jnp.float32(jnp.nan).astype(logits.dtype),
                               logits)
            ok = jnp.isfinite(logits).all(axis=-1)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), ok,
                    cache)

        def merge_slot(cache, slot_cache, i):
            def upd(c, s):
                idx = (jnp.int32(0), i) + (jnp.int32(0),) * (c.ndim - 2)
                return jax.lax.dynamic_update_slice(c, s, idx)
            return jax.tree.map(upd, cache, slot_cache)

        def prefill_chunk_paged(params, cache, toks, tables, start,
                                last_idx):
            return api.prefill_chunk_paged(params, cfg, cache, tables,
                                           toks, start, last_idx, qm)

        def decode_paged(params, cache, toks, cur_len, tables,
                         poison_lane):
            logits, cache = api.decode_paged(params, cfg, cache, toks,
                                             cur_len, tables, qm)
            lanes = jnp.arange(logits.shape[0], dtype=jnp.int32)
            logits = jnp.where((lanes == poison_lane)[:, None],
                               jnp.float32(jnp.nan).astype(logits.dtype),
                               logits)
            ok = jnp.isfinite(logits).all(axis=-1)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), ok,
                    cache)

        def copy_page(cache, src, dst):
            # clone one pool page (all layers, k and v, codes and
            # scales): the admission copy-on-write of a partially
            # reused prefix page
            return jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), cache)

        # sampled decode variants: same forward + NaN guard as the
        # greedy closures (which stay byte-identical — their compile
        # counts are pinned by tests), with the argmax replaced by the
        # per-lane seeded sampler. Dispatched only when a live lane is
        # actually non-greedy, so greedy-only traffic never compiles or
        # pays for them.
        def decode_sampled(params, cache, toks, cur_len, poison_lane,
                           temps, top_ks, top_ps, seeds, steps):
            logits, cache = api.decode(params, cfg, cache, toks, cur_len,
                                       qm)
            lanes = jnp.arange(logits.shape[0], dtype=jnp.int32)
            logits = jnp.where((lanes == poison_lane)[:, None],
                               jnp.float32(jnp.nan).astype(logits.dtype),
                               logits)
            ok = jnp.isfinite(logits).all(axis=-1)
            nxt = sampling.sample_tokens(logits, temps, top_ks, top_ps,
                                         seeds, steps)
            return nxt, ok, cache

        def decode_paged_sampled(params, cache, toks, cur_len, tables,
                                 poison_lane, temps, top_ks, top_ps,
                                 seeds, steps):
            logits, cache = api.decode_paged(params, cfg, cache, toks,
                                             cur_len, tables, qm)
            lanes = jnp.arange(logits.shape[0], dtype=jnp.int32)
            logits = jnp.where((lanes == poison_lane)[:, None],
                               jnp.float32(jnp.nan).astype(logits.dtype),
                               logits)
            ok = jnp.isfinite(logits).all(axis=-1)
            nxt = sampling.sample_tokens(logits, temps, top_ks, top_ps,
                                         seeds, steps)
            return nxt, ok, cache

        # speculative verify: one multi-token forward scores the current
        # token + drafts, then the acceptance rule picks the emitted run
        # — all fused in one jit so a verify step is a single dispatch +
        # a single host sync, like a decode step.
        # The host-varying per-step state rides in ONE packed int32
        # array (one device_put per verify step instead of five):
        #   packed[:, :C]  = verify inputs [cur, d_1..d_K]
        #   packed[:, C]   = per-lane write position
        #   packed[:, C+1] = per-lane valid-slot count (1 + draft len)
        #   packed[:, C+2] = per-lane emission index (the RNG step)
        #   packed[:, C+3] = poisoned lane id, broadcast (-1 = none)
        # and the three results come back as one packed int32 array
        # [out | n_emit | ok] — one blocking fetch per step instead of
        # three. The drafts spec_accept needs are exactly toks[:, 1:],
        # sliced inside the jit rather than committed separately.
        def _verify_unpack(packed):
            C = packed.shape[1] - 4
            return (packed[:, :C], packed[:, C], packed[:, C + 1],
                    packed[:, C + 2], packed[0, C + 3])

        def _verify_accept(logits, toks, n_valid, steps, poison_lane,
                           temps, top_ks, top_ps, seeds):
            lanes = jnp.arange(logits.shape[0], dtype=jnp.int32)
            logits = jnp.where((lanes == poison_lane)[:, None, None],
                               jnp.float32(jnp.nan).astype(logits.dtype),
                               logits)
            out, n_emit, okrow = sampling.spec_accept(
                logits, toks[:, 1:], n_valid - 1, temps, top_ks, top_ps,
                seeds, steps)
            return jnp.concatenate(
                [out, n_emit[:, None], okrow.astype(jnp.int32)], axis=1)

        def verify_step(params, cache, packed, temps, top_ks, top_ps,
                        seeds):
            toks, pos, n_valid, steps, poison_lane = _verify_unpack(
                packed)
            logits, cache = api.verify(params, cfg, cache, toks, pos,
                                       n_valid, qm)
            res = _verify_accept(logits, toks, n_valid, steps,
                                 poison_lane, temps, top_ks, top_ps,
                                 seeds)
            return res, cache

        def verify_step_paged(params, cache, packed, tables, temps,
                              top_ks, top_ps, seeds):
            toks, pos, n_valid, steps, poison_lane = _verify_unpack(
                packed)
            logits, cache = api.verify_paged(params, cfg, cache, toks,
                                             pos, n_valid, tables, qm)
            res = _verify_accept(logits, toks, n_valid, steps,
                                 poison_lane, temps, top_ks, top_ps,
                                 seeds)
            return res, cache

        self._prefill = jax.jit(prefill)
        self._prefill_chunk = jax.jit(prefill_chunk)
        self._decode = jax.jit(decode)
        self._merge = jax.jit(merge_slot)
        self._prefill_chunk_paged = jax.jit(prefill_chunk_paged)
        self._decode_paged = jax.jit(decode_paged)
        self._copy_page = jax.jit(copy_page)
        self._decode_sampled = jax.jit(decode_sampled)
        self._decode_paged_sampled = jax.jit(decode_paged_sampled)
        self._verify = jax.jit(verify_step)
        self._verify_paged = jax.jit(verify_step_paged)
        self._sample_tokens = jax.jit(sampling.sample_tokens)

        # streaming state
        self._queue = RequestQueue(       # priority + backoff admission
            max_depth=self.policy.max_queue_depth)
        self._shed_streak = 0             # consecutive sheds -> Retry-After
        self._by_id: dict = {}            # request_id -> live Request
        self._next_id = 0                 # request_id autonumber
        self._slots: List[Optional[_Slot]] = [None] * self.B
        self._admit_cursor = 0            # ring rotation over the lanes
        self._cache = None                # persistent (B, max_len) KV pool
        self._slot_cache = None           # (1, max_len) admission scratch
        self._home = None                 # canonical input sharding (lazy)
        self._greedy_vecs: dict = {}      # batch -> constant samp vectors

    # ------------------------------------------------------------------
    # Telemetry helpers + legacy counter attributes (registry views)
    # ------------------------------------------------------------------

    @property
    def admitted(self) -> int:
        return int(self._c_admitted.value)

    @property
    def decode_steps(self) -> int:
        return int(self._c_decode_steps.value)

    @property
    def slot_steps(self) -> int:
        return int(self._c_slot_steps.value)

    @property
    def useful_decode_tokens(self) -> int:
        return int(self._c_useful.value)

    @property
    def prefill_chunk_steps(self) -> int:
        return int(self._c_chunk_steps.value)

    @property
    def prefix_hit_tokens(self) -> int:
        return int(self._c_prefix_hit_toks.value)

    @property
    def prefill_compiles(self) -> int:
        return int(self._c_compiles["prefill"].value)

    @property
    def prefill_chunk_compiles(self) -> int:
        return int(self._c_compiles["prefill_chunk"].value)

    @property
    def decode_compiles(self) -> int:
        return int(self._c_compiles["decode"].value)

    def _span(self, name: str, **args):
        """Engine-track span, or a no-op when tracing is off."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, **args)

    def _count_compile(self, kind: str, key) -> None:
        """First sighting of a jit signature: bump the cumulative
        compile counter and drop a distinctly-marked trace event."""
        shapes = {"prefill": self._prefill_shapes,
                  "prefill_chunk": self._chunk_shapes,
                  "decode": self._decode_shapes}[kind]
        if key in shapes:
            return
        shapes.add(key)
        self._c_compiles[kind].inc()
        if self.tracer is not None:
            self.tracer.instant(f"compile:{kind}", cat="compile",
                                signature=str(key))

    def _sync_alloc_metrics(self) -> None:
        """Mirror BlockAllocator state into gauges/counters (paged)."""
        if self._alloc is None:
            return
        self._g_blocks_in_use.set(self._alloc.in_use)
        self._g_blocks_cached.set(self._alloc.cached)
        if self._alloc.evicted > self._evicted_seen:
            self._c_evicted.inc(self._alloc.evicted - self._evicted_seen)
            self._evicted_seen = self._alloc.evicted

    def _home_sharding(self):
        """Canonical replicated sharding for fresh host-built inputs (the
        pool cache, a burst's first cur/pos). Uncommitted arrays are a
        different jit cache key than the committed outputs the steps
        produce — without this, the chunk-prefill/decode/merge functions
        each compile twice (fresh-input signature + steady state), a
        multi-second hit that landed inside the timed serving run and was
        most of the continuous scheduler's tok/s gap. Scope: this matches
        the steps' output shardings on single-replica serving (the tested
        posture); under a live multi-device mesh whose steps constrain
        the cache to batch/model axes, the first step after a fresh input
        can still compile separately."""
        if self._home is None:
            home = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            for leaf in jax.tree.leaves(self.params):
                s = getattr(leaf, "sharding", None)
                if isinstance(s, jax.sharding.NamedSharding):
                    home = jax.sharding.NamedSharding(
                        s.mesh, jax.sharding.PartitionSpec())
                    break
            self._home = home
        return self._home

    def _commit(self, tree):
        """device_put a fresh pytree onto the canonical sharding."""
        return jax.device_put(tree, self._home_sharding())

    @classmethod
    def from_artifact(cls, path, batch_size: int = 4, max_len: int = 256,
                      eager: bool = False, verify: bool = True,
                      backend: str | None = None,
                      scheduler: str = "wave",
                      eos_id: Optional[int] = None,
                      kv_cache: "str | KVCacheQuant | None" = None,
                      kv_layout: str = "contiguous",
                      page_size: Optional[int] = None,
                      n_pages: Optional[int] = None,
                      metrics: Optional[MetricsRegistry] = None,
                      tracer: Optional[Tracer] = None,
                      policy: Optional[SchedulingPolicy] = None,
                      faults: Optional[FaultInjector] = None,
                      spec: Optional[SpecConfig] = None) -> "Engine":
        """Serve directly from an exported artifact directory: no
        calibration, no re-quantization — load packed bytes and go.

        eager=False keeps quantized weights 4-bit packed in HBM
        (dequantized per layer inside the compiled step); eager=True
        materializes dense fp weights once at load. backend='fused'
        routes the quantized matmuls through the packed-native Pallas
        kernels (requires eager=False to have any effect — eager loads
        are dense and fall back to the reference path). scheduler/eos_id/
        kv_cache/kv_layout/page_size/n_pages/metrics/tracer/policy/
        faults/spec are forwarded to :class:`Engine`."""
        from repro.artifacts import load_artifact
        params, cfg, qm = load_artifact(path, eager=eager, verify=verify,
                                        backend=backend)
        return cls(params, cfg, qm, batch_size=batch_size, max_len=max_len,
                   scheduler=scheduler, eos_id=eos_id, kv_cache=kv_cache,
                   kv_layout=kv_layout, page_size=page_size,
                   n_pages=n_pages, metrics=metrics, tracer=tracer,
                   policy=policy, faults=faults, spec=spec)

    # ------------------------------------------------------------------
    # Streaming API
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> Request:
        """Enqueue a request. It starts executing on the next step().

        Assigns a ``request_id`` (for :meth:`cancel`) when the request
        has none, applies the engine policy's default deadlines to
        requests that don't carry their own, and moves the request into
        the QUEUED lifecycle state.

        **Admission control** (``policy.max_queue_depth`` /
        ``max_queue_depth_per_priority`` / ``admit_token_budget``): an
        over-limit request is *shed*, not silently requeued — it lands
        in the terminal SHED state (still counted toward submitted, so
        ``sum(terminal) == submitted`` holds) and :class:`ShedError` is
        raised with a ``retry_after_s`` that grows along the policy's
        backoff schedule for each *consecutive* shed (reset on the next
        successful admission, capped at ``backoff_s(6)``) — sustained
        overload pushes clients further out instead of inviting an
        immediate retry storm."""
        req.t_submit = time.time()             # absolute (logs)
        req.m_submit = time.perf_counter()     # durations
        if req.request_id is None:
            req.request_id = f"req-{self._next_id}"
            self._next_id += 1
        if req.deadline_ms is None:
            req.deadline_ms = self.policy.deadline_ms
        if req.ttft_deadline_ms is None:
            req.ttft_deadline_ms = self.policy.ttft_deadline_ms
        self._c_submitted.inc()
        reason = self.policy.shed_reason(self._queue, req)
        if reason is not None:
            self._shed_streak += 1
            retry_after = self.policy.backoff_s(min(self._shed_streak, 6))
            self._c_shed.inc()
            if self.tracer is not None:
                self.tracer.instant("shed", track="engine", cat="request",
                                    request=req.request_id, reason=reason)
            self._finish(req, req._gen, state=RequestState.SHED,
                         error=f"shed by admission control: {reason}")
            raise ShedError(req, reason, retry_after)
        self._shed_streak = 0
        req.state = RequestState.QUEUED
        self._by_id[req.request_id] = req
        if self.tracer is not None and req.trace_track is None:
            # Index comes from the tracer, not the engine, so request
            # tracks stay unique when several engines share one tracer.
            req.trace_track = f"req-{self.tracer.next_index('req')}"
        self._queue.push(req)
        self._g_queue_depth.set(len(self._queue))
        return req

    def cancel(self, request_id: str) -> bool:
        """Client-side cancellation: stop ``request_id`` wherever it is.

        Queued requests are dropped (the queue skips non-QUEUED entries
        lazily); a running request's lane is freed and its pages
        deref'd mid-flight. The request lands in the terminal CANCELLED
        state with any tokens emitted so far in ``out``. Returns False
        when the id is unknown or the request already reached a
        terminal state (cancellation is idempotent, not an error)."""
        req = self._by_id.get(request_id)
        if req is None or req.state in TERMINAL_STATES:
            return False
        for i, sl in enumerate(self._slots):
            if sl is not None and sl.req is req:
                self._slots[i] = None
                if self.kv_layout == "paged":
                    self._release_paged(i)
                    self._sync_alloc_metrics()
                break
        if self.tracer is not None and req.trace_track is not None:
            self.tracer.instant("cancel", track=req.trace_track,
                                cat="request")
        self._finish(req, req._gen, state=RequestState.CANCELLED,
                     error="cancelled by client")
        self._g_queue_depth.set(len(self._queue))
        return True

    def fail_lane(self, lane: int, error: str):
        """Supervisor hook: terminal-FAIL the request on ``lane`` and
        free the lane + its pages. Used after a stuck/failed engine step
        to remove the poisoned request — re-running it would poison the
        restarted loop the same way. Returns the failed request, or
        None for an empty lane."""
        sl = self._slots[lane]
        if sl is None:
            return None
        req = sl.req
        self._slots[lane] = None
        if self.kv_layout == "paged":
            self._release_paged(lane)
            self._sync_alloc_metrics()
        if self.tracer is not None and req.trace_track is not None:
            self.tracer.instant("fail_lane", track=req.trace_track,
                                cat="request", lane=lane, reason=error)
        self._finish(req, req._gen, state=RequestState.FAILED, error=error)
        return req

    def requeue_lane(self, lane: int, reason: str):
        """Supervisor hook: return ``lane``'s request to the queue
        *without* charging its preemption retry budget — bystander lanes
        of a failed step did nothing wrong. The lane and its pages are
        freed; tokens emitted so far stay in ``_gen``, so re-admission
        re-prefills prompt+gen and resumes bit-identically under greedy
        decoding (the recompute-resume path preemption uses). Returns
        the requeued request, or None for an empty lane."""
        sl = self._slots[lane]
        if sl is None:
            return None
        req = sl.req
        self._slots[lane] = None
        if self.kv_layout == "paged":
            self._release_paged(lane)
            self._sync_alloc_metrics()
        if self.tracer is not None and req.trace_track is not None:
            self.tracer.instant("requeue", track=req.trace_track,
                                cat="request", lane=lane, reason=reason)
        req.state = RequestState.QUEUED
        self._queue.push_front(req)
        self._g_queue_depth.set(len(self._queue))
        return req

    def step(self) -> List[Request]:
        """Run one scheduler step; return the requests it completed.

        Continuous: admit queued requests into free slots (chunked
        prefill), then one batched decode step over all live slots.
        Wave: serve one full wave of up to B queued requests.

        Both schedulers first honor the ``slow_step`` fault point and
        expire queued requests whose deadlines already passed."""
        if self._faults is not None:
            hit = self._faults.fire("slow_step")
            if hit is not None:
                time.sleep(float(hit.get("delay_s", 0.01)))
        if self.scheduler == "continuous":
            return self._step_continuous()
        done: List[Request] = []
        self._expire_queued(done)
        reqs = []
        now = time.perf_counter()
        while len(reqs) < self.B:
            req = self._queue.pop(now)
            if req is None:
                break
            err = self._never_fits(req)
            if err is not None:
                self._reject_never_fit(req, err, done)
                continue
            reqs.append(req)
        self._g_queue_depth.set(len(self._queue))
        return (self._wave(reqs) if reqs else []) + done

    @property
    def busy(self) -> bool:
        """True while any request is queued or occupies a slot (i.e.
        :meth:`step` still has work — the load generator's poll)."""
        return bool(len(self._queue)) or any(
            s is not None for s in self._slots)

    def drain(self) -> List[Request]:
        """Step until the queue and every slot are empty; return all
        requests completed while draining (completion order)."""
        done: List[Request] = []
        while self.busy:
            done.extend(self.step())
        return done

    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a list of requests; returns the same list (original
        order) with ``out``/latency fields filled."""
        for r in requests:
            self.submit(r)
        self.drain()
        return requests

    # ------------------------------------------------------------------
    # Shared helpers
    # ------------------------------------------------------------------

    def _bucket_len(self, s: int, max_new: int) -> int:
        """Round a prompt length up to the attention chunk so the jitted
        prefill compiles once per bucket, not once per distinct prompt
        length. Buckets only when the decode budget still fits in the
        cache (otherwise the raw length is kept — old behavior).

        Bucketed prompts are left-padded further than strictly needed;
        pads share the engine's existing ragged-wave semantics (left-pad
        tokens are attended — static batching, no per-row masks). Disable
        with ``Engine(..., bucket_prompts=False)``."""
        if not self.bucket_prompts:
            return s
        chunk = self.cfg.attn_chunk
        sb = (s + chunk - 1) // chunk * chunk
        while sb > s and sb + max_new > self.max_len:
            sb -= chunk
        return max(sb, s)

    def _trim_eos(self, toks: np.ndarray) -> np.ndarray:
        if self.eos_id is None:
            return toks
        hits = np.flatnonzero(toks == self.eos_id)
        return toks[:hits[0] + 1] if hits.size else toks

    def _finish(self, req: Request, toks,
                state: RequestState = RequestState.FINISHED,
                error: Optional[str] = None) -> None:
        """Move ``req`` into terminal ``state`` with output ``toks``
        (possibly partial for the failure states). Every terminal
        transition funnels through here: it owns the terminal-state
        counter, the latency histograms, and the lifecycle span, so the
        counters sum to submitted requests at quiescence."""
        req.out = np.asarray(toks, np.int32)
        req.state = state
        if error is not None:
            req.error = error
        req.t_done = time.time()
        req.m_done = time.perf_counter()
        if not req.m_first and state is RequestState.FINISHED:
            req.m_first = req.m_done         # wave / empty-budget path:
            req.t_first = req.t_done         # tokens delivered at once
        self._c_terminal[state].inc()
        self._c_useful.inc(max(len(req.out) - 1, 0))
        if req.m_submit and state is not RequestState.SHED:
            # shed requests never ran — a ~0 latency sample would fake
            # great percentiles exactly when the server is overloaded
            self._h_latency.observe(req.m_done - req.m_submit)
            if req.m_first:
                # no first token (expired in queue, failed prefill):
                # nothing to observe — a zero would fake a great TTFT
                self._h_ttft.observe(req.m_first - req.m_submit)
        if len(req.out) > 1 and req.m_done > req.m_first > 0:
            self._h_tpot.observe((req.m_done - req.m_first)
                                 / (len(req.out) - 1))
        if self.tracer is not None and req.trace_track is not None:
            if req.m_first and req.m_done > req.m_first:
                self.tracer.complete("decode", req.m_first, req.m_done,
                                     track=req.trace_track, cat="request")
            self.tracer.complete("request", req.m_submit or req.m_done,
                                 req.m_done, track=req.trace_track,
                                 cat="request", tokens=len(req.out),
                                 prompt=len(req.prompt),
                                 state=state.value,
                                 **({"error": req.error}
                                    if req.error else {}))
        self._by_id.pop(req.request_id, None)

    # ------------------------------------------------------------------
    # Lifecycle policy: deadlines, never-fit rejection, preemption
    # ------------------------------------------------------------------

    def _never_fits(self, req: Request) -> Optional[str]:
        """Reason this request can NEVER be served (even by evicting
        every cached page), or None. Checked once at admission pop —
        requeueing such a request would block the head of the queue
        forever (the pre-lifecycle engine's failure mode)."""
        s = len(req.prompt)
        if self.scheduler == "continuous" and self.kv_layout == "paged":
            if s + req.max_new > self.max_len:
                return (f"prompt {s} + max_new {req.max_new} > max_len "
                        f"{self.max_len}")
            pages = -(-(s + req.max_new) // self.page_size)
            if pages > self._alloc.capacity:
                return (f"needs {pages} pages but the pool holds only "
                        f"{self._alloc.capacity} even after evicting "
                        f"every cached page")
            return None
        sb = self._bucket_len(s, req.max_new)
        if sb + req.max_new > self.max_len:
            return (f"prompt {s} (bucketed {sb}) + max_new {req.max_new}"
                    f" > max_len {self.max_len}")
        return None

    def _reject_never_fit(self, req: Request, err: str,
                          done: List[Request]) -> None:
        self._c_never_fit.inc()
        self._finish(req, req._gen, state=RequestState.FAILED,
                     error=f"request can never fit the KV pool: {err} — "
                           f"raise max_len/n_pages or lower max_new")
        done.append(req)

    def _deadline_reason(self, req: Request, now: float,
                         where: str) -> Optional[str]:
        """Which deadline (if any) ``req`` has blown at ``now``."""
        if not req.m_submit:
            return None
        waited_ms = (now - req.m_submit) * 1e3
        if req.deadline_ms is not None and waited_ms >= req.deadline_ms:
            return (f"end-to-end deadline {req.deadline_ms:g}ms exceeded "
                    f"{where} ({waited_ms:.0f}ms elapsed)")
        if (req.ttft_deadline_ms is not None and not req.m_first
                and waited_ms >= req.ttft_deadline_ms):
            return (f"TTFT deadline {req.ttft_deadline_ms:g}ms exceeded "
                    f"{where} ({waited_ms:.0f}ms elapsed)")
        return None

    def _timeout(self, req: Request, reason: str,
                 done: List[Request]) -> None:
        if self.tracer is not None and req.trace_track is not None:
            self.tracer.instant("timeout", track=req.trace_track,
                                cat="request", reason=reason)
        self._finish(req, req._gen, state=RequestState.TIMED_OUT,
                     error=reason)
        done.append(req)

    def _expire_queued(self, done: List[Request]) -> None:
        """Time out queued requests whose TTFT / end-to-end deadline
        already passed — they would waste prefill work and then time out
        anyway. The queue drops the now-terminal entries lazily."""
        now = time.perf_counter()
        for req in list(self._queue):
            reason = self._deadline_reason(req, now, "while queued")
            if reason is not None:
                self._timeout(req, reason, done)

    def _expire_running(self, done: List[Request], paged: bool) -> None:
        """Time out running requests (end-to-end deadline only — a
        running request has its first token by definition). Called
        between decode bursts; ``policy.deadline_burst_cap`` bounds how
        stale this check can get."""
        now = time.perf_counter()
        for i in range(self.B):
            sl = self._slots[i]
            if sl is None:
                continue
            reason = self._deadline_reason(sl.req, now, "while decoding")
            if reason is not None:
                self._slots[i] = None
                if paged:
                    self._release_paged(i)
                self._timeout(sl.req, reason, done)

    def _preempt(self, lane: int, done: List[Request],
                 reason: str) -> None:
        """Evict lane ``lane``: free the lane + deref its pages, then
        requeue the request with backoff (tokens emitted so far are kept
        in ``_gen``; re-admission re-prefills prompt+gen, cheap under
        the prefix cache, and continues bit-identically). A request out
        of retry budget lands in the terminal PREEMPTED state."""
        sl = self._slots[lane]
        req = sl.req
        self._slots[lane] = None
        if self.kv_layout == "paged":
            self._release_paged(lane)
        self._c_preempt.inc()
        req.preemptions += 1
        req.retries += 1
        if self.tracer is not None and req.trace_track is not None:
            self.tracer.instant("preempt", track=req.trace_track,
                                cat="request", lane=lane, reason=reason,
                                retry=req.retries)
        if req.retries > self.policy.max_retries:
            self._finish(
                req, req._gen, state=RequestState.PREEMPTED,
                error=f"preempted {req.preemptions}x ({reason}); retry "
                      f"budget {self.policy.max_retries} exhausted")
            done.append(req)
            return
        req.state = RequestState.QUEUED
        req.not_before = (time.perf_counter()
                          + self.policy.backoff_s(req.retries))
        self._queue.push_front(req)

    def _victim_lanes(self):
        return ((i, s.req) for i, s in enumerate(self._slots)
                if s is not None)

    def _maybe_preempt_priority(self, done: List[Request]) -> None:
        """Priority-inversion trigger: every lane is busy and a
        strictly higher-priority request waits — evict the worst lane
        (at most one per step; admission picks up the freed lane this
        same step)."""
        if not self.policy.preemption:
            return
        if any(s is None for s in self._slots):
            return
        head = self._queue.peek(time.perf_counter())
        if head is None:
            return
        lane = pick_victim(self._victim_lanes(),
                           max_priority=head.priority)
        if lane is not None:
            self._preempt(lane, done, "priority")

    @staticmethod
    def _effective_prompt(req: Request) -> np.ndarray:
        """What (re-)admission prefills: the prompt plus every token
        already emitted before a preemption. Greedy sampling makes the
        resumed continuation bit-identical to the uninterrupted run."""
        prompt = np.asarray(req.prompt, np.int32)
        if not req._gen:
            return prompt
        return np.concatenate(
            [prompt, np.asarray(req._gen, np.int32)])

    def _cache_dtype(self):
        emb = self.params.get("embed") if isinstance(self.params, dict) \
            else None
        return emb.dtype if emb is not None else jnp.float32

    def _count_decode_compile(self, b: int, kind: str) -> None:
        self._count_compile("decode", (b, kind))

    # ------------------------------------------------------------------
    # Wave scheduler (static batching)
    # ------------------------------------------------------------------

    def _wave(self, reqs: List[Request]) -> List[Request]:
        t0 = time.time()
        B = len(reqs)
        max_new = max(r.max_new for r in reqs)
        S = self._bucket_len(max(len(r.prompt) for r in reqs), max_new)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad

        # greedy waves keep the untouched argmax + greedy-decode path
        # (bit-identical, same compile keys); a wave with any sampled
        # request switches the whole wave to the sampled closures —
        # greedy members still argmax inside them (per-lane temp 0)
        sampled = any(r.sampling is not None and not r.sampling.greedy
                      for r in reqs)
        self._count_compile("prefill", (B, S))
        self._count_decode_compile(
            B, "scalar-sampled" if sampled else "scalar")
        if sampled:
            # wave requests never resume, so every lane's first emission
            # index is 0; the loop then advances all lanes in lockstep
            temps_d, tks_d, tps_d, seeds_d, steps_d = self._samp_vectors(
                list(reqs), [0] * B)
        for r in reqs:
            r.state = RequestState.RUNNING
        with self._span("wave", batch=B, prompt_len=S, max_new=max_new):
            with self._span("prefill", batch=B, prompt_len=S):
                last_logits, cache = self._prefill(self.params,
                                                   jnp.asarray(toks))
                if sampled:
                    nxt = self._sample_tokens(last_logits, temps_d,
                                              tks_d, tps_d, seeds_d,
                                              steps_d)
                    steps_d = steps_d + 1
                else:
                    nxt = jnp.argmax(last_logits, axis=-1) \
                             .astype(jnp.int32)
                ok = jnp.isfinite(last_logits).all(axis=-1)
            # accumulate sampled tokens on device; one host transfer at
            # the end (a per-step np.asarray would sync the dispatch
            # pipeline every decode step)
            toks_dev = [nxt]
            oks_dev = [ok]
            pos = S
            with self._span("decode_loop", steps=max(max_new - 1, 0)):
                for _ in range(max_new - 1):
                    poison = -1
                    if self._faults is not None:
                        hit = self._faults.fire("nan_logits")
                        if hit is not None:
                            poison = int(hit.get("lane", 0))
                    if sampled:
                        nxt, ok, cache = self._decode_sampled(
                            self.params, cache, nxt, jnp.int32(pos),
                            jnp.int32(poison), temps_d, tks_d, tps_d,
                            seeds_d, steps_d)
                        steps_d = steps_d + 1
                    else:
                        nxt, ok, cache = self._decode(
                            self.params, cache, nxt, jnp.int32(pos),
                            jnp.int32(poison))
                    toks_dev.append(nxt)
                    oks_dev.append(ok)
                    pos += 1
            with self._span("host_sync", tokens=B * max_new):
                host = np.asarray(jnp.stack(toks_dev, axis=1))
                okh = np.asarray(jnp.stack(oks_dev, axis=1))
        t1 = time.time()
        self._c_admitted.inc(B)
        self._c_decode_steps.inc(max(max_new - 1, 0))  # max_new=0: none
        self._c_slot_steps.inc(B * max(max_new - 1, 0))
        for i, r in enumerate(reqs):
            bad = np.flatnonzero(~okh[i, :r.max_new])
            if bad.size:
                # the guard fails only this lane: its output stops just
                # before the first poisoned step, neighbors are untouched
                out = self._trim_eos(host[i, :bad[0]].astype(np.int32))
                self._c_nan.inc()
                if self.tracer is not None and r.trace_track is not None:
                    self.tracer.instant("nan_guard", track=r.trace_track,
                                        cat="request", lane=i,
                                        step=int(bad[0]))
                self._finish(r, out, state=RequestState.FAILED,
                             error=f"non-finite logits in lane {i} at "
                                   f"wave step {int(bad[0])}")
            else:
                out = self._trim_eos(host[i, :r.max_new].astype(np.int32))
                self._finish(r, out)
            r.t_submit, r.t_done = t0, t1
            if r.on_token is not None:
                for t in out:
                    r.on_token(int(t))
        return reqs

    # ------------------------------------------------------------------
    # Continuous scheduler (slot pool + chunked prefill)
    # ------------------------------------------------------------------

    def _ensure_pool(self) -> None:
        if self._cache is not None:
            return
        dt = self._cache_dtype()
        if self.kv_layout == "paged":
            self._cache = self._commit(
                api.init_cache_paged(self.cfg, self._alloc.n_pages,
                                     self.page_size, dt,
                                     kv_quant=self.kv_quant))
            return
        self._cache = self._commit(
            api.init_cache(self.cfg, self.B, self.max_len, dt,
                           kv_quant=self.kv_quant))
        self._slot_cache = self._commit(
            api.init_cache(self.cfg, 1, self.max_len, dt,
                           kv_quant=self.kv_quant))

    def _admit(self, slot: int, req: Request) -> tuple:
        """Chunk-prefill ``req`` into lane ``slot`` of the persistent
        cache. Returns (bucketed prompt length, first sampled token).

        The prompt is left-padded to its chunk bucket (same semantics as
        the wave engine) and processed in fixed attn_chunk-wide pieces —
        the final piece right-pads to the chunk width and passes the index
        of the last real token, so every prompt length reuses the single
        compiled chunk step. Pad writes land at cache positions beyond
        the prompt where they stay masked until decode overwrites them.

        A preempted request re-admits with its emitted tokens appended
        to the prompt (``_effective_prompt``) and the remaining budget;
        greedy decode then continues bit-identically."""
        prompt = self._effective_prompt(req)
        s = len(prompt)
        max_new = req.max_new - len(req._gen)
        C = self.cfg.attn_chunk
        sb = self._bucket_len(s, max_new)
        if sb + max_new > self.max_len:
            raise ValueError(
                f"request does not fit the KV pool: prompt {s} (bucketed "
                f"{sb}) + max_new {max_new} > max_len {self.max_len}")
        n_chunks = -(-sb // C)
        buf = np.zeros(n_chunks * C, np.int32)
        buf[sb - s:sb] = prompt
        self._count_compile("prefill_chunk", (1, C))
        logits = None
        for ci in range(n_chunks):
            width = min(sb - ci * C, C)
            with self._span("prefill_chunk", chunk=ci, slot=slot):
                logits, self._slot_cache = self._prefill_chunk(
                    self.params, self._slot_cache,
                    jnp.asarray(buf[None, ci * C:(ci + 1) * C]),
                    jnp.int32(ci * C), jnp.int32(width - 1))
            self._c_chunk_steps.inc()
            self._c_prefill_lane_steps.inc()
            self._h_prefill_batch.observe(1)
        with self._span("merge", slot=slot):
            self._cache = self._merge(self._cache, self._slot_cache,
                                      jnp.int32(slot))
        with self._span("logits_fetch", slot=slot):
            row = np.asarray(logits)[0]
        tok = self._first_token(req, row)
        return sb, tok, bool(np.isfinite(row).all())

    def _first_token(self, req: Request, row: np.ndarray) -> int:
        """Sample the admission token from a (V,) prefill-logits row.

        Greedy requests keep the host argmax (bit-identical to the
        pre-sampling engine). Sampled requests draw through the same
        jitted per-lane sampler the decode burst uses, on a (1, V)
        batch: the draw depends only on (seed, emission index), so the
        admission token equals what a decode-batch draw at the same
        index would produce — including after a preemption-resume,
        where the emission index restarts at ``len(req._gen)``."""
        sp = req.sampling
        with self._span("sample_first"):
            if sp is None or sp.greedy:
                return int(row.argmax())
            return int(np.asarray(self._sample_tokens(
                jnp.asarray(row[None]),
                jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.top_p], jnp.float32),
                jnp.asarray([sp.seed], jnp.uint32),
                jnp.asarray([len(req._gen)], jnp.int32)))[0])

    def _samp_vectors(self, reqs: List[Optional[Request]],
                      steps: List[int]) -> tuple:
        """Build the per-lane sampling argument vectors for a batch.

        ``reqs[i]`` may be None (idle lane — greedy no-op args);
        ``steps[i]`` is the lane's next emission index.  All-greedy
        batches reuse one cached constant tuple: greedy lanes take the
        argmax branch, so none of these vectors (steps included) affect
        the output, and committing five fresh arrays per spec step is
        pure host overhead."""
        n = len(reqs)
        if all(r is None or r.sampling is None or r.sampling.greedy
               for r in reqs):
            cached = self._greedy_vecs.get(n)
            if cached is None:
                z = self._commit(jnp.zeros(n, jnp.float32))
                cached = self._greedy_vecs[n] = (
                    z, self._commit(jnp.zeros(n, jnp.int32)),
                    self._commit(jnp.ones(n, jnp.float32)),
                    self._commit(jnp.zeros(n, jnp.uint32)),
                    self._commit(jnp.zeros(n, jnp.int32)))
            return cached
        temps = np.zeros(n, np.float32)
        tks = np.zeros(n, np.int32)
        tps = np.ones(n, np.float32)
        seeds = np.zeros(n, np.uint32)
        for i, r in enumerate(reqs):
            sp = r.sampling if r is not None and r.sampling is not None \
                else GREEDY
            temps[i] = sp.temperature
            tks[i] = sp.top_k
            tps[i] = sp.top_p
            seeds[i] = sp.seed
        return (self._commit(jnp.asarray(temps)),
                self._commit(jnp.asarray(tks)),
                self._commit(jnp.asarray(tps)),
                self._commit(jnp.asarray(seeds)),
                self._commit(jnp.asarray(np.asarray(steps, np.int32))))

    def _emit(self, req: Request, tok: int) -> None:
        if req.on_token is not None:
            req.on_token(tok)

    # ------------------------------------------------------------------
    # Paged admission: block tables + ref-counted prefix caching
    # ------------------------------------------------------------------

    def _page_hashes(self, prompt: np.ndarray) -> List[bytes]:
        """Chained content hashes of the prompt's *full* pages: hash j
        commits to tokens [0, (j+1)*P) — page content alone is not
        enough, because a page's KV depends on everything before it."""
        P = self.page_size
        hs: List[bytes] = []
        h = hashlib.sha256(b"mx-paged-kv")
        for j in range(len(prompt) // P):
            h = hashlib.sha256(
                h.digest()
                + np.ascontiguousarray(prompt[j * P:(j + 1) * P],
                                       np.int32).tobytes())
            hs.append(h.digest())
        return hs

    def _tables_committed(self):
        if self._tables_dev is None:
            self._tables_dev = self._commit(jnp.asarray(self._tables))
        return self._tables_dev

    def _release_paged(self, slot: int) -> None:
        """Drop lane ``slot``'s page references and park its block table
        on the scrap page (dead-lane decode writes must not touch live
        pages). Registered pages whose refcount hits zero stay cached
        for future prefix hits until LRU eviction reclaims them."""
        pages = self._slot_pages[slot]
        if pages is not None:
            for p in pages:
                self._alloc.decref(p)
            self._slot_pages[slot] = None
        self._tables[slot, :] = 0
        self._tables_dev = None

    def _admit_paged(self, slot: int, req: Request) -> Optional[tuple]:
        """Admit ``req`` into lane ``slot`` of the paged pool. Returns
        (prompt length, first sampled token), or ``None`` when the pool
        cannot supply the pages right now (backpressure — the caller
        requeues the request and stops admitting this step).

        Prefix caching: the prompt's full pages are chain-hashed and
        matched against the allocator's registry. Matched pages are
        reused *by reference* (refcount bump, zero prefill work);
        chunked prefill resumes at the first unmatched chunk. At least
        the chunk holding the last prompt token always re-runs — the
        admission needs its logits to sample the first output token —
        and when that rewrite would land inside a shared page, the page
        is copied into a private one first (copy-on-write), preserving
        the cached bytes for other requests. After prefill, this
        prompt's own full pages are registered for future sharing.

        Prompts are placed unpadded at position 0 (no bucketing): page
        content is position-dependent (RoPE), so identical placement is
        what makes equal prefixes shareable.

        A preempted request re-admits with prompt+emitted tokens
        (``_effective_prompt``): its original prompt's registered pages
        are prefix-cache hits, so the retry re-prefills only the tail."""
        with self._span("admit_prep", slot=slot):
            plan = self._admit_paged_prep(slot, req)
        if plan is None:
            return None
        return self._prefill_plan_serial(plan)

    def _admit_paged_prep(self, slot: int, req: Request,
                          in_flight: bool = False) -> Optional[dict]:
        """Host-side half of a paged admission: page accounting, prefix
        matching, copy-on-write, and the lane's block-table write —
        everything up to (not including) the prefill chunk loop.
        Returns a *plan* dict consumed by :meth:`_prefill_plan_serial`
        or the batched admission loop, or ``None`` on backpressure
        (every page reference taken here has been released).

        ``in_flight`` marks that other admissions hold pages but do not
        occupy a slot yet (earlier plans of the same batched-admission
        step) — it suppresses the exhausted-with-idle-pool error, which
        would otherwise misread their reservations as a permanently
        unsatisfiable request."""
        prompt = self._effective_prompt(req)
        s = len(prompt)
        max_new = req.max_new - len(req._gen)
        C = self.cfg.attn_chunk
        P = self.page_size
        if s + max_new > self.max_len:
            raise ValueError(
                f"request does not fit the KV pool: prompt {s} + "
                f"max_new {max_new} > max_len {self.max_len}")
        n_req_pages = -(-(s + max_new) // P)
        hashes = self._page_hashes(prompt)
        matched: List[int] = []
        for h in hashes:
            p = self._alloc.lookup(h)
            if p is None:
                break
            matched.append(p)
        # resume point: whole matched pages, capped so the chunk holding
        # the last prompt token is always re-run (its logits seed decode)
        resume = max(0, min(len(matched) * P, (s - 1) // C * C))
        m_full = resume // P
        cow_src = matched[m_full] if resume % P else None
        for p in matched[:m_full]:
            self._alloc.incref(p)
        if cow_src is not None:
            self._alloc.incref(cow_src)     # pin across alloc + copy
        forced = (self._faults is not None and
                  self._faults.fire("alloc_exhausted",
                                    need=n_req_pages - m_full) is not None)
        fresh = (None if forced
                 else self._alloc.alloc(n_req_pages - m_full))
        if fresh is None:
            for p in matched[:m_full]:
                self._alloc.decref(p)
            if cow_src is not None:
                self._alloc.decref(cow_src)
            if (not forced and not in_flight
                    and not any(sl is not None for sl in self._slots)):
                raise ValueError(
                    f"KV page pool exhausted with no requests in "
                    f"flight: request needs {n_req_pages - m_full} "
                    f"fresh pages but only {self._alloc.available} of "
                    f"{self._alloc.capacity} are obtainable — raise "
                    f"n_pages or lower max_new")
            return None
        pages = matched[:m_full] + fresh
        if cow_src is not None:
            with self._span("copy_page", src=cow_src, dst=fresh[0]):
                self._cache = self._copy_page(self._cache,
                                              jnp.int32(cow_src),
                                              jnp.int32(fresh[0]))
            self._alloc.decref(cow_src)
        self._c_prefix_hit_toks.inc(resume)
        (self._c_prefix_hits if m_full else self._c_prefix_misses).inc()
        self._tables[slot, :] = 0
        self._tables[slot, :len(pages)] = pages
        self._tables_dev = None

        n_chunks = -(-(s - resume) // C)
        buf = np.zeros(n_chunks * C, np.int32)
        buf[:s - resume] = prompt[resume:]
        return {"slot": slot, "req": req, "s": s, "resume": resume,
                "n_chunks": n_chunks, "buf": buf, "pages": pages,
                "hashes": hashes}

    def _prefill_plan_serial(self, plan: dict) -> tuple:
        """Run one admission plan's chunked prefill serially (one lane
        per dispatch — the pre-batching jit signature) and finish it."""
        slot, s, resume = plan["slot"], plan["s"], plan["resume"]
        C = self.cfg.attn_chunk
        buf = plan["buf"]
        table_row = self._commit(jnp.asarray(self._tables[slot:slot + 1]))
        self._count_compile("prefill_chunk", ("paged", 1, C))
        logits = None
        for ci in range(plan["n_chunks"]):
            width = min(s - resume - ci * C, C)
            with self._span("prefill_chunk", chunk=ci, slot=slot,
                            paged=True, prefill_batch=1):
                logits, self._cache = self._prefill_chunk_paged(
                    self.params, self._cache,
                    jnp.asarray(buf[None, ci * C:(ci + 1) * C]), table_row,
                    jnp.int32(resume + ci * C), jnp.int32(width - 1))
            self._c_chunk_steps.inc()
            self._c_prefill_lane_steps.inc()
            self._h_prefill_batch.observe(1)
        with self._span("logits_fetch", slot=slot):
            row = np.asarray(logits)[0]
        return self._admit_paged_finish(plan, row)

    def _admit_paged_finish(self, plan: dict, row: np.ndarray) -> tuple:
        """Post-prefill bookkeeping of a paged admission: register the
        prompt's full pages for prefix sharing, pin the lane's page
        list, sample the first token from the last chunk's logits row."""
        slot, req, s = plan["slot"], plan["req"], plan["s"]
        for j in range(s // self.page_size):
            self._alloc.register(plan["hashes"][j], plan["pages"][j])
        self._slot_pages[slot] = plan["pages"]
        tok = self._first_token(req, row)
        return s, tok, bool(np.isfinite(row).all())

    def _admit_one(self, i: int, req: Request, paged: bool):
        """Admit ``req`` into lane ``i`` with lifecycle telemetry.
        Returns the (sb, tok, ok) admission result, or None on paged
        backpressure (nothing was recorded for the request). TTFT /
        queue-wait are observed only on the *first* admission — a
        preempted request's retry is not a new first token."""
        t_a0 = time.perf_counter()
        with self._span("admit", slot=i, prompt=len(req.prompt),
                        req=req.trace_track or ""):
            if paged:
                res = self._admit_paged(i, req)
                if res is None:
                    return None
            else:
                res = self._admit(i, req)
        self._record_admission(req, t_a0, time.perf_counter(), res[2])
        return res

    def _record_admission(self, req: Request, t_a0: float, t_a1: float,
                          ok: bool) -> None:
        """Admission lifecycle telemetry, shared by serial and batched
        admission: admitted counter, RUNNING transition, first-token /
        queue-wait observations, request-track trace events. ``t_a0``
        is when admission work started for this request, ``t_a1`` when
        its first token became available on the host."""
        self._c_admitted.inc()
        req.state = RequestState.RUNNING
        first = not req.m_first
        if first and ok:
            req.m_first = t_a1
            req.t_first = time.time()
            if req.m_submit:
                self._h_queue_wait.observe(t_a0 - req.m_submit)
        if self.tracer is not None and req.trace_track is not None:
            if req.m_submit and first:
                self.tracer.complete("queued", req.m_submit, t_a0,
                                     track=req.trace_track, cat="request")
            self.tracer.complete("prefill", t_a0, t_a1,
                                 track=req.trace_track, cat="request",
                                 prompt=len(req.prompt), resumed=not first)
            if first and ok:
                self.tracer.instant("first_token", track=req.trace_track,
                                    cat="request")

    def _post_admission(self, i: int, req: Request, res: tuple,
                        paged: bool, done: List[Request]) -> bool:
        """Shared admission epilogue: NaN guard, first-token emission,
        same-step completion, or lane occupancy. Returns True when the
        lane is now occupied (False: it stays free for the next
        queued request)."""
        sb, tok, ok = res
        if not ok:
            # prefill produced non-finite logits: fail this request
            # alone, the lane stays free for the next
            self._c_nan.inc()
            if (self.tracer is not None
                    and req.trace_track is not None):
                self.tracer.instant("nan_guard", track=req.trace_track,
                                    cat="request", lane=i, step=-1)
            if paged:
                self._release_paged(i)
            self._finish(req, req._gen, state=RequestState.FAILED,
                         error=f"non-finite logits at prefill "
                               f"(lane {i})")
            done.append(req)
            return False
        req._gen.append(tok)
        self._emit(req, tok)
        if req.max_new - len(req._gen) == 0 or tok == self.eos_id:
            self._finish(req, req._gen)  # lane freed same step
            done.append(req)
            if paged:
                self._release_paged(i)
            return False
        self._slots[i] = _Slot(req, req._gen, sb,
                               req.max_new - len(req._gen))
        return True

    def _admit_batched(self, done: List[Request], knob: int) -> None:
        """Paged admission with prefill batching: admit up to ``knob``
        queued requests per engine step through ONE chunked-prefill
        loop whose dispatches carry every candidate lane at once —
        per-lane block tables, start offsets, and last-token indices
        stacked on the batch axis under a single jit signature
        (``("paged", B, C)``). The loop runs ``max(n_chunks)`` steps;
        a lane whose prompt ran out simply goes inactive (its row
        rides along on the scrap table, see below).

        Semantics match serial admission exactly: candidates are
        collected in admit-cursor ring order with the same pop /
        never-fits / zero-budget / preempt-retry / backpressure
        handling, and the fused or fallback prefill is row-independent,
        so each lane's tokens are bit-identical to admitting it alone.
        The one cross-request interaction serial admission has — a
        later request prefix-hitting pages a *just-admitted* earlier
        request registered — cannot happen mid-batch, so a candidate
        whose prompt pages collide with hashes this batch is about to
        register is deferred (pushed back to the queue front, stopping
        collection to preserve queue order); it admits next step with
        its prefix hit intact.

        Non-candidate lanes (and candidates past their last chunk) run
        on an all-zeros table row: their writes land on the scrap page
        (page 0) and their logits rows are never read — rows are
        independent, so garbage lanes cannot perturb live ones."""
        C = self.cfg.attn_chunk
        P = self.page_size
        plans: List[dict] = []
        pending: set = set()     # page hashes this batch will register
        stop = False
        for off in range(self.B):
            if stop or len(plans) >= knob:
                break
            i = (self._admit_cursor + off) % self.B
            if self._slots[i] is not None:
                continue
            while True:
                req = self._queue.pop(time.perf_counter())
                if req is None:
                    stop = True
                    break
                err = self._never_fits(req)
                if err is not None:
                    self._reject_never_fit(req, err, done)
                    continue
                if req.max_new - len(req._gen) <= 0:
                    self._c_admitted.inc()
                    self._finish(req, req._gen)
                    done.append(req)
                    continue
                if plans and any(
                        h in pending
                        for h in self._page_hashes(
                            self._effective_prompt(req))):
                    # would prefix-hit a page an earlier candidate in
                    # this batch registers only *after* its prefill —
                    # defer so the hit is not silently skipped
                    self._queue.push_front(req)
                    stop = True
                    break
                t_a0 = time.perf_counter()
                with self._span("admit_prep", slot=i):
                    plan = self._admit_paged_prep(i, req,
                                                  in_flight=bool(plans))
                while plan is None and self.policy.preemption:
                    # page pressure: same victim/retry dance as serial
                    lane = pick_victim(self._victim_lanes(),
                                       max_priority=req.priority)
                    if lane is None:
                        break
                    self._preempt(lane, done, "page pressure")
                    with self._span("admit_prep", slot=i):
                        plan = self._admit_paged_prep(
                            i, req, in_flight=bool(plans))
                if plan is None:
                    self._queue.push_front(req)
                    stop = True
                    break
                plan["t0"] = t_a0
                pending.update(plan["hashes"][:plan["s"] // P])
                plans.append(plan)
                break
        if not plans:
            return
        if len(plans) == 1:
            # a batch of one IS the serial path — same jit signature,
            # same spans, same counters
            p = plans[0]
            req = p["req"]
            with self._span("admit", slot=p["slot"],
                            prompt=len(req.prompt),
                            req=req.trace_track or ""):
                res = self._prefill_plan_serial(p)
            self._record_admission(req, p["t0"], time.perf_counter(),
                                   res[2])
            self._post_admission(p["slot"], req, res, True, done)
            return

        B = self.B
        maxp = self._tables.shape[1]
        tables = np.zeros((B, maxp), np.int32)
        for p in plans:
            tables[p["slot"]] = self._tables[p["slot"]]
        # committed as a COPY: `tables` is mutated between steps while
        # earlier dispatches are still in flight, and jnp.asarray of a
        # host array can be zero-copy on CPU backends — aliasing it
        # would let the mutation reach computations already enqueued
        tables_d = self._commit(jnp.asarray(tables.copy()))
        n_steps = max(p["n_chunks"] for p in plans)
        self._count_compile("prefill_chunk", ("paged", B, C))
        lane_logits: dict = {}   # slot -> device logits, its last chunk
        with self._span("admit", lanes=len(plans), batched=True):
            for ci in range(n_steps):
                active = [p for p in plans if ci < p["n_chunks"]]
                if ci and any(p["n_chunks"] == ci for p in plans):
                    # a lane just ran out of chunks: park it on the
                    # scrap table BEFORE the next dispatch, or its
                    # ride-along garbage rows would overwrite the real
                    # KV it just finished writing
                    for p in plans:
                        if p["n_chunks"] <= ci:
                            tables[p["slot"]] = 0
                    tables_d = self._commit(jnp.asarray(tables.copy()))
                toks = np.zeros((B, C), np.int32)
                starts = np.zeros(B, np.int32)
                last = np.zeros(B, np.int32)
                for p in active:
                    toks[p["slot"]] = p["buf"][ci * C:(ci + 1) * C]
                    starts[p["slot"]] = p["resume"] + ci * C
                    last[p["slot"]] = min(
                        p["s"] - p["resume"] - ci * C, C) - 1
                with self._span("prefill_chunk", chunk=ci, paged=True,
                                prefill_batch=len(active)):
                    logits, self._cache = self._prefill_chunk_paged(
                        self.params, self._cache, jnp.asarray(toks),
                        tables_d, jnp.asarray(starts),
                        jnp.asarray(last))
                self._c_chunk_steps.inc()
                self._c_prefill_lane_steps.inc(len(active))
                if len(active) > 1:
                    self._c_prefill_batched.inc()
                self._h_prefill_batch.observe(len(active))
                for p in active:
                    if ci == p["n_chunks"] - 1:
                        lane_logits[p["slot"]] = logits
            t_a1 = time.perf_counter()
            for p in plans:
                # one host fetch per lane, after every dispatch is in
                # flight — the sync cost is paid once per admission,
                # exactly like the serial path's trailing fetch
                with self._span("logits_fetch", slot=p["slot"]):
                    row = np.asarray(lane_logits[p["slot"]])[p["slot"]]
                res = self._admit_paged_finish(p, row)
                self._record_admission(p["req"], p["t0"], t_a1, res[2])
                self._post_admission(p["slot"], p["req"], res, True,
                                     done)

    def _step_continuous(self) -> List[Request]:
        self._ensure_pool()
        paged = self.kv_layout == "paged"
        done: List[Request] = []
        with self._span("engine_step"):
            done = self._step_continuous_inner(paged, done)
        if paged:
            self._sync_alloc_metrics()
        self._g_queue_depth.set(len(self._queue))
        if not done and not any(s is not None for s in self._slots):
            # nothing ran and nothing finished: every queued request is
            # in a backoff hold — sleep toward the nearest release so
            # drain() doesn't spin the host
            d = self._queue.next_eligible_delay(time.perf_counter())
            if d:
                time.sleep(min(d, 0.02))
        return done

    def _step_continuous_inner(self, paged: bool,
                               done: List[Request]) -> List[Request]:
        # --- lifecycle pre-pass: forced eviction fault, queued-deadline
        # expiry, then the priority-inversion preemption trigger ---
        if (self._faults is not None and paged
                and self._faults.fire("evict_cache") is not None):
            n = self._alloc.flush_cache()
            if self.tracer is not None:
                self.tracer.instant("fault:evict_cache", cat="fault",
                                    evicted=n)
        self._expire_queued(done)
        self._maybe_preempt_priority(done)

        # --- admission: fill free lanes from the queue (ring order).
        # Paged admission batches up to max_prefill_lanes_per_step
        # requests into one chunked-prefill loop; knob 1 (and the
        # contiguous layout, whose admission runs in a single-lane
        # scratch cache) keeps the serial path bit-identical to the
        # pre-batching engine. ---
        t_admit = time.perf_counter()
        knob = (max(1, self.policy.max_prefill_lanes_per_step)
                if paged else 1)
        if knob > 1:
            self._admit_batched(done, knob)
        else:
            blocked = False
            for off in range(self.B):
                i = (self._admit_cursor + off) % self.B
                if self._slots[i] is not None:
                    continue
                while True:
                    req = self._queue.pop(time.perf_counter())
                    if req is None:
                        break
                    err = self._never_fits(req)
                    if err is not None:
                        self._reject_never_fit(req, err, done)
                        continue
                    if req.max_new - len(req._gen) <= 0:
                        self._c_admitted.inc()
                        self._finish(req, req._gen)
                        done.append(req)
                        continue
                    res = self._admit_one(i, req, paged)
                    while res is None and self.policy.preemption:
                        # page pressure: evict a strictly lower-priority
                        # running request and retry this admission — its
                        # freed pages (plus cache evictions) cover us
                        lane = pick_victim(self._victim_lanes(),
                                           max_priority=req.priority)
                        if lane is None:
                            break
                        self._preempt(lane, done, "page pressure")
                        res = self._admit_one(i, req, paged)
                    if res is None:
                        # pool pressure with nothing evictable: requeue
                        # at the front and stop admitting — pages free
                        # up as lanes finish
                        self._queue.push_front(req)
                        blocked = True
                        break
                    if self._post_admission(i, req, res, paged, done):
                        break
                if blocked:
                    break
        self._admit_cursor = (self._admit_cursor + 1) % self.B
        self._c_admit_s.inc(time.perf_counter() - t_admit)

        live = [i for i in range(self.B) if self._slots[i] is not None]
        if not live:
            return done

        if self.spec is not None:
            # speculative decoding replaces the decode burst: one verify
            # step per engine step (drafts depend on the tokens the
            # previous step emitted, so steps are inherently host-paced
            # — each one can emit up to k+1 tokens per lane instead)
            self._spec_decode_step(live, paged, done)
            self._expire_running(done, paged)
            return done

        # --- decode burst over every lane (dead lanes idle; their
        # sampled tokens are discarded, their stale cache rows are
        # overwritten wholesale at the next admission merge).
        #
        # With no eos_id the slot schedule is deterministic on the host:
        # every lane runs exactly `remaining` more steps. All steps up to
        # the next lane completion are therefore dispatched back-to-back
        # with the sampled-token array fed straight back on device — the
        # device->host fetch (needed only for on_token emission and
        # bookkeeping) is batched ONCE per burst instead of syncing the
        # dispatch pipeline every step, which is what let the wave
        # scheduler out-run continuous on tok/s. With an eos_id any step
        # can free a lane, so the burst degenerates to one step (EOS must
        # be observed before the next input token is chosen... it is the
        # next input token, so the pipeline is inherently serialized).
        burst = 1 if self.eos_id is not None else min(
            self._slots[i].remaining for i in live)
        if any(self._slots[i].req.deadline_ms is not None for i in live):
            # deadlines are only observable between bursts; cap the
            # burst so enforcement granularity stays bounded (deadline-
            # free traffic keeps the full burst and its single sync)
            burst = min(burst, max(1, self.policy.deadline_burst_cap))
        with self._span("decode_prep", lanes=len(live)):
            cur = np.zeros(self.B, np.int32)
            pos = np.zeros(self.B, np.int32)
            for i in live:
                cur[i] = self._slots[i].toks[-1]
                pos[i] = self._slots[i].pos
            # the greedy closures are dispatched untouched whenever every
            # live lane is greedy, so greedy traffic (and its compile
            # counts) is bit-identical to an engine without sampling
            sampled = any(self._slots[i].req.sampling is not None
                          and not self._slots[i].req.sampling.greedy
                          for i in live)
            self._count_decode_compile(
                self.B, ("vector-paged" if paged else "vector") +
                        ("-sampled" if sampled else ""))
            # committed onto the canonical sharding so the burst's first
            # step shares one jit signature with the steady-state steps
            # (whose cur/pos are the previous step's committed outputs)
            cur_d = self._commit(jnp.asarray(cur))
            pos_d = self._commit(jnp.asarray(pos))
            tables_d = self._tables_committed() if paged else None
            if sampled:
                # steps[i] = the lane's next emission index: sl.toks
                # already includes the admission token (emission 0), so
                # index = len(toks). Idle lanes get greedy no-op args.
                temps_d, tks_d, tps_d, seeds_d, steps_d = \
                    self._samp_vectors(
                        [self._slots[i].req if self._slots[i] is not None
                         else None for i in range(self.B)],
                        [len(self._slots[i].toks)
                         if self._slots[i] is not None else 0
                         for i in range(self.B)])
        toks_dev = []
        oks_dev = []
        with self._span("decode_burst", steps=burst, lanes=len(live)):
            for _ in range(burst):
                poison = -1
                if self._faults is not None:
                    hit = self._faults.fire("nan_logits")
                    if hit is not None:
                        poison = int(hit.get("lane", live[0]))
                # spans time the *dispatch* (device work is async; the
                # device wait shows up in host_sync below) — no per-step
                # host sync is ever introduced by tracing
                with self._span("decode_step", paged=paged):
                    if paged and sampled:
                        cur_d, ok_d, self._cache = \
                            self._decode_paged_sampled(
                                self.params, self._cache, cur_d, pos_d,
                                tables_d, jnp.int32(poison), temps_d,
                                tks_d, tps_d, seeds_d, steps_d)
                    elif paged:
                        cur_d, ok_d, self._cache = self._decode_paged(
                            self.params, self._cache, cur_d, pos_d,
                            tables_d, jnp.int32(poison))
                    elif sampled:
                        cur_d, ok_d, self._cache = self._decode_sampled(
                            self.params, self._cache, cur_d, pos_d,
                            jnp.int32(poison), temps_d, tks_d, tps_d,
                            seeds_d, steps_d)
                    else:
                        cur_d, ok_d, self._cache = self._decode(
                            self.params, self._cache, cur_d, pos_d,
                            jnp.int32(poison))
                toks_dev.append(cur_d)
                oks_dev.append(ok_d)
                pos_d = pos_d + 1
                if sampled:
                    steps_d = steps_d + 1
                self._c_decode_steps.inc()
                self._c_slot_steps.inc(self.B)
            with self._span("host_sync", steps=burst):
                host = np.asarray(jnp.stack(toks_dev, axis=1))  # 1 sync
                okh = np.asarray(jnp.stack(oks_dev, axis=1))
        with self._span("emit", steps=burst, lanes=len(live)):
            for step in range(burst):
                for i in live:
                    sl = self._slots[i]
                    if sl is None:
                        continue
                    if not okh[i, step]:
                        # per-lane failure isolation: only the poisoned
                        # lane's request fails; its private cache rows are
                        # garbage now but nothing shared was written (decode
                        # writes land past the registered prefix pages) and
                        # the lane's next admission overwrites them
                        req = sl.req
                        self._c_nan.inc()
                        if (self.tracer is not None
                                and req.trace_track is not None):
                            self.tracer.instant("nan_guard",
                                                track=req.trace_track,
                                                cat="request", lane=i,
                                                step=step)
                        self._slots[i] = None
                        if paged:
                            self._release_paged(i)
                        self._finish(req, sl.toks,
                                     state=RequestState.FAILED,
                                     error=f"non-finite logits in lane {i} "
                                           f"at decode position {sl.pos}")
                        done.append(req)
                        continue
                    tok = int(host[i, step])
                    sl.toks.append(tok)
                    sl.pos += 1
                    sl.remaining -= 1
                    self._emit(sl.req, tok)
                    if sl.remaining == 0 or tok == self.eos_id:
                        self._finish(sl.req, sl.toks)
                        done.append(sl.req)
                        self._slots[i] = None
                        if paged:
                            self._release_paged(i)
        self._expire_running(done, paged)
        return done

    def _spec_decode_step(self, live: List[int], paged: bool,
                          done: List[Request]) -> None:
        """One speculative decode step over every live lane.

        Host side proposes up to ``spec.k`` draft tokens per lane by
        prompt lookup over (prompt + emitted tokens); one batched verify
        forward scores current-token + drafts at the lane's positions
        and the fused acceptance rule emits 1..k+1 tokens per lane
        (accepted draft prefix, then the rejection resample or the
        bonus sample). Rollback is a pure position rewind: ``sl.pos``
        advances only by the emitted count, so rejected slots' cache
        rows stay masked (causal + kv_len) until the next verify step
        overwrites them in place — under the paged layout the pages
        were preallocated at admission, so no page is allocated,
        dereffed, or leaked by acceptance or rejection."""
        K = self.spec.k
        C = K + 1
        # the whole host-varying step state in one packed array — see
        # the verify_step closure for the column layout
        packed = np.zeros((self.B, C + 4), np.int32)
        steps = [0] * self.B
        reqs: List[Optional[Request]] = [None] * self.B
        n_prop = 0
        for i in live:
            sl = self._slots[i]
            reqs[i] = sl.req
            # drafts come from the request's own history; d_len is
            # capped at remaining-1 so the emitted run (<= d_len+1)
            # never overruns the decode budget — which also keeps every
            # verify write inside the rows/pages admission reserved
            ctx = np.concatenate([np.asarray(sl.req.prompt, np.int64),
                                  np.asarray(sl.toks, np.int64)])
            d = propose_ngram(ctx, K, self.spec.ngram_max,
                              self.spec.ngram_min)
            dn = max(0, min(len(d), sl.remaining - 1))
            packed[i, 0] = sl.toks[-1]
            packed[i, 1:1 + dn] = d[:dn]
            packed[i, C] = sl.pos
            packed[i, C + 1] = dn + 1
            packed[i, C + 2] = len(sl.toks)
            steps[i] = len(sl.toks)
            n_prop += dn
        self._c_spec_proposed.inc(n_prop)
        temps_d, tks_d, tps_d, seeds_d, _ = self._samp_vectors(
            reqs, steps)
        self._count_decode_compile(
            self.B, "verify-paged" if paged else "verify")
        poison = -1
        if self._faults is not None:
            hit = self._faults.fire("nan_logits")
            if hit is not None:
                poison = int(hit.get("lane", live[0]))
        packed[:, C + 3] = poison
        packed_d = self._commit(jnp.asarray(packed))
        with self._span("verify_step", lanes=len(live), k=K,
                        proposed=n_prop, paged=paged):
            if paged:
                res_d, self._cache = self._verify_paged(
                    self.params, self._cache, packed_d,
                    self._tables_committed(),
                    temps_d, tks_d, tps_d, seeds_d)
            else:
                res_d, self._cache = self._verify(
                    self.params, self._cache, packed_d,
                    temps_d, tks_d, tps_d, seeds_d)
            with self._span("host_sync", steps=1):
                res = np.asarray(res_d)
        out = res[:, :C]
        ne = res[:, C]
        okh = res[:, C + 1:].astype(bool)
        self._c_decode_steps.inc()
        self._c_slot_steps.inc(self.B)
        for i in live:
            sl = self._slots[i]
            if sl is None:
                continue
            n = int(ne[i])
            self._c_spec_accepted.inc(n - 1)
            row = out[i]
            bad = np.flatnonzero(~okh[i, :n])
            if bad.size:
                # poisoned verify: the lane keeps the tokens before the
                # first non-finite slot, then fails alone (a NaN'd lane
                # accepts nothing, so n == 1 and nothing garbage is
                # emitted); neighbors are untouched
                k0 = int(bad[0])
                for t in row[:k0]:
                    sl.toks.append(int(t))
                    self._emit(sl.req, int(t))
                sl.pos += k0
                req = sl.req
                self._c_nan.inc()
                if (self.tracer is not None
                        and req.trace_track is not None):
                    self.tracer.instant("nan_guard",
                                        track=req.trace_track,
                                        cat="request", lane=i, step=k0)
                self._slots[i] = None
                if paged:
                    self._release_paged(i)
                self._finish(req, sl.toks, state=RequestState.FAILED,
                             error=f"non-finite logits in lane {i} at "
                                   f"verify position {sl.pos}")
                done.append(req)
                continue
            emitted = row[:n]
            if self.eos_id is not None:
                hits = np.flatnonzero(emitted == self.eos_id)
                if hits.size:
                    # stop at (and include) the first EOS: later
                    # accepted drafts are discarded, their stale cache
                    # rows die with the lane
                    emitted = emitted[:int(hits[0]) + 1]
            kept = len(emitted)
            for t in emitted:
                sl.toks.append(int(t))
                self._emit(sl.req, int(t))
            sl.pos += kept
            sl.remaining -= kept
            if (sl.remaining == 0
                    or (kept and int(emitted[-1]) == self.eos_id)):
                self._finish(sl.req, sl.toks)
                done.append(sl.req)
                self._slots[i] = None
                if paged:
                    self._release_paged(i)

    # ------------------------------------------------------------------
    # Accounting
    # ------------------------------------------------------------------

    def _counter_values(self) -> dict:
        """Current cumulative values of the windowable counters."""
        self._sync_alloc_metrics()
        return {"admitted": self.admitted,
                "decode_steps": self.decode_steps,
                "slot_steps": self.slot_steps,
                "useful_decode_tokens": self.useful_decode_tokens,
                "prefill_chunk_steps": self.prefill_chunk_steps,
                "prefill_batched_steps": int(
                    self._c_prefill_batched.value),
                "prefill_lane_steps": int(
                    self._c_prefill_lane_steps.value),
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "blocks_evicted": int(self._c_evicted.value),
                "spec_proposed_tokens": int(self._c_spec_proposed.value),
                "spec_accepted_tokens": int(self._c_spec_accepted.value),
                "admit_seconds": self._c_admit_s.value}

    def reset_stats(self) -> None:
        """Start a new stats *window*: ``stats()['window']`` counts from
        here. Explicitly NOT reset: the cumulative (flat) counters, the
        compile counters (the jit cache is an engine-lifetime property —
        a "window" of compiles is meaningless), the latency histograms,
        and the gauges (they describe current state, not a period)."""
        self._window_base = self._counter_values()

    @staticmethod
    def _quantiles(h) -> dict:
        """{p50, p99} of a histogram in seconds; None before any
        observation (JSON-safe, unlike NaN)."""
        if h.count == 0:
            return {"p50": None, "p99": None}
        return {"p50": h.quantile(0.5), "p99": h.quantile(0.99)}

    def stats(self) -> dict:
        """Serving counters, as a view over the metrics registry
        (``Engine.metrics`` holds the full catalog; see
        ``docs/observability.md``).

        Key classes — the cumulative/window split is explicit:

        * flat counter keys (``admitted`` ... ``blocks_evicted``) —
          **cumulative since construction** (bit-compatible with every
          pre-telemetry release);
        * ``window`` — the same counters **since the last**
          :meth:`reset_stats` (plus the window's decode_utilization);
        * ``cumulative_compiles`` — compile counts, never windowed (the
          jit cache is an engine-lifetime property; the flat
          ``*_compiles`` keys alias these);
        * ``ttft_p50/p99`` / ``tpot_p50/p99`` — seconds, from the
          registry's latency histograms (None before any completion;
          TPOT needs a multi-token continuous-scheduler completion).

        decode_utilization is the fraction of decode slot-steps that
        produced a token which made it into a request's output — the
        wave scheduler burns slot-steps on requests shorter than their
        wave; the continuous scheduler only idles lanes when the queue
        runs dry. ``admit_seconds`` is the host time continuous steps
        spent admitting (seconds, windowed like the counts): every lane
        waits for it.

        Paged-layout counters (zero under 'contiguous'):
        ``prefix_hit_tokens`` — prompt tokens served from cached prefix
        pages instead of being re-prefilled; ``blocks_in_use`` — pages
        currently referenced by live block tables (a gauge);
        ``blocks_evicted`` — cached prefix pages reclaimed by LRU
        eviction under pool pressure (cumulative).
        ``prefill_chunk_steps`` counts chunked-prefill invocations under
        both layouts — with prefix hits it drops below the no-sharing
        chunk count, which is how tests prove a shared prefix is
        prefilled exactly once. Batched paged admission
        (``policy.max_prefill_lanes_per_step`` > 1) folds several
        lanes into each invocation: ``prefill_batched_steps`` counts
        the invocations that carried >=2 lanes, ``prefill_lane_steps``
        counts invocations x active lanes, and
        ``prefill_lanes_per_step`` (= lane_steps / chunk_steps) is the
        mean prefill batch occupancy — 1.0 under strictly serial
        admission.

        Lifecycle keys (``docs/robustness.md``): ``submitted`` —
        requests accepted by submit(); ``terminal`` — dict of terminal-
        state counts (finished/cancelled/timed_out/failed/preempted;
        sums to ``submitted`` at quiescence); ``preemptions`` — lane
        evictions (each either requeued or terminal-PREEMPTED);
        ``nan_guard_trips`` — requests failed by the non-finite-logit
        guard; ``rejected_never_fit`` — admissions rejected because
        prompt+budget can never fit. All cumulative (not windowed) —
        ``admitted`` counts every admission *including* preemption
        retries, so ``admitted >= submitted`` under preemption."""
        cum = self._counter_values()
        util = (cum["useful_decode_tokens"] / cum["slot_steps"]
                if cum["slot_steps"] else 0.0)
        window = {k: cum[k] - self._window_base[k]
                  for k in self._WINDOW_KEYS}
        window["decode_utilization"] = (
            window["useful_decode_tokens"] / window["slot_steps"]
            if window["slot_steps"] else 0.0)
        window["spec_acceptance"] = (
            window["spec_accepted_tokens"] / window["spec_proposed_tokens"]
            if window["spec_proposed_tokens"] else 0.0)
        compiles = {"prefill": self.prefill_compiles,
                    "prefill_chunk": self.prefill_chunk_compiles,
                    "decode": self.decode_compiles}
        ttft = self._quantiles(self._h_ttft)
        tpot = self._quantiles(self._h_tpot)
        return {"scheduler": self.scheduler, "backend": self.qm.backend,
                "kv_cache": (self.kv_quant.fmt if self.kv_quant else "none"),
                "kv_layout": self.kv_layout,
                "admitted": cum["admitted"],
                "prefill_compiles": compiles["prefill"],
                "prefill_chunk_compiles": compiles["prefill_chunk"],
                "decode_compiles": compiles["decode"],
                "decode_steps": cum["decode_steps"],
                "slot_steps": cum["slot_steps"],
                "useful_decode_tokens": cum["useful_decode_tokens"],
                "decode_utilization": util,
                "prefill_chunk_steps": cum["prefill_chunk_steps"],
                "prefill_batched_steps": cum["prefill_batched_steps"],
                "prefill_lane_steps": cum["prefill_lane_steps"],
                "prefill_lanes_per_step": (
                    cum["prefill_lane_steps"]
                    / max(cum["prefill_chunk_steps"], 1)),
                "prefix_hit_tokens": cum["prefix_hit_tokens"],
                "spec_proposed_tokens": cum["spec_proposed_tokens"],
                "spec_accepted_tokens": cum["spec_accepted_tokens"],
                "spec_acceptance": (
                    cum["spec_accepted_tokens"]
                    / cum["spec_proposed_tokens"]
                    if cum["spec_proposed_tokens"] else 0.0),
                "blocks_in_use": (self._alloc.in_use if self._alloc
                                  else 0),
                "blocks_evicted": (self._alloc.evicted if self._alloc
                                   else 0),
                "admit_seconds": cum["admit_seconds"],
                "ttft_p50": ttft["p50"], "ttft_p99": ttft["p99"],
                "tpot_p50": tpot["p50"], "tpot_p99": tpot["p99"],
                "submitted": int(self._c_submitted.value),
                "terminal": {s.value: int(c.value)
                             for s, c in self._c_terminal.items()},
                "preemptions": int(self._c_preempt.value),
                "nan_guard_trips": int(self._c_nan.value),
                "rejected_never_fit": int(self._c_never_fit.value),
                "window": window,
                "cumulative_compiles": compiles}

    def kv_bytes_resident(self) -> int:
        """Bytes of KV cache currently holding data the engine may read.

        Contiguous layouts reserve the full (B, max_len) pool up front,
        so the whole allocation is resident regardless of traffic. The
        paged layout counts only pages that are referenced by a live
        block table or cached for prefix reuse (plus the scrap page) —
        the number the serving benchmark tracks to show paging's memory
        win on short/mixed traffic."""
        if self._cache is None:
            return 0
        leaves = jax.tree.leaves(self._cache)
        total = sum(int(a.size) * a.dtype.itemsize for a in leaves)
        if self.kv_layout != "paged":
            # the admission scratch lane is part of the contiguous
            # engine's standing KV allocation
            leaves = jax.tree.leaves(self._slot_cache)
            return total + sum(int(a.size) * a.dtype.itemsize
                               for a in leaves)
        live = self._alloc.resident + self._alloc.reserved
        return total * live // self._alloc.n_pages

    def throughput(self, n_requests: int = 8, prompt_len: int = 32,
                   max_new: int = 32, seed: int = 0,
                   sampling: Optional[SamplingParams] = None) -> dict:
        """Tokens/second over a synthetic request wave (Fig. 4 metric),
        plus the scheduler counters from :meth:`stats`.

        The flat step/token counters and decode_utilization describe
        *this run* only (deltas against the engine's cumulative
        counters; ``window`` is overwritten with the same per-run
        values); compile counts stay cumulative — the jit cache is an
        engine-lifetime property. Timed with ``time.perf_counter()``
        (wall clock is not monotonic)."""
        rng = np.random.default_rng(seed)
        reqs = [Request(prompt=rng.integers(
            0, self.cfg.vocab_size, prompt_len).astype(np.int32),
            max_new=max_new,
            sampling=(dataclasses.replace(sampling, seed=sampling.seed + i)
                      if sampling is not None else None))
            for i in range(n_requests)]
        before = self.stats()
        t0 = time.perf_counter()
        done = self.generate(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        rate = toks / dt if dt > 0 else float("inf")  # clock can tick 0
        run = self.stats()
        for k in self._WINDOW_KEYS:
            run[k] -= before[k]
        run["decode_utilization"] = (
            run["useful_decode_tokens"] / run["slot_steps"]
            if run["slot_steps"] else 0.0)
        run["spec_acceptance"] = (
            run["spec_accepted_tokens"] / run["spec_proposed_tokens"]
            if run["spec_proposed_tokens"] else 0.0)
        run["window"] = {k: run[k] for k in self._WINDOW_KEYS}
        run["window"]["decode_utilization"] = run["decode_utilization"]
        run["window"]["spec_acceptance"] = run["spec_acceptance"]
        return {"tokens": toks, "seconds": dt, "tok_per_s": rate, **run}
