"""Continuous-batching RAP engine — shared-budget serving of concurrent
requests (the production form of paper Algorithm 3).

``RAPServer`` replays requests one at a time, so each request sees a
*private* instantaneous budget and "runtime memory variation" is simulated.
The engine makes the contention real: many in-flight requests compete for
one device budget, and the policy's keep-mask decision is made against
whatever the *pool* has left.

Since the serving-API split (DESIGN.md §2) the engine is a thin
orchestration loop over four seams:

  * :class:`~repro.runtime.scheduler.Scheduler` — who is admitted next
    (FIFO / SJF / priority), emitting explicit ``SchedulerOutput`` plans;
  * :class:`~repro.core.policy.PruningPolicy` — what shape they run in:
    ``observe(PolicyState) → Decision`` against the remaining shared
    budget (the RL controller, any static baseline, or dense);
  * :class:`~repro.runtime.executor.ModelExecutor` — how the mask
    executes: slot groups, prefill, fused bucketed decode;
  * :class:`~repro.runtime.kv_pool.KVPool` — whether the bytes exist:
    page-granular admission against ``budget − resident params``. With a
    paged executor (``PagedExecutor``) the pool additionally OWNS the
    physical page arrays: admission charges the request's worst-case page
    count as a commitment, prefill writes into granted pages, each decoded
    token appends a page when it crosses a boundary (``KVPool.extend``),
    and completion frees the pages.

One iteration of :meth:`RAPEngine._tick` (the async macro-tick,
DESIGN.md §6 — device work is dispatched FIRST so host scheduling
overlaps the in-flight scans):

  1. **launch** — every occupied group in the scheduler's decode plan
     dispatches one fused horizon of up to ``EngineConfig.decode_horizon``
     tokens (DESIGN.md §5). JAX async dispatch returns token futures
     immediately; nothing syncs yet;
  2. **arrivals** — requests become visible at their trace timestamps
     (virtual clock; idle gaps are skipped, compute time is real) and
     enter the scheduler's waiting set;
  3. **admission** — the scheduler orders candidates; for each, the
     policy decides a keep-mask against the *remaining* shared budget and
     the request's analytical KV/state bytes are allocated from the pool.
     A deferral (no pages / no free slots) ends the admission loop, so
     the scheduler's ordering is never overtaken within a tick. ``force``
     mode (the one-shot compatibility path) admits regardless and records
     the overcommit. Prefill is monolithic by default; with
     ``EngineConfig.max_prefill_tokens > 0`` prompts are split into pow2
     chunks advanced one per tick, interleaved with running decodes;
  4. **finish** — the single device→host read-back folds each horizon's
     tokens into the requests that were resident at launch. Completion
     (``max_new`` today; an EOS-style stop condition, when one lands,
     would share the same boundary semantics) is checked once per
     horizon; tokens a request over-generated inside its final horizon
     are truncated, so results are bitwise-identical to H=1.

Completed requests free their pages and slots, unblocking the queue, and
are reported back to the policy via ``feedback()``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core import masks as masks_lib
from repro.core.controller import RAPController
from repro.core.policy import Decision, PolicyState, PruningPolicy
from repro.runtime.executor import (LocalExecutor, ModelExecutor, SlotGroup,
                                    chunk_widths)
from repro.runtime.latency import summarize as _lat_summarize
from repro.runtime.kv_pool import (KVPool, default_page_bytes,
                                   resolve_kv_dtype)
from repro.runtime.scheduler import (Scheduler, VictimCandidate,
                                     make_scheduler)
from repro.runtime.tracing import Recorder

__all__ = ["EngineConfig", "EngineRequest", "RequestResult", "EngineReport",
           "RAPEngine", "compile_cache_dir", "enable_compile_cache"]

_MIGRATION_HINT = (
    "RAPEngine's constructor changed with the serving-API split: it now "
    "takes a PruningPolicy instead of a RAPController. Wrap your "
    "controller — RAPEngine(model, params, "
    "repro.core.policy.RLPolicy(controller), cfg) — or build any "
    "registered policy with repro.core.policy.make_policy(). Schedulers "
    "and executors are injectable via the scheduler=/executor= kwargs."
)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _kv_byte_ratio(kv_dtype, mcfg) -> float:
    """Quantized-vs-model KV byte ratio for slot-cache admission.

    int8/fp8 slot caches store 1-byte elements plus one f32 scale per
    (token, kv-head) (``attention.kv_quant``), while the analytical memory
    model charges at the model's KV width — the ratio converts an
    Eq. (3)–(4) charge into the bytes the cache actually occupies."""
    _, _, quantized, _ = resolve_kv_dtype(kv_dtype)
    if not quantized:
        return 1.0
    from repro.core.memory import dtype_bytes
    dh = max(int(mcfg.dh), 1)
    return (dh * 1.0 + 4.0) / (dh * dtype_bytes(mcfg.dtype))


# -------------------------------------------- persistent compilation cache
# Process-wide hit/miss counters fed by JAX's monitoring events; the engine
# reports per-run deltas next to compile_events. compile_events counts
# TRACES (Python → jaxpr, paid either way); a cache hit means the expensive
# XLA compile behind a trace was served from disk.
_CACHE_EVENTS = {"hits": 0, "misses": 0}
_CACHE_LISTENER = {"registered": False}


def _on_jax_monitoring_event(event: str, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_EVENTS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE_EVENTS["misses"] += 1


# the fixed in-checkout cache location: ``.jax_cache/`` at the repository
# root (listed in .gitignore). A fixed path matters — the directory is part
# of what a later process must find again, so it is never a temp, pid or
# time-based name.
CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where the persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
    :data:`CHECKOUT_COMPILE_CACHE`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        CHECKOUT_COMPILE_CACHE


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory.

    A second serve of the same config (same process or a fresh one)
    re-traces its executables but deserializes the XLA binaries from disk
    instead of recompiling — the recompile-dominated structural cold start
    becomes a warm start (DESIGN.md §9). Process-wide and idempotent; the
    floors are lowered so even sub-second compiles (smoke-sized models)
    populate the cache.
    """
    import jax
    from jax._src import compilation_cache as _cc
    cache_dir = compile_cache_dir()
    changed = _CACHE_LISTENER.get("dir") != cache_dir
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if changed:
        # JAX latches the cache-used decision at the process's FIRST
        # compile: a process that already compiled with caching off (any
        # engine built without compile_cache) must reset the latch or the
        # directory is silently ignored
        _cc.reset_cache()
        _CACHE_LISTENER["dir"] = cache_dir
    if not _CACHE_LISTENER["registered"]:
        jax.monitoring.register_event_listener(_on_jax_monitoring_event)
        _CACHE_LISTENER["registered"] = True
    return cache_dir


# ------------------------------------------------------------------- config
@dataclasses.dataclass
class EngineConfig:
    mode: str = "masked"              # masked | structural
    max_new_tokens: int = 16
    max_active: int = 8               # cache slots per group (decode batch)
    max_len: int = 256                # slot cache length (prompt + generated)
    budget_bytes: float = 0.0         # TOTAL device budget (params + states)
    page_bytes: int = 0               # 0 → derived from the memory model
    tokens_per_page: int = 16
    kv_dtype: Any = None
    admission: str = "strict"         # strict (queue) | force (overcommit)
    # Admission quantizes the effective budget DOWN to this fraction of the
    # request's dense peak before calling the policy. The pool level drifts
    # continuously; without a quantum every admission sees a fresh budget,
    # the policy emits a fresh mask, and structural mode compiles a fresh
    # bucket — quantizing collapses steady-state admissions onto a handful
    # of memoized decisions/buckets. Safety is unaffected: the page
    # allocator, not the decision, enforces the byte budget.
    budget_quantum_frac: float = 0.05
    # "pow2": slot caches are minted per power-of-two length bucket (the
    # group key includes the bucket), so one long prompt mints a long-cache
    # group instead of invalidating every compiled short one, and short
    # requests keep decoding against short caches — the RAPServer shim's
    # setting (sequential serves, heterogeneous lengths). "max" (default):
    # one max_len-sized cache per group family — requests of every length
    # share one decode batch, which is what continuous batching is for;
    # splitting by length would fragment the fused decode step per bucket.
    len_buckets: str = "max"          # max | pow2
    # Decode batch buckets: the executor steps occupied slots in the
    # smallest bucket that holds them instead of always paying
    # max_active-wide compute. () disables (always full width).
    decode_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    # Horizon decode (DESIGN.md §5): each engine macro-tick advances every
    # running request up to this many tokens through ONE fused on-device
    # loop per group, with completion checked at the horizon boundary and
    # over-generated tokens truncated (token streams are bitwise-identical
    # to decode_horizon=1). Clamped per tick to the largest remaining
    # token need in the group, so short tails don't pay full-horizon
    # compute — and, while requests are queued, to the group's SOONEST
    # completion, so a full horizon can't stall admission behind its
    # longest resident. 1 restores per-token ticks.
    decode_horizon: int = 8
    # Chunked prefill (DESIGN.md §6): 0 (default) prefills each prompt in
    # one monolithic pass; >0 caps the prompt tokens prefilled per engine
    # macro-tick — long prompts are split into power-of-two chunks
    # (largest-first, e.g. 13 → 8+4+1 under a cap of 8) interleaved with
    # the running requests' decode horizons, so a long prefill no longer
    # stalls every in-flight decode for its full length. Token streams are
    # bitwise-identical with chunking on or off. Backends without a
    # chunked path (heterogeneous layouts) fall back to monolithic.
    max_prefill_tokens: int = 0
    # Elastic budgets (DESIGN.md §11): when run() is given a budget_trace
    # and the budget shrinks below the bytes already reserved, the engine
    # preempts running victims (Scheduler.select_victims order), spilling
    # their KV pages to host and resuming them when the budget recovers.
    # False serves the trace for observability only: the budget still
    # gates NEW admissions, but running requests are never preempted.
    preemption_enabled: bool = True
    # Preemption overshoots the deficit by this fraction of the shrunken
    # KV budget, so the next admission/extension doesn't immediately
    # re-trigger a shock at the boundary. 0 frees exactly the deficit.
    spill_headroom_frac: float = 0.1
    # "scheduler" delegates victim order to Scheduler.select_victims
    # (SLO tiers + aging under PriorityScheduler); "arrival" preempts the
    # newest running request first (least sunk work, LIFO).
    victim_policy: str = "scheduler"
    # Structural bucket-shape quantization (DESIGN.md §9): snap every
    # decision mask onto a ladder of whole-layer keep-sets before a bucket
    # is minted, realizing the exact mask as 0/1 gates INSIDE the bucket
    # (bitwise-identical tokens), so an adaptive policy's stream of
    # distinct masks compiles a bounded executable family set instead of
    # one program per mask. none | layer | pow2 (masks.quantize_mask);
    # paged executors floor "none" at "layer".
    bucket_quant: str = "none"
    # Cap on live structural slot groups in the default LocalExecutor
    # (0 = unbounded): idle groups past the cap are evicted LRU, dropping
    # their prefill executables and — when they were the signature's last
    # group — the resident compacted param stack.
    max_structural_groups: int = 0
    # Enable JAX's persistent compilation cache (enable_compile_cache:
    # JAX_COMPILATION_CACHE_DIR when set, else .jax_cache/ in the
    # checkout), so a second serve of the same config skips XLA
    # compilation. Per-run activity is reported as
    # EngineReport.compile_cache_hits / compile_cache_misses.
    compile_cache: bool = False

    def __post_init__(self):
        if self.mode not in ("masked", "structural"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.admission not in ("strict", "force"):
            raise ValueError(f"unknown admission {self.admission!r}")
        if self.len_buckets not in ("pow2", "max"):
            raise ValueError(f"unknown len_buckets {self.len_buckets!r} "
                             f"(expected 'pow2' or 'max')")
        if not (0.0 <= self.budget_quantum_frac <= 1.0):
            raise ValueError(
                f"budget_quantum_frac must be in [0, 1], got "
                f"{self.budget_quantum_frac!r} — it is a fraction of the "
                f"request's dense peak (0 disables admission quantization)")
        if self.max_active < 1:
            raise ValueError(
                f"max_active must be >= 1, got {self.max_active!r} — the "
                f"engine needs at least one cache slot to host a request")
        if self.max_len < 1:
            raise ValueError(
                f"max_len must be >= 1, got {self.max_len!r} — slot caches "
                f"must hold at least one token (prompt + generated)")
        if self.max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {self.max_new_tokens!r}")
        if self.tokens_per_page < 1:
            raise ValueError(
                f"tokens_per_page must be >= 1, got "
                f"{self.tokens_per_page!r} — KV pool pages hold at least "
                f"one token of dense per-token state")
        if self.budget_bytes < 0:
            raise ValueError(
                f"budget_bytes must be >= 0, got {self.budget_bytes!r} "
                f"(0 means 'pass the budget per run() call')")
        if self.page_bytes < 0:
            raise ValueError(
                f"page_bytes must be >= 0, got {self.page_bytes!r} "
                f"(0 derives the page size from the memory model)")
        if any(int(b) < 1 for b in self.decode_buckets):
            raise ValueError(
                f"decode_buckets must be positive slot counts, got "
                f"{self.decode_buckets!r}")
        if self.decode_horizon < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {self.decode_horizon!r} "
                f"— each macro-tick advances at least one token")
        if self.max_prefill_tokens < 0:
            raise ValueError(
                f"max_prefill_tokens must be >= 0, got "
                f"{self.max_prefill_tokens!r} (0 prefills prompts "
                f"monolithically; >0 caps prompt tokens prefilled per "
                f"engine tick)")
        if not isinstance(self.preemption_enabled, bool):
            raise ValueError(
                f"preemption_enabled must be a bool, got "
                f"{self.preemption_enabled!r} — it gates mid-serve KV "
                f"spill/resume when a budget_trace shrinks the budget "
                f"below the bytes already reserved")
        if not (0.0 <= self.spill_headroom_frac < 1.0):
            raise ValueError(
                f"spill_headroom_frac must be in [0, 1), got "
                f"{self.spill_headroom_frac!r} — the fraction of the "
                f"shrunken KV budget preemption frees beyond the deficit "
                f"(0 frees exactly the deficit)")
        if self.victim_policy not in ("scheduler", "arrival"):
            raise ValueError(
                f"unknown victim_policy {self.victim_policy!r} (expected "
                f"'scheduler' — Scheduler.select_victims's SLO-tier order "
                f"— or 'arrival' — newest running request first)")
        if self.bucket_quant not in ("none", "layer", "pow2"):
            raise ValueError(
                f"unknown bucket_quant {self.bucket_quant!r} (expected "
                f"'none' — one bucket per exact mask — 'layer' — "
                f"whole-layer buckets over the exact retained rows — or "
                f"'pow2' — keep-count rounded up to a power of two)")
        if self.max_structural_groups < 0:
            raise ValueError(
                f"max_structural_groups must be >= 0, got "
                f"{self.max_structural_groups!r} (0 disables the "
                f"structural group cap)")


@dataclasses.dataclass
class EngineRequest:
    rid: str                          # unique among in-flight requests
    prompt: np.ndarray                # int32 [b, S]
    arrival_t: float = 0.0
    max_new: Optional[int] = None     # generated tokens (≥1: prefill always
                                      # yields one); None → engine default
    priority: int = 0                 # PriorityScheduler rank (lower=sooner)


@dataclasses.dataclass
class RequestResult:
    rid: str
    status: str                       # done | rejected | cancelled
    tokens: Optional[np.ndarray]      # [b, generated]
    mask: Optional[np.ndarray]
    bucket: Tuple
    arrival_t: float
    admitted_t: float
    finished_t: float
    queue_delay_s: float
    decide_s: float
    fits: bool
    cached_decision: bool
    peak_bytes: float
    kv_bytes: float
    reason: str = ""
    # time to first token, measured from ARRIVAL (so it decomposes as
    # queue_delay_s + prefill time; -1.0 for rejected requests)
    ttft_s: float = -1.0
    # token deliveries (engine-clock time, tokens): the prefill's first
    # token, then one entry per decode horizon; Σ tokens == tokens.shape[1]
    # (empty for rejected requests and those cancelled before any token)
    deliveries: List[Tuple[float, int]] = \
        dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EngineReport:
    results: List[RequestResult]
    wall_s: float                     # real compute wall time
    makespan_s: float                 # virtual: includes skipped arrival gaps
    generated_tokens: int
    tokens_per_s: float               # generated / makespan_s
    mean_queue_delay_s: float
    budget_fit_rate: float            # admitted requests whose peak fit
    rejected: int
    decode_iters: int                 # macro-ticks (horizons), not tokens
    compile_events: int
    pool: Dict[str, float]
    # persistent-compile-cache activity during the run (zeros unless
    # EngineConfig.compile_cache enabled the cache): a hit means a
    # traced executable was deserialized from disk instead of recompiled,
    # so a warmed replay shows compile_events ≈ compile_cache_hits and
    # near-zero misses
    compile_cache_hits: int = 0
    compile_cache_misses: int = 0
    # wall time spent inside compiled-executable launches + read-backs
    # (prefill and decode horizons): wall_s − launch_s is the host-side
    # orchestration share the horizon decode exists to shrink
    launch_s: float = 0.0
    # measured physical KV fragmentation: mean over decode ticks of
    # 1 − used_bytes / physical_bytes from the executor's kv_utilization()
    # (0.0 when the backend does not track it)
    measured_frag: float = 0.0
    # latency percentiles (repro.runtime.latency.summarize dicts, seconds):
    # ttft pools per-request time-to-first-token (arrival → first token);
    # itl pools per-token inter-token latencies across every request's
    # decode stream (a fused H-token horizon contributes H samples of its
    # per-token share)
    ttft: Dict[str, float] = dataclasses.field(default_factory=dict)
    itl: Dict[str, float] = dataclasses.field(default_factory=dict)
    # elastic-budget counters (DESIGN.md §11): preemption events, requests
    # cancelled via cancel(), MB of KV spilled to host across the run
    preempted_count: int = 0
    cancelled: int = 0
    spilled_mb: float = 0.0
    # preempt→resume latency percentiles (summarize dict; one sample per
    # resume, on the virtual clock)
    resume_latency: Dict[str, float] = dataclasses.field(default_factory=dict)
    # ITL samples of requests that were preempted at least once, pooled
    # SEPARATELY from `itl` — a resume gap lands in the victim's stream as
    # one huge inter-token latency and would otherwise poison the p99 of
    # requests that were never touched
    itl_preempted: Dict[str, float] = dataclasses.field(default_factory=dict)
    # (virtual_t, budget_bytes) breakpoints the run actually applied —
    # scenario harnesses use these to window per-phase goodput
    budget_events: List[Tuple[float, float]] = \
        dataclasses.field(default_factory=list)
    # the run's host spans and counters (repro.runtime.tracing)
    trace: Optional[Recorder] = None

    def result(self, rid: str) -> RequestResult:
        for r in self.results:
            if r.rid == rid:
                return r
        raise KeyError(rid)


@dataclasses.dataclass
class _Running:
    req: EngineRequest
    decision: Decision
    group: SlotGroup
    slots: List[int]
    admitted_t: float
    kv_bytes: float
    max_new: int
    out: List[np.ndarray]            # per generated step: [b] tokens
    bucket: Tuple
    # token-emission events (virtual-clock time, tokens appended): the
    # first entry is the prefill's token #1 (TTFT anchor); each decode
    # horizon appends one entry covering its H tokens (ITL samples). It
    # becomes the result's ``deliveries``
    events: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    # times this request was preempted (routes its ITL samples to the
    # report's itl_preempted pool instead of itl)
    preempt_count: int = 0
    # set by the force-resume liveness backstop: exempt from further
    # preemption so it drains instead of livelocking (a budget too small
    # for even ONE request would otherwise re-spill the resurrected
    # victim at the next tick start, before it ever decodes)
    pinned: bool = False


@dataclasses.dataclass
class _Prefilling:
    """A request admitted (pool charged, slots reserved) whose prompt is
    still being prefilled chunk-by-chunk across engine ticks."""
    req: EngineRequest
    decision: Decision
    group: SlotGroup
    slots: List[int]
    admitted_t: float
    kv_bytes: float
    max_new: int
    bucket: Tuple
    task: Any                        # executor _PrefillTask


@dataclasses.dataclass
class _Preempted:
    """A running request evicted under a budget shock: its KV pages live
    in the pool's host-side spill store, its non-KV device state (pos,
    last tokens, slot caches for the local path) in ``state``. Resuming
    re-grants pages, restores the state into free slots of an equivalent
    group, and the request decodes on, bitwise-identical to never having
    been preempted."""
    run: _Running
    state: Dict[str, Any]            # executor.spill_state() payload
    cache_len: int                   # group bucket to restore into
    preempted_t: float               # virtual clock (resume-latency anchor)


# ------------------------------------------------------------------- engine
class RAPEngine:
    """Thin orchestration loop: Scheduler × PruningPolicy × ModelExecutor
    × KVPool."""

    def __init__(self, model, params, policy: PruningPolicy = None,
                 cfg: EngineConfig = None, *,
                 scheduler: Optional[Scheduler] = None,
                 executor: Optional[ModelExecutor] = None, **legacy):
        if legacy:
            raise TypeError(
                f"RAPEngine got unexpected kwargs {sorted(legacy)}. "
                + _MIGRATION_HINT)
        if isinstance(policy, RAPController):
            raise TypeError(
                "RAPEngine received a RAPController where a PruningPolicy "
                "is expected. " + _MIGRATION_HINT)
        if policy is None or not isinstance(policy, PruningPolicy):
            raise TypeError(
                f"RAPEngine requires a PruningPolicy, got "
                f"{type(policy).__name__}. " + _MIGRATION_HINT)
        self.model = model
        self.mcfg = model.cfg
        if getattr(self.mcfg, "is_encoder_decoder", False):
            raise NotImplementedError("engine serves decoder-only models")
        self.params = params
        self.policy = policy
        # private copy: ensure_capacity mutates max_len/max_active, and a
        # caller-shared config would desync another engine's shape checks
        # from its actual cache sizes
        self.cfg = dataclasses.replace(cfg if cfg is not None
                                       else EngineConfig())
        if self.cfg.compile_cache:
            enable_compile_cache()
        self.mm = policy.mm
        self.scheduler = make_scheduler(scheduler)
        self.executor = executor if executor is not None else LocalExecutor(
            model, params, mode=self.cfg.mode, max_active=self.cfg.max_active,
            kv_dtype=self.cfg.kv_dtype,
            decode_buckets=self.cfg.decode_buckets,
            bucket_quant=self.cfg.bucket_quant,
            max_groups=self.cfg.max_structural_groups)
        self._paged = bool(getattr(self.executor, "paged", False))
        if self._paged:
            ex_mode = getattr(self.executor, "mode", self.cfg.mode)
            if ex_mode != self.cfg.mode:
                raise ValueError(
                    f"paged executor was built for mode={ex_mode!r} but "
                    f"EngineConfig.mode={self.cfg.mode!r}; construct "
                    f"PagedExecutor(..., mode={self.cfg.mode!r})")
            if self.cfg.admission != "strict":
                raise ValueError(
                    "a paged executor requires strict admission: overflow "
                    "pages have no physical backing to write KV into")
        # precision as a policy action: when the stack was built with a
        # canonical KV precision (cfg.kv_dtype or a quantized executor),
        # stamp it on the policy so every Decision carries it — admission
        # then charges quantized bytes and the pool's dtype check has a
        # request-side precision to validate. Launchers may override
        # policy.kv_dtype afterwards for per-run choices.
        kv_name = getattr(self.executor, "kv_dtype_name", None)
        if kv_name is None:
            kv_name, _, _, _ = resolve_kv_dtype(self.cfg.kv_dtype)
        if kv_name is not None and getattr(policy, "kv_dtype", None) is None:
            policy.kv_dtype = kv_name
        self._full_mask = masks_lib.full_mask(self.mcfg.n_layers)
        self.resident_param_bytes = self.mm.param_bytes(self._full_mask)
        self.pool: Optional[KVPool] = None
        # run state
        self._pending: List[EngineRequest] = []
        self._running: "Dict[str, _Running]" = {}
        self._prefilling: "Dict[str, _Prefilling]" = {}
        self._results: List[RequestResult] = []
        self._ttft_samples: List[float] = []
        self._itl_samples: List[float] = []
        self._decode_iters = 0
        self._compiles_at_run_start = 0
        self._cache_hits_at_run_start = 0
        self._cache_misses_at_run_start = 0
        self._t0 = 0.0
        self._skew = 0.0
        self._budget = self.cfg.budget_bytes
        self._frag_samples: List[float] = []
        # elastic-budget state (DESIGN.md §11)
        self._preempted: "Dict[str, _Preempted]" = {}
        self._budget_trace: Any = None
        self._run_budget = self.cfg.budget_bytes
        self._budget_events: List[Tuple[float, float]] = []
        self._resume_samples: List[float] = []
        self._itl_preempted_samples: List[float] = []
        self._preempted_count = 0
        self._spilled_bytes = 0.0
        self._stall_ticks = 0
        self._new_trace()

    def _new_trace(self) -> None:
        """One recorder per run, shared with the executor."""
        self.trace = Recorder()
        self.executor.tracer = self.trace

    # ------------------------------------------------------------ capacity
    def ensure_capacity(self, batch: int, total_len: int) -> None:
        """Grow slot count / cache-length cap. Slot growth drops compiled
        groups (the slot axis changes); length growth is quantized to
        powers of two and — under pow2 length buckets — keeps every
        existing group valid (they own their own shorter caches)."""
        if total_len > self.cfg.max_len:
            self.cfg.max_len = _next_pow2(total_len)
            if self.cfg.len_buckets == "max":
                # legacy single-length groups are sized by cfg.max_len:
                # growth invalidates them
                self.executor.drop_groups()
        if batch > self.cfg.max_active:
            self.cfg.max_active = int(batch)
            self.executor.set_max_active(self.cfg.max_active)

    def _cache_len(self, total: int) -> int:
        """Cache length bucket hosting a (prompt+gen)-token request.

        pow2 buckets deliberately ignore cfg.max_len (admission already
        guaranteed total ≤ max_len): clamping to a non-power-of-two cap
        would remap the same request shape to a different bucket after
        capacity growth, re-introducing the recompile the buckets exist
        to prevent."""
        if self.cfg.len_buckets == "pow2":
            return max(_next_pow2(total), 16)
        return self.cfg.max_len

    # ---------------------------------------------------------------- time
    def _now(self) -> float:
        return (time.perf_counter() - self._t0) + self._skew

    # ---------------------------------------------------------------- pool
    def _make_pool(self, budget_bytes: float) -> KVPool:
        if self._paged:
            # physical page size is dictated by the model's KV geometry
            # (cfg.page_bytes would desync the ledger from the arrays)
            page = self.executor.page_phys_bytes(self.cfg.tokens_per_page)
        else:
            page = self.cfg.page_bytes or default_page_bytes(
                self.mm, self.cfg.tokens_per_page)
        cap = budget_bytes - self.resident_param_bytes
        if cap < page and self.cfg.admission == "strict":
            raise ValueError(
                f"budget {budget_bytes:.0f}B leaves no KV pool after "
                f"resident params ({self.resident_param_bytes:.0f}B)")
        return KVPool(max(cap, 0.0), page_bytes=page, mm=self.mm,
                      tokens_per_page=(self.cfg.tokens_per_page
                                       if self._paged else None))

    # ------------------------------------------------------------- serving
    def run(self, requests: List[EngineRequest], *,
            budget_bytes: Optional[float] = None,
            budget_trace: Any = None,
            on_tick: Any = None) -> EngineReport:
        """Serve a trace to completion and report aggregate stats.

        ``budget_trace`` makes the device budget time-varying (DESIGN.md
        §10): either a list of ``(t_seconds, budget_bytes)`` breakpoints —
        piecewise-constant on the run's VIRTUAL clock, applied at the
        start of the first tick at or after each breakpoint — or a
        callable ``now → budget_bytes`` evaluated once per tick (call-
        counting callables give deterministic shocks in tests, where tick
        wall time varies). The pool's physical arrays are sized once from
        the base budget — the trace modulates admission and triggers
        preemption; values above the base are clamped by pool capacity.

        ``on_tick(engine)`` is called once per tick during the host phase
        (decode scans already in flight), after launch and before
        arrivals — the seam fault-injection harnesses use to cancel
        requests mid-horizon deterministically.
        """
        budget = self.cfg.budget_bytes if budget_bytes is None else budget_bytes
        self.pool = self._make_pool(budget)
        if self._paged:
            self.executor.bind_pool(self.pool, self.cfg.max_len)
        self._budget = budget
        self._run_budget = budget
        if budget_trace is not None and not callable(budget_trace):
            budget_trace = sorted((float(t), float(v))
                                  for t, v in budget_trace)
        self._budget_trace = budget_trace
        self._budget_events = ([(0.0, float(budget))]
                               if budget_trace is not None else [])
        self._frag_samples: List[float] = []
        self._pending = sorted(requests, key=lambda r: r.arrival_t)
        self.scheduler.clear()
        self._running.clear()
        self._prefilling.clear()
        self._preempted.clear()
        self._results = []
        self._ttft_samples = []
        self._itl_samples = []
        self._resume_samples = []
        self._itl_preempted_samples = []
        self._preempted_count = 0
        self._spilled_bytes = 0.0
        self._stall_ticks = 0
        self._decode_iters = 0
        self._compiles_at_run_start = self.executor.compile_events
        self._cache_hits_at_run_start = _CACHE_EVENTS["hits"]
        self._cache_misses_at_run_start = _CACHE_EVENTS["misses"]
        self._launch_s_at_run_start = getattr(self.executor, "launch_s", 0.0)
        self._new_trace()
        self._skew = 0.0
        self._t0 = time.perf_counter()
        self.executor.evict_all()             # previous run's occupants
        try:
            while (self._pending or len(self.scheduler) or self._running
                   or self._prefilling or self._preempted):
                with self.trace.span("rap.tick"):
                    self._tick(on_tick)
        except BaseException:
            # a run that raises mid-serve must not leak pool ledger
            # entries / spilled pages / seated slots into the next run()
            # on this engine (pinned by
            # tests/test_engine.py::test_run_exception_releases_pool)
            self._abort_cleanup()
            raise
        # makespan is on the VIRTUAL clock (skipped idle gaps included) —
        # the same clock request timestamps live on, so throughput is
        # comparable with any other replay of the same arrival process
        makespan = self._now()
        wall = time.perf_counter() - self._t0
        done = [r for r in self._results if r.status == "done"]
        gen = sum(r.tokens.size for r in done if r.tokens is not None)
        delays = [r.queue_delay_s for r in done]
        return EngineReport(
            results=self._results,
            wall_s=wall,
            makespan_s=makespan,
            generated_tokens=gen,
            tokens_per_s=gen / max(makespan, 1e-9),
            mean_queue_delay_s=float(np.mean(delays)) if delays else 0.0,
            budget_fit_rate=(float(np.mean([r.fits for r in done]))
                             if done else 0.0),
            rejected=sum(1 for r in self._results if r.status == "rejected"),
            decode_iters=self._decode_iters,
            compile_events=(self.executor.compile_events
                            - self._compiles_at_run_start),
            compile_cache_hits=(_CACHE_EVENTS["hits"]
                                - self._cache_hits_at_run_start),
            compile_cache_misses=(_CACHE_EVENTS["misses"]
                                  - self._cache_misses_at_run_start),
            pool=self.pool.stats(),
            launch_s=(getattr(self.executor, "launch_s", 0.0)
                      - self._launch_s_at_run_start),
            measured_frag=(float(np.mean(self._frag_samples))
                           if self._frag_samples else 0.0),
            ttft=_lat_summarize(self._ttft_samples),
            itl=_lat_summarize(self._itl_samples),
            preempted_count=self._preempted_count,
            cancelled=sum(1 for r in self._results
                          if r.status == "cancelled"),
            spilled_mb=self._spilled_bytes / 1e6,
            resume_latency=_lat_summarize(self._resume_samples),
            itl_preempted=_lat_summarize(self._itl_preempted_samples),
            budget_events=list(self._budget_events),
            trace=self.trace)

    # ------------------------------------------------------------ one tick
    def _tick(self, on_tick: Any = None) -> None:
        """One engine macro-tick, host work overlapped with device work:

          0. **budget** — re-evaluate the elastic budget on the virtual
             clock; if reserved bytes now exceed it, preempt victims
             (spill KV pages to host, free slots). This happens FIRST,
             before any launch, when no scan is in flight and the pool's
             page arrays are concrete — the only point in the tick where
             gathering page contents is race-free;
          1. **launch** — dispatch this tick's fused decode horizons (the
             scheduler's decode plan). JAX async dispatch returns the
             token futures immediately, so the scans run on device while…
          2. **host phase** — the on_tick hook, arrivals, resume of
             preempted requests (budget permitting), admission (policy
             decision, pool allocation, page granting), and one chunk of
             every in-flight chunked prefill all execute on the host with
             the scans still in flight (pinned by the transfer-guard
             overlap tests in tests/test_horizon.py);
          3. **finish** — the single device→host read-back folds the
             horizon's tokens into the running requests and completions
             are processed.

        A request admitted during the host phase joins decode from the
        NEXT tick — its slots were free padding (or reserved) when this
        tick's scan launched, so this tick's rows for them are garbage
        and are never read (the launch's captured occupancy pins this).
        The same captured-occupancy contract makes resume and mid-horizon
        cancellation safe: a restored request's slots and pages were free
        at launch, and a cancelled request simply vanishes from
        ``_running`` so fold-back skips it (over-generated horizon tokens
        are truncated exactly like a completion's).

        ``run`` wraps each tick in a ``rap.tick`` span; the phases are its
        children (``repro.runtime.tracing``)."""
        tr = self.trace
        now = self._now()
        with tr.span("rap.budget"):
            self._eval_budget(now)
            self._maybe_preempt(now)
        with tr.span("rap.schedule"):
            plan = self.scheduler.schedule(now, running=list(self._running))
        backlog = (len(self.scheduler) > 0
                   or bool(self._pending
                           and self._pending[0].arrival_t <= now))
        launches = self._launch_decode(plan.decode, backlog=backlog)
        # ---- host phase (device scans in flight from here to finish) ----
        if on_tick is not None:
            with tr.span("rap.on_tick"):
                on_tick(self)
        while self._pending and self._pending[0].arrival_t <= now:
            req = self._pending.pop(0)
            if (req.rid in self.scheduler or req.rid in self._running
                    or req.rid in self._prefilling):
                self._reject(req, f"duplicate request id {req.rid!r} "
                                  f"(already in flight)")
                continue
            max_new = (self.cfg.max_new_tokens if req.max_new is None
                       else req.max_new)
            # total token cost: batch rows each hold prompt+decode tokens
            # (this is what scales the request's KV demand — SJF orders
            # by it)
            cost = req.prompt.shape[0] * (req.prompt.shape[1]
                                          + max(max_new, 1))
            self.scheduler.add(req, cost=cost)
        # resume preempted requests BEFORE admitting new ones: a victim
        # already holds its admission (and its partial output) — letting
        # the queue overtake it would turn one preemption into starvation
        self._try_resume()
        # admission plan: try candidates in the scheduler's order; a
        # deferral ends the loop so the order is never overtaken in-tick
        deferred = None
        with tr.span("rap.schedule"):
            admit = self.scheduler.schedule(now).admit
        for req in admit:
            with tr.span("rap.admit", rid=req.rid):
                verdict = self._try_admit(req)
            if verdict == "defer":
                deferred = req
                break
            self.scheduler.remove(req.rid)
        # a deferral is "stuck" only if judged NOW, before this tick's
        # in-flight work lands: with nothing launched, running,
        # prefilling, or preempted, no completion or resume can ever free
        # the memory it waits on. (Work finishing later this tick frees
        # capacity — the deferred request simply retries next tick.)
        stuck = (deferred is not None and not launches
                 and not self._running and not self._prefilling
                 and not self._preempted)
        self._advance_prefills()
        # ---- finish: the tick's one sync point --------------------------
        if launches:
            with tr.span("rap.foldback"):
                self._finish_decode(launches)
        if self._running or self._prefilling:
            self._stall_ticks = 0
        else:
            self._idle_step(deferred, stuck)

    def _idle_step(self, deferred, stuck: bool) -> None:
        """Liveness with an idle engine (nothing running or prefilling):
        fast-forward the virtual clock to the next event that can change
        admissibility — a pending arrival or a budget-trace breakpoint —
        and backstop the cases where no such event exists (callable
        traces tick forward on evaluation; a trace that never recovers
        must not spin forever)."""
        now = self._now()
        nxt = self._next_breakpoint(now)
        if stuck:
            if nxt is not None:
                # the budget may recover at the next breakpoint: jump
                # there instead of rejecting the deferred head
                self._skew += max(nxt - now, 0.0) + 1e-9
            elif callable(self._budget_trace):
                # call-counting traces advance per evaluation: give the
                # shock a bounded number of idle ticks to recover before
                # declaring the deferral permanent
                self._stall_ticks += 1
                if self._stall_ticks > 256:
                    self.scheduler.remove(deferred.rid)
                    self._reject(deferred, "deferred with idle engine "
                                           "(budget trace never recovered)")
            else:
                # deferred head with an idle engine and no future budget
                # event: reject the scheduler's choice instead of
                # spinning (defensive; strict capacity misfits are
                # rejected in _try_admit already)
                self.scheduler.remove(deferred.rid)
                self._reject(deferred, "deferred with idle engine")
        elif deferred is None and self._pending and not self._preempted:
            # fast-forward the virtual clock across the idle gap (clamped
            # so a budget breakpoint inside the gap is not skipped over)
            tgt = self._pending[0].arrival_t
            if nxt is not None:
                tgt = min(tgt, nxt)
            self._skew += max(tgt - now, 0.0) + 1e-9
        elif self._preempted:
            if nxt is not None:
                tgt = nxt
                if self._pending:
                    tgt = min(tgt, self._pending[0].arrival_t)
                self._skew += max(tgt - now, 0.0) + 1e-9
            else:
                # no breakpoint will ever raise the budget again (constant
                # callable, or trace exhausted low): after a bounded spin,
                # force-resume — physical capacity checks only — so the
                # run drains instead of deadlocking
                self._stall_ticks += 1
                if self._stall_ticks > 8 and not self._force_resume():
                    raise RuntimeError(
                        "elastic-budget deadlock: preempted requests "
                        "cannot be restored even ignoring the budget "
                        "(pool capacity lost?)")

    # ----------------------------------------- elastic budget / preemption
    def _kv_budget(self) -> float:
        """KV-side share of the current elastic budget (params stay
        resident through a shock — shrinking below them just means zero
        KV headroom, not negative)."""
        return max(self._budget - self.resident_param_bytes, 0.0)

    def _eval_budget(self, now: float) -> None:
        """Re-evaluate the piecewise-constant budget on the virtual clock
        (list traces apply every breakpoint ≤ now; callables are invoked
        once per tick). Changes are recorded as (t, bytes) events."""
        tr = self._budget_trace
        if tr is None:
            return
        if callable(tr):
            b = float(tr(now))
        else:
            b = self._run_budget
            for t, v in tr:
                if t <= now + 1e-12:
                    b = v
                else:
                    break
        if b != self._budget:
            self._budget = b
            self._budget_events.append((now, b))

    def _next_breakpoint(self, now: float) -> Optional[float]:
        """First future breakpoint of a list trace (None for callables —
        they advance by being evaluated, and for exhausted traces)."""
        tr = self._budget_trace
        if tr is None or callable(tr):
            return None
        for t, _ in tr:
            if t > now + 1e-12:
                return t
        return None

    def _maybe_preempt(self, now: float) -> None:
        """Shed reserved bytes when the elastic budget shrank below them:
        spill victims (Scheduler.select_victims order) until reservations
        fit the shrunken budget minus headroom. Runs at tick START — no
        scan is in flight, so the pool's page arrays are concrete and
        gathering page contents races nothing. Only decoding requests are
        candidates; an in-flight chunked prefill finishes its prompt
        first and becomes preemptible the next tick."""
        if (not self.cfg.preemption_enabled or self._budget_trace is None
                or not self._running):
            return
        kv_budget = self._kv_budget()
        if self.pool.bytes_reserved <= kv_budget + 1e-6:
            return
        target = kv_budget * (1.0 - self.cfg.spill_headroom_frac)
        cands = [VictimCandidate(
                     rid=rid,
                     priority=getattr(run.req, "priority", 0),
                     arrival_t=run.req.arrival_t,
                     remaining_tokens=max(run.max_new - len(run.out), 0),
                     reserved_bytes=self.pool.request_reserved_bytes(rid))
                 for rid, run in self._running.items() if not run.pinned]
        if self.cfg.victim_policy == "arrival":
            order = sorted(cands, key=lambda c: -c.arrival_t)
        else:
            order = self.scheduler.select_victims(cands, now)
        for cand in order:
            if self.pool.bytes_reserved <= target + 1e-6:
                break
            self._preempt(self._running[cand.rid], now)

    def _preempt(self, run: _Running, now: float) -> None:
        """Evict one running request with its state: copy the non-KV
        device state out (executor seam), free its slots, spill its KV
        pages to the pool's host store, release its reservation."""
        rid = run.req.rid
        cache_len = run.group.cache_len
        with self.trace.span("rap.spill", rid=rid):
            state = self.executor.spill_state(run.group, run.slots)
            run.group.evict(run.slots)
            self._spilled_bytes += self.pool.spill(rid)
        del self._running[rid]
        run.preempt_count += 1
        self._preempted[rid] = _Preempted(run=run, state=state,
                                          cache_len=cache_len,
                                          preempted_t=now)
        self._preempted_count += 1

    def _try_resume(self) -> None:
        """Restore preempted requests that fit the recovered budget,
        most-important first (reverse of preemption order — victims were
        shed least-important first)."""
        if not self._preempted:
            return
        kv_budget = self._kv_budget()
        with self.trace.span("rap.resume"):
            for rid in reversed(list(self._preempted)):
                self._resume_one(rid, kv_budget)

    def _resume_one(self, rid: str, kv_budget: float, *,
                    force: bool = False) -> bool:
        p = self._preempted[rid]
        if not force:
            need = self.pool.restore_reserved_bytes(rid)
            if self.pool.bytes_reserved + need > kv_budget + 1e-6:
                return False
        if not self.pool.can_restore(rid):
            return False
        b = len(p.run.slots)
        group = self.executor.group_for(p.run.decision.mask, p.cache_len)
        free = group.free_slots()
        if len(free) < b:
            return False
        slots = free[:b]
        with self.trace.span("rap.restore", rid=rid):
            rows = self.pool.restore(rid)
            self.executor.restore_state(group, slots, rid, p.state,
                                        p.run.decision.mask, rows)
        run = p.run
        run.group, run.slots = group, slots
        if force:
            run.pinned = True      # liveness: must drain, never re-spill
        del self._preempted[rid]
        self._running[rid] = run
        self._resume_samples.append(self._now() - p.preempted_t)
        self._stall_ticks = 0
        return True

    def _force_resume(self) -> bool:
        """Deadlock backstop: restore the most-important preempted
        request ignoring the elastic budget (physical page/slot capacity
        still checked — with an idle engine every page is free, so this
        succeeds unless the pool itself shrank). The resurrected run is
        PINNED — exempt from re-preemption — so it decodes to completion
        one victim at a time instead of livelocking through an endless
        spill/resume cycle when the shocked budget cannot host even one
        request; the overshoot is bounded by that single run."""
        for rid in reversed(list(self._preempted)):
            if self._resume_one(rid, float("inf"), force=True):
                return True
        return False

    # --------------------------------------------------------- cancellation
    def cancel(self, rid: str) -> bool:
        """Cancel a request at ANY lifecycle stage — pending, queued,
        prefilling, decoding mid-horizon, or preempted. Returns True if
        the request was found and cancelled; False for unknown, already
        finished, or already cancelled ids (idempotent — double-cancel
        and cancel racing a normal completion are both no-ops). Tokens a
        cancelled decode over-generated inside its in-flight horizon are
        truncated: fold-back skips rids no longer in the running set.
        Pages are freed via the pool's ``missing_ok`` seam, so the free
        cannot race a completion's."""
        for i, req in enumerate(self._pending):
            if req.rid == rid:
                self._pending.pop(i)
                self._record_cancelled(req)
                return True
        req = self.scheduler.peek(rid)
        if req is not None:
            self.scheduler.remove(rid)
            self._record_cancelled(req)
            return True
        pf = self._prefilling.pop(rid, None)
        if pf is not None:
            pf.group.evict(pf.slots)
            self.pool.free(rid, missing_ok=True)
            self._record_cancelled(pf.req, decision=pf.decision,
                                   admitted_t=pf.admitted_t,
                                   kv_bytes=pf.kv_bytes, bucket=pf.bucket)
            return True
        run = self._running.pop(rid, None)
        if run is not None:
            run.group.evict(run.slots)
            self.pool.free(rid, missing_ok=True)
            self._record_cancelled(run.req, decision=run.decision,
                                   admitted_t=run.admitted_t,
                                   kv_bytes=run.kv_bytes, bucket=run.bucket,
                                   out=run.out, events=run.events)
            return True
        p = self._preempted.pop(rid, None)
        if p is not None:
            self.pool.drop_spilled(rid, missing_ok=True)
            run = p.run
            self._record_cancelled(run.req, decision=run.decision,
                                   admitted_t=run.admitted_t,
                                   kv_bytes=run.kv_bytes, bucket=run.bucket,
                                   out=run.out, events=run.events)
            return True
        return False

    def _record_cancelled(self, req: EngineRequest, *, decision=None,
                          admitted_t: float = -1.0, kv_bytes: float = 0.0,
                          bucket: Tuple = (), out=None, events=None) -> None:
        now = self._now()
        d = decision
        tokens = np.stack(out, axis=1) if out else None
        ttft = (events[0][0] - req.arrival_t) if events else -1.0
        self._results.append(RequestResult(
            rid=req.rid, status="cancelled", tokens=tokens,
            mask=(d.mask if d is not None else None), bucket=bucket,
            arrival_t=req.arrival_t, admitted_t=admitted_t,
            finished_t=now,
            queue_delay_s=(admitted_t - req.arrival_t if admitted_t >= 0.0
                           else now - req.arrival_t),
            decide_s=(d.latency_s if d is not None else 0.0),
            fits=(d.fits if d is not None else False),
            cached_decision=(d.cached if d is not None else False),
            peak_bytes=(d.peak_bytes if d is not None else 0.0),
            kv_bytes=kv_bytes, reason="cancelled", ttft_s=ttft,
            deliveries=list(events or ())))

    # --------------------------------------------------------- fault safety
    def _abort_cleanup(self) -> None:
        """Release everything a raising run would otherwise leak into the
        next run() on this engine: pool ledger entries (live AND
        spilled), seated slots, and the queues. Idempotent via the pool's
        missing_ok seam."""
        if self.pool is not None:
            for rid in list(self.pool.live_requests()):
                self.pool.free(rid, missing_ok=True)
            for rid in list(self.pool.spilled_requests()):
                self.pool.drop_spilled(rid, missing_ok=True)
        try:
            self.executor.evict_all()
        except Exception:
            pass                      # executor may be mid-wreck already
        self._running.clear()
        self._prefilling.clear()
        self._preempted.clear()
        self.scheduler.clear()
        self._pending = []

    # ----------------------------------------------------------- admission
    def _reject(self, req: EngineRequest, reason: str) -> None:
        now = self._now()
        self._results.append(RequestResult(
            rid=req.rid, status="rejected", tokens=None, mask=None,
            bucket=(), arrival_t=req.arrival_t, admitted_t=-1.0,
            finished_t=now, queue_delay_s=now - req.arrival_t,
            decide_s=0.0, fits=False, cached_decision=False,
            peak_bytes=0.0, kv_bytes=0.0, reason=reason))

    def _try_admit(self, req: EngineRequest) -> str:
        """→ 'admitted' | 'defer' | 'rejected' (rejection recorded here)."""
        b, S = req.prompt.shape
        max_new = (self.cfg.max_new_tokens if req.max_new is None
                   else req.max_new)
        # prefill always yields one token, so the floor is 1 (a max_new=0
        # request is served as prefill-only next-token prediction)
        max_new = max(max_new, 1)
        total = S + max_new
        if req.rid in self._running or req.rid in self._prefilling:
            self._reject(req, f"duplicate request id {req.rid!r} "
                              f"(already in flight)")
            return "rejected"
        if total > self.cfg.max_len or b > self.cfg.max_active:
            if self.cfg.admission != "force":
                self._reject(req, f"shape (b={b}, prompt+gen={total}) "
                                  f"exceeds engine capacity "
                                  f"({self.cfg.max_active} slots × "
                                  f"{self.cfg.max_len})")
                return "rejected"
            if self._running:
                return "defer"   # growth drops live caches; wait for drain
            self.ensure_capacity(b, total)

        # keep-mask against the REMAINING shared budget (quantized down so
        # steady-state admissions hit the policy's memo table)
        eff = self._budget - self.pool.bytes_reserved
        quantum = self.cfg.budget_quantum_frac * self.mm.dense_peak(b, total)
        if quantum > 0 and self.cfg.admission == "strict":
            # (force mode is the one-shot compatibility path: budgets pass
            # through exactly so decisions match the historical contract)
            eff = np.floor(eff / quantum + 1e-9) * quantum
        cache_len = self._cache_len(total)
        with self.trace.span("rap.policy") as sp:
            d = self._sticky_decision(b, total, eff, cache_len)
            if d is None:
                d = self.policy.observe(PolicyState(
                    batch=b, total_len=total, budget_bytes=eff,
                    reserved_bytes=self.pool.bytes_reserved,
                    capacity_bytes=self.pool.acct.capacity_bytes,
                    n_running=len(self._running), now=self._now()))
            sp.set(cached=int(d.cached))
        kv_bytes = self.mm.state_bytes(d.mask, b, total)
        if not self._paged:
            # slot-path admission charges QUANTIZED bytes: the analytical
            # model speaks model-width bytes, but an int8/fp8 slot cache
            # stores 1-byte elements (+ one f32 scale per token·head), so
            # a quantized request admits ~width× the sequence under the
            # same budget. (The paged path gets this for free: its pages
            # are physically narrower, so page counts already shrank, and
            # the pool's in_use_scale converts the analytical charge.)
            kv_bytes *= _kv_byte_ratio(d.kv_dtype, self.mcfg)
        if self._budget_trace is not None and self.cfg.admission == "strict":
            # elastic-budget gate: the pool's capacity was sized from the
            # BASE budget and cannot see a mid-run shrink, so admission
            # additionally checks the request's worst-case reservation
            # against the CURRENT budget — otherwise a shock would admit
            # into bytes the trace just took away and immediately preempt
            worst = (self.pool.pages_for_tokens(b, total)
                     * self.pool.page_bytes if self._paged
                     else self.pool.pages_needed(kv_bytes)
                     * self.pool.page_bytes)
            if self.pool.bytes_reserved + worst > self._kv_budget() + 1e-6:
                return "defer"
        force = self.cfg.admission == "force"
        if self._paged:
            # page-granular admission: the paged path physically stores
            # every layer's KV whatever the mask says (masked-mode gates
            # save compute, not memory), so the charge is the request's
            # worst-case PAGE commitment, not its analytical byte count —
            # the honest signal the policy's budget observation reflects
            if not self.pool.fits_capacity_tokens(b, total):
                self._reject(
                    req, f"{self.pool.pages_for_tokens(b, total)} pages "
                         f"({b}×{total} tokens) can never fit pool "
                         f"capacity of {self.pool.n_pages} pages")
                return "rejected"
            if not self.pool.can_alloc_tokens(b, total):
                return "defer"
        elif not force:
            if not self.pool.fits_capacity(kv_bytes):
                self._reject(req, f"state {kv_bytes:.0f}B can never fit "
                                  f"pool capacity "
                                  f"{self.pool.acct.capacity_bytes:.0f}B")
                return "rejected"
            if not self.pool.can_alloc(kv_bytes):
                return "defer"

        group = self.executor.group_for(d.mask, cache_len)
        free = group.free_slots()
        if len(free) < b:
            return "defer"
        slots = free[:b]
        # admission ends HERE: admitted_t (and so queue_delay_s) measures
        # time spent queued, not queueing + prefill — TTFT decomposes as
        # queue_delay_s + prefill time
        admitted_t = self._now()
        bucket = group.key if self.cfg.mode == "structural" else ()
        chunked = (self.cfg.max_prefill_tokens > 0 and S >= 1
                   and self.executor.supports_chunked_prefill(group))
        if self._paged:
            # grant pages backing the prompt now; commit the decode tail.
            # The ledger's in-use side stays analytical (the Eq. (3)–(4)
            # bytes) as a cross-check against the physical reservation.
            # Chunked prefill grants only the FIRST chunk's pages here —
            # each later chunk extends the allocation just before it runs
            # (the commitment above covers them, so the grants can't fail).
            if chunked:
                c1 = chunk_widths(S, self.cfg.max_prefill_tokens)[0]
                rate = kv_bytes / max(total, 1)
                self.pool.alloc_tokens(req.rid, b, c1, max_tokens=total,
                                       in_use_bytes=rate * c1,
                                       in_use_per_token=rate,
                                       kv_dtype=d.kv_dtype)
            else:
                prompt_bytes = self.mm.state_bytes(d.mask, b, S)
                rate = max(kv_bytes - prompt_bytes, 0.0) / max(total - S, 1)
                self.pool.alloc_tokens(req.rid, b, S, max_tokens=total,
                                       in_use_bytes=prompt_bytes,
                                       in_use_per_token=rate,
                                       kv_dtype=d.kv_dtype)
        else:
            self.pool.alloc(req.rid, kv_bytes, allow_overcommit=force)
        prompt = np.asarray(req.prompt, np.int32)
        if chunked:
            task = self.executor.prefill_begin(
                group, slots, req.rid, prompt, d.mask,
                max_chunk=self.cfg.max_prefill_tokens)
            self._prefilling[req.rid] = _Prefilling(
                req=req, decision=d, group=group, slots=slots,
                admitted_t=admitted_t, kv_bytes=kv_bytes, max_new=max_new,
                bucket=bucket, task=task)
            return "admitted"
        with self._prefill_chunk(req.rid, 0, S):
            first = self.executor.prefill_into(group, slots, req.rid, prompt,
                                               d.mask)
        run = _Running(req=req, decision=d, group=group, slots=slots,
                       admitted_t=admitted_t, kv_bytes=kv_bytes,
                       max_new=max_new, out=[first], bucket=bucket,
                       events=[(self._now(), 1)])
        self._running[req.rid] = run
        # the prefill already produced token #1
        if run.max_new <= len(run.out):
            self._complete(run)
        return "admitted"

    def _sticky_decision(self, b: int, total: int, eff: float,
                         cache_len: int) -> Optional[Decision]:
        """Bucket affinity for structural mode: joining an already-compiled
        bucket whose keep-mask still fits the remaining budget batches with
        the requests resident there and skips both the policy rollout and a
        fresh compile. Without this, the drifting pool level mints a new
        bucket per admission and structural serving degenerates into
        per-request executables (the exact failure one-shot serving has)."""
        if self.cfg.mode != "structural" or self.cfg.admission != "strict":
            return None
        best = None
        for group in self.executor.groups():
            if group.mask is None or len(group.free_slots()) < b:
                continue
            # paged groups have no dense cache (pages grow per token), so
            # any bucket can host any admissible length — cache_len
            # affinity only applies to the slot-cache path
            if not self._paged and group.cache_len != cache_len:
                continue
            peak = self.mm.peak_bytes(group.mask, b, total)
            if peak > eff:
                continue
            if self._paged:
                if not self.pool.can_alloc_tokens(b, total):
                    continue
            elif not self.pool.can_alloc(
                    self.mm.state_bytes(group.mask, b, total)):
                continue
            # prefer the bucket keeping the most blocks (least over-pruned)
            kept = int(group.mask.sum())
            if best is None or kept > best[0]:
                best = (kept, group, peak)
        if best is None:
            return None
        _, group, peak = best
        return Decision(mask=group.mask.copy(), steps=0, peak_bytes=peak,
                        fits=True, latency_s=0.0, cached=True)

    # ------------------------------------------------------ chunked prefill
    def _advance_prefills(self) -> None:
        """Advance every in-flight chunked prefill by ONE chunk (at most
        ``cfg.max_prefill_tokens`` prompt tokens) — the interleave grain
        that bounds how long a long prompt can stall running decodes. A
        completing prefill seats its request (it joins decode next tick)
        and stamps its first-token event."""
        for rid in list(self._prefilling):
            pf = self._prefilling[rid]
            task = pf.task
            with self._prefill_chunk(rid, task.pos, task.widths[task.step]):
                first = self.executor.prefill_step(task)
            if first is None:
                continue
            del self._prefilling[rid]
            run = _Running(req=pf.req, decision=pf.decision, group=pf.group,
                           slots=pf.slots, admitted_t=pf.admitted_t,
                           kv_bytes=pf.kv_bytes, max_new=pf.max_new,
                           out=[first], bucket=pf.bucket,
                           events=[(self._now(), 1)])
            self._running[rid] = run
            if run.max_new <= len(run.out):
                self._complete(run)

    @contextlib.contextmanager
    def _prefill_chunk(self, rid: str, start: int, width: int):
        """A ``rap.prefill_chunk`` span and its chunk record."""
        with self.trace.span("rap.prefill_chunk", rid=rid, start=start,
                             width=width) as sp:
            yield
        self.trace.chunk(sp.end, rid, start, width)

    # --------------------------------------------------------------- decode
    def _launch_decode(self, decode_plan: Optional[List[str]],
                       backlog: bool = False) -> List[Tuple[Any, set]]:
        """Dispatch one fused horizon per occupied group named in the
        scheduler's decode plan, WITHOUT syncing. Returns the in-flight
        launches paired with the rids resident at launch time (the only
        requests this tick's tokens belong to). Plans are per-request but
        execution is per-group: a group steps if any of its residents are
        planned (the fused scan advances every occupant regardless — an
        unplanned co-resident's tokens are still folded back, since
        skipping them would discard real device work)."""
        launches: List[Tuple[Any, set]] = []
        if not self._running:
            return launches
        allowed = None if decode_plan is None else set(decode_plan)
        for group in self.executor.groups():
            if not group.occupied():
                continue
            runs = [run for run in self._running.values()
                    if run.group is group]
            if not runs or (allowed is not None
                            and not any(r.req.rid in allowed for r in runs)):
                continue
            # clamp the horizon to the group's largest remaining token
            # need, QUANTIZED up to a power of two: executables are
            # compiled per (batch width, horizon), and an exact clamp
            # would mint one per remaining-need value (timing-dependent —
            # steady state would never stop compiling). Pow2 bounds the
            # horizon set to {1, 2, 4, ...} while short tails still skip
            # most full-horizon compute; the overshoot is truncated at
            # fold-back.
            remaining = max((run.max_new - len(run.out) for run in runs),
                            default=1)
            horizon = min(self.cfg.decode_horizon,
                          _next_pow2(max(remaining, 1)))
            if backlog:
                # admission-stall clamp (bench triage): while requests
                # wait, a full horizon holds every completion — and the
                # slots/budget it would free — hostage until the group's
                # LONGEST resident retires it, so short-max_new traces
                # see queue delay grow with H. Clamp to the group's
                # soonest completion instead (pow2-quantized, same
                # bounded executable set): finished requests hand their
                # capacity to the queue at the earliest boundary. With an
                # empty queue the max-need horizon amortizes dispatch
                # exactly as before. Horizon size stays unobservable in
                # the token streams either way (truncated at fold-back).
                soonest = min((run.max_new - len(run.out) for run in runs),
                              default=1)
                horizon = min(horizon, _next_pow2(max(soonest, 1)))
            launches.append((self.executor.decode_launch(group, horizon),
                             {run.req.rid for run in runs}))
        return launches

    def _finish_decode(self, launches: List[Tuple[Any, set]]) -> None:
        """The tick's sync point: read back each launched horizon and fold
        its tokens into the requests that were resident at launch (a
        request admitted during the overlapped host phase gets nothing
        from this tick — its slot's rows are garbage). Completion is
        checked once at the horizon boundary; a request whose ``max_new``
        lands mid-horizon keeps only the tokens up to it — the trailing
        over-generated ones are truncated here, which is what makes
        horizon size unobservable in the results (bitwise-identical to
        decode_horizon=1)."""
        for launch, rids in launches:
            toks, _ = self.executor.decode_finish(launch)
            now = self._now()
            for rid in rids:
                run = self._running.get(rid)
                if run is None:
                    continue
                need = run.max_new - len(run.out)
                if need <= 0:
                    continue
                cols = toks[np.asarray(run.slots)]     # [b, horizon]
                n = min(need, launch.horizon)
                for h in range(n):
                    run.out.append(cols[:, h])
                run.events.append((now, n))
        self._decode_iters += 1
        used, phys = self.executor.kv_utilization()
        if phys > 0:
            self._frag_samples.append(1.0 - used / phys)
        done = [run for run in self._running.values()
                if len(run.out) >= run.max_new]
        # batch the device-side slot resets: one fused eviction per group
        # per macro-tick instead of one per completing request
        by_group: Dict[int, Tuple[Any, List[int]]] = {}
        for run in done:
            slots = by_group.setdefault(id(run.group), (run.group, []))[1]
            slots.extend(run.slots)
        for group, slots in by_group.values():
            group.evict(slots)
        for run in done:
            self._complete(run, evict=False)

    def _complete(self, run: _Running, *, evict: bool = True) -> None:
        if evict:
            run.group.evict(run.slots)
        self.pool.free(run.req.rid)
        now = self._now()
        d = run.decision
        # latency samples from the run's token-emission events: TTFT is
        # first token minus ARRIVAL (it includes the queue delay); each
        # later event covers one fused horizon and contributes its
        # per-token share n times, so long horizons don't undercount
        ttft = (run.events[0][0] - run.req.arrival_t if run.events
                else -1.0)
        if run.events:
            self._ttft_samples.append(ttft)
            # a preempted request's resume gap lands in its stream as one
            # huge inter-token latency: pool those samples separately so
            # untouched requests' ITL percentiles stay meaningful
            sink = (self._itl_preempted_samples if run.preempt_count > 0
                    else self._itl_samples)
            prev = run.events[0][0]
            for t, n in run.events[1:]:
                sink.extend([(t - prev) / max(n, 1)] * n)
                prev = t
        result = RequestResult(
            rid=run.req.rid, status="done",
            tokens=np.stack(run.out, axis=1),       # [b, generated]
            mask=d.mask, bucket=run.bucket,
            arrival_t=run.req.arrival_t, admitted_t=run.admitted_t,
            finished_t=now, queue_delay_s=run.admitted_t - run.req.arrival_t,
            decide_s=d.latency_s, fits=d.fits, cached_decision=d.cached,
            peak_bytes=d.peak_bytes, kv_bytes=run.kv_bytes, ttft_s=ttft,
            deliveries=run.events)
        self._results.append(result)
        del self._running[run.req.rid]
        self.policy.feedback(result)

    # ---------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        return dict(self.executor.stats())
