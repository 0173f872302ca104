"""Model executors — the execution seam of the serving engine.

The engine decides *who* runs (scheduler) and *what shape* they run in
(pruning policy); a :class:`ModelExecutor` owns *how* the chosen masks
execute: slot-batched caches, compiled executable families, prefill
scattering, and the fused decode loop. PR 1 inlined all of this into
``RAPEngine``; extracting it means sharded serving is "swap the
executor", not "rewrite the engine".

Decode state is **device-resident** (DESIGN.md §5 "Horizon decode"):
groups keep tokens, positions, gates, and (paged) page-table rows as
device arrays that are updated *incrementally* at placement, eviction,
and page grants — never re-uploaded per step — and decode advances in
fused **horizons** of H tokens: one compiled ``lax.scan`` launch, one
``[B, H]`` token read-back. A warmed horizon performs zero host↔device
transfers between the launch and that read-back (pinned in
``tests/test_horizon.py`` under ``jax.transfer_guard``).

Executors:
  * :class:`LocalExecutor` — today's single-process path. Groups (one per
    structural bucket, or one gated group in masked mode) are additionally
    keyed by a power-of-two *cache length*, so a long request mints a new
    long-cache group instead of invalidating every compiled short one.
    Decode runs in dynamic batch buckets B ∈ {1, 2, 4, 8} (ROADMAP): the
    occupied slots are gathered into the smallest bucket that holds them,
    stepped H tokens, and scattered back, so a lightly loaded engine does
    not pay full-slot-count compute per token.
  * :class:`PagedExecutor` — physically paged KV execution (DESIGN.md §3
    "Paged KV"): requests own *pages* of a global KV pool
    (``repro.runtime.kv_pool.KVPool`` holds the page arrays), prefill
    writes KV straight into granted pages, and one fused horizon launch
    advances any mix of cache lengths through a per-request page table —
    no ``max_len × max_active`` slot caches, no pow2 cache-length groups,
    and page-granular (not slot-granular) internal fragmentation. Pages
    for the whole horizon are pre-granted in ONE bulk ``KVPool.extend``
    before the launch (the admission-time worst-case commitment
    guarantees it cannot fail), so no paging happens mid-loop. Serves
    both pruning modes: structural mode runs per-bucket compacted layer
    stacks over the SAME shared pool (a bucket with L' retained layers
    touches pool layers 0..L'-1 of its pages; see DESIGN.md §9).
  * :class:`ShardedExecutor` — mesh-resident serving (DESIGN.md §7
    "Sharded serving"): parameters placed with the production partition
    rules of ``repro.parallel.sharding`` (and a sharded decode-step
    lowering for cost analysis, ``launch/rap_sweep.py``), groups are
    :class:`ShardedSlotGroup` whose decode state lives sharded on the
    mesh — KV over slots (DP) and KV heads (TP), gates replicated — and
    whose horizon scan is ONE mesh-lowered executable per macro-tick,
    paying collectives once per H tokens. Masked mode only; structural
    sharded buckets are a ROADMAP item.

``LocalExecutor`` remains the reference backend: it serves every layout
(heterogeneous mixers keep per-request slot state) and both pruning modes,
and every other backend's token-equivalence is pinned against it by the
cross-executor conformance suite (``tests/test_executors.py``) — a new
executor only registers a fixture there.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masks as masks_lib
from repro.kernels import paged_decode_attention as paged_kernel
from repro.models import attention, decoder
from repro.runtime import tracing
from repro.runtime.kv_pool import resolve_kv_dtype

__all__ = ["ModelExecutor", "SlotGroup", "LocalExecutor", "PagedExecutor",
           "PagedGroup", "ShardedExecutor", "ShardedSlotGroup",
           "chunk_widths"]


def chunk_widths(n_tokens: int, max_chunk: int) -> List[int]:
    """Split a prompt into power-of-two chunk widths for chunked prefill.

    Greedy largest-power-of-two-first: 13 tokens under an 8-token cap
    chunk as [8, 4, 1]. Every width is an exact power of two ≤ the cap,
    so the chunked-prefill executable set is bounded at log2(cap)+1
    widths per (batch, group) — and chunks are never padded, which is
    what keeps chunked prefill bitwise-identical to the monolithic pass
    (no garbage K/V ever lands in the cache)."""
    n = int(n_tokens)
    cap = int(max_chunk)
    if n < 1:
        raise ValueError(f"n_tokens must be >= 1, got {n_tokens!r}")
    if cap < 1:
        raise ValueError(f"max_chunk must be >= 1, got {max_chunk!r}")
    cap = 1 << (cap.bit_length() - 1)          # pow2 floor of the cap
    widths: List[int] = []
    while n > 0:
        c = min(cap, 1 << (n.bit_length() - 1))
        widths.append(c)
        n -= c
    return widths


@dataclasses.dataclass
class _PrefillTask:
    """One in-flight chunked prefill (``prefill_begin``/``prefill_step``).

    The request's slots are *reserved* in its group for the task's
    lifetime (they pad no decode bucket and admit no other request) and
    seated only when the final chunk completes. ``state`` is the
    backend's partial cache (Local: the request-sized attn cache the
    chunks accumulate into; Paged: None — chunks write straight into the
    pool's granted pages)."""
    group: Any
    slots: List[int]
    rid: str
    prompt: np.ndarray                # int32 [b, S]
    mask: Optional[np.ndarray]
    gates: Optional[dict]             # mask_to_gates(mask) for gated groups
    widths: List[int]                 # pow2 chunk widths, sum == S
    pos: int = 0                      # prompt tokens processed so far
    step: int = 0                     # chunks processed so far
    state: Any = None

    @property
    def done(self) -> bool:
        return self.pos >= self.prompt.shape[1]


@dataclasses.dataclass
class _InFlightHorizon:
    """A launched-but-unsynced fused decode horizon.

    ``decode_launch`` returns one; ``decode_finish`` performs the single
    device→host read-back. Occupancy is captured at launch so host work
    overlapped with the in-flight scan (admission may seat new requests
    into slots that were free/padding when the scan launched) cannot
    corrupt the finish-side bookkeeping."""
    group: Any
    horizon: int
    toks_dev: Any                     # device [width, horizon] tokens
    idx: Optional[List[int]]          # stepped slots (None = full width)
    occupants: List[Optional[str]]    # per stepped slot, at launch time
    new: bool                         # compiled a new executable


# Fused device-state updates. Placement/eviction touch four resident
# tensors each; issuing the column updates as eager ``.at[].set`` chains
# costs one dispatch (plus index-normalization work) per tensor per call,
# which the admission/completion profile is dominated by. One shared
# jitted executable per update kind replaces the chain with a single
# launch; donation makes the updates in-place.
@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def rap_paged_place(table, pos, tok, gates, sidx, rows, plen, first, cols):
    return (table.at[sidx].set(rows),
            pos.at[sidx].set(plen),
            tok.at[sidx].set(first),
            gates.at[:, :, sidx].set(cols[:, :, None]))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def rap_paged_evict(table, pos, tok, gates, sidx, scratch):
    return (table.at[sidx].set(scratch),
            pos.at[sidx].set(0),
            tok.at[sidx].set(0),
            gates.at[:, :, sidx].set(1.0))


@functools.partial(jax.jit, donate_argnums=(0,))
def rap_paged_grant(table, rows, cols, vals):
    return table.at[rows, cols].set(vals)


def _hold_free(pos, pos_out):
    """Positions after a paged horizon: free rows (position 0 — a seated
    row holds at least its prompt) stay at 0, so their length stays
    within one horizon and the decode kernel walks them one block."""
    return jnp.where(pos == 0, pos, pos_out)


def rap_slot_place(cache, tokens, req_cache, sidx, plen, first, cols,
                   gates):
    out = {}
    for k, v in cache.items():
        if k == "pos":
            out[k] = v.at[sidx].set(plen)
        else:
            out[k] = jax.tree.map(
                lambda big, small: big.at[:, sidx].set(small), v,
                req_cache[k])
    tokens = tokens.at[sidx, 0].set(first)
    if gates is not None:
        gates = gates.at[:, :, sidx].set(cols[:, :, None])
    return out, tokens, gates


# undecorated body kept separate: ShardedSlotGroup re-jits it with explicit
# output shardings so placement cannot silently re-shard the resident state
_slot_place_upd = jax.jit(rap_slot_place, donate_argnums=(0, 1, 7))


def _mark_compile(sp, executor, compiles_before: int, key: str) -> None:
    """Tag a dispatch span that mints a new executable."""
    if executor.compile_events != compiles_before:
        sp.set(compiled=1, key=key)


# distinct occupancy patterns a group may cache device index vectors for;
# a long adaptive serve cycles through unboundedly many patterns, so the
# cache evicts FIFO past the cap (each entry is a tiny int32 vector, but
# "tiny and immortal" is still a leak)
_IIDX_CACHE_CAP = 256


def _cached_iidx(cache: Dict[Tuple[int, ...], Any], idx: List[int]):
    """Device copy of a slot-index vector, cached by its pattern — the
    hot paths (horizon launches, placement, eviction) re-use the resident
    array instead of re-uploading the index list every call."""
    key = tuple(idx)
    dev = cache.get(key)
    if dev is None:
        if len(cache) >= _IIDX_CACHE_CAP:
            cache.pop(next(iter(cache)))
        dev = jnp.asarray(idx, jnp.int32)
        cache[key] = dev
    return dev


def _gate_cols(mask, gate_rows: Optional[np.ndarray]) -> np.ndarray:
    """A request's gate columns [2, Lg] for its host group: the keep-mask
    split into mixer/ffn rows and, for gated *compacted* buckets,
    restricted to the bucket's retained layers (``gate_rows`` — gates are
    indexed by compacted layout position, not original layer)."""
    m = np.asarray(mask, np.float32)
    L = m.shape[0] // 2
    gm, gf = m[:L], m[L:]
    if gate_rows is not None:
        gm, gf = gm[gate_rows], gf[gate_rows]
    return np.stack([gm, gf])


def _bucket_batch(occ: List[int], free: List[int], n_slots: int,
                  buckets: Sequence[int]) -> Optional[List[int]]:
    """Slot indices to step this iteration: the occupied slots padded with
    free ones up to the smallest bucket that holds them, or None for the
    full-width path. Padding uses *distinct free* slots so a scatter-back
    never writes one index twice; their compute is garbage but unobservable
    (slot rows are independent and re-seeded on placement)."""
    n = len(occ)
    for b in sorted(set(buckets)):
        if n <= b < n_slots:
            return occ + free[: b - n]
    return None


# ------------------------------------------------------------------- groups
class SlotGroup:
    """One slot-batched executable family sharing a cache.

    masked mode: a single group over the full params with per-slot gates.
    structural mode: one group per bucket (compacted params, gates absorbed
    into structure). Groups are minted per (bucket, cache_len).

    All decode state — the cache (including int32 [n_slots] positions),
    the per-slot seed tokens, and the [2, L, n_slots] gate tensor — lives
    on device. Placement and eviction touch only the affected columns via
    ``.at[...]`` updates; a horizon launch reads the resident arrays
    directly, so the per-token hot path performs no host→device uploads.
    """

    def __init__(self, key, params, layout, cfg_model, n_slots: int,
                 cache_len: int, kv_dtype, gated: bool,
                 mask: Optional[np.ndarray] = None,
                 gate_rows: Optional[np.ndarray] = None):
        self.key = key                # logical bucket key ("masked" | tuple)
        self.params = params
        self.layout = layout
        self.mask = mask              # the keep-mask that minted this bucket
        # gated compacted buckets (bucket quantization): the original
        # layer index behind each layout row — request masks restrict to
        # these rows before becoming per-slot gate columns
        self.gate_rows = gate_rows
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.gated = gated
        self.occupants: List[Optional[str]] = [None] * n_slots
        # slots held by an in-flight chunked prefill: not yet occupied
        # (no decode steps them) but not free either (no other admission
        # may claim them). Cleared by place()/evict().
        self.reserved: set = set()
        self.cache = decoder.init_cache(cfg_model, n_slots, cache_len,
                                        layout, kv_dtype)
        self.cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
        self.tokens = jnp.zeros((n_slots, 1), jnp.int32)
        if gated:
            # gates are indexed by layout position: a compacted gated
            # bucket carries len(layout) gate rows, not n_layers
            Lg = len(layout) if layout is not None else cfg_model.n_layers
            self._gates_dev = jnp.ones((2, Lg, n_slots), jnp.float32)
        self._mcfg = cfg_model
        # fused horizon executables, one jit per horizon length (batch
        # widths retrace inside jit); compile accounting per (width, H)
        self._hfns: Dict[int, Any] = {}
        self._compiled_batches: set = set()
        # device copies of the bucket gather/scatter index vectors, keyed
        # by the occupancy pattern — steady-state horizons re-use them
        # instead of re-uploading the index list every launch
        self._iidx_cache: Dict[Tuple[int, ...], Any] = {}

    # ----------------------------------------------------------- occupancy
    def free_slots(self) -> List[int]:
        return [i for i, o in enumerate(self.occupants)
                if o is None and i not in self.reserved]

    def occupied_slots(self) -> List[int]:
        return [i for i, o in enumerate(self.occupants) if o is not None]

    def occupied(self) -> bool:
        return any(o is not None for o in self.occupants)

    def place(self, rid: str, slots: List[int], req_cache: dict,
              mask: Optional[np.ndarray], prompt_len: int,
              first: np.ndarray) -> None:
        """Write a freshly prefilled request cache into ``slots`` — cache
        rows, positions, seed tokens, and (masked mode) ONLY the placed
        gate columns, all in one fused jitted update. Re-uploading the
        full [2, L, n_slots] gate tensor per placement would scale
        placement cost with slot count, not request size."""
        self.reserved.difference_update(slots)
        for s in slots:
            self.occupants[s] = rid
        cols = None
        if self.gated and mask is not None:
            cols = _gate_cols(mask, self.gate_rows)
        # mask=None on a gated group skips the gate write (the historical
        # contract): the fused update traces a no-gate variant rather
        # than scattering a None
        gates = self._gates_dev if cols is not None else None
        self.cache, self.tokens, gates = self._place_fn(cols is not None)(
            self.cache, self.tokens, req_cache, self._iidx(slots),
            int(prompt_len), np.asarray(first, np.int32), cols, gates)
        if cols is not None:
            self._gates_dev = gates

    def _place_fn(self, with_gates: bool):
        """The fused placement executable — mesh-resident subclasses
        override to pin output shardings to the group's layout."""
        return _slot_place_upd

    def evict(self, slots: List[int]) -> None:
        self.reserved.difference_update(slots)
        for s in slots:
            self.occupants[s] = None

    # -------------------------------------------------------------- decode
    def _decode_batch(self, buckets: Sequence[int]) -> Optional[List[int]]:
        return _bucket_batch(self.occupied_slots(), self.free_slots(),
                             self.n_slots, buckets)

    def _full_width_horizon(self, horizon: int):
        """Un-jitted full-width fused horizon:
        ``(p, cache, tok[, gates]) → (toks [B, H], cache', last [B, 1])``.
        Shared between the local jit and the sharded re-jit
        (:class:`ShardedSlotGroup` pins ``out_shardings`` on it), so the
        horizon step itself is defined exactly once."""
        h = int(horizon)
        cfg, layout_c, gated = self._mcfg, self.layout, self.gated
        if gated:
            def rap_slot_decode_horizon(p, cache, tok, gates):
                toks, cache = decoder.decode_horizon(
                    p, cfg, cache, tok, h,
                    gates={"mixer": gates[0], "ffn": gates[1]},
                    layout=layout_c)
                return toks, cache, toks[:, -1:]
        else:
            def rap_slot_decode_horizon(p, cache, tok):
                toks, cache = decoder.decode_horizon(p, cfg, cache, tok, h,
                                                     layout=layout_c)
                return toks, cache, toks[:, -1:]
        return rap_slot_decode_horizon

    def _horizon_fn(self, horizon: int, bucketed: bool):
        """Jitted fused horizon, one executable family per (H, bucketed).
        The bucketed variant takes the *full-width* resident state plus a
        device index vector and performs the gather → H-step scan →
        scatter-back entirely inside the compiled call — eager indexing
        would smuggle a scalar host→device upload per launch (the index
        normalization constant), which the transfer-guard test forbids."""
        h = int(horizon)
        key = (h, bool(bucketed))
        if key not in self._hfns:
            cfg, layout_c, gated = self._mcfg, self.layout, self.gated

            if not bucketed:
                fn = jax.jit(self._full_width_horizon(h),
                             donate_argnums=(1, 2))
            else:
                def scan_h(p, cache, tok, gates):
                    g = ({"mixer": gates[0], "ffn": gates[1]} if gated
                         else None)
                    return decoder.decode_horizon(p, cfg, cache, tok, h,
                                                  gates=g, layout=layout_c)

                def gather_scan_scatter(p, cache, tok, gates, iidx):
                    sub = {k: (v[iidx] if k == "pos"
                               else jax.tree.map(lambda a: a[:, iidx], v))
                           for k, v in cache.items()}
                    toks, sub = scan_h(p, sub, tok[iidx],
                                       gates[:, :, iidx]
                                       if gates is not None else None)
                    out = {}
                    for k, v in sub.items():
                        if k == "pos":
                            out[k] = cache[k].at[iidx].set(v)
                        else:
                            out[k] = jax.tree.map(
                                lambda full, small, _i=iidx:
                                full.at[:, _i].set(small), cache[k], v)
                    tok = tok.at[iidx].set(toks[:, -1:])
                    return toks, out, tok

                if gated:
                    def rap_slot_decode_horizon_bucketed(p, cache, tok,
                                                         gates, iidx):
                        return gather_scan_scatter(p, cache, tok, gates,
                                                   iidx)
                else:
                    def rap_slot_decode_horizon_bucketed(p, cache, tok,
                                                         iidx):
                        return gather_scan_scatter(p, cache, tok, None,
                                                   iidx)
                fn = jax.jit(rap_slot_decode_horizon_bucketed,
                             donate_argnums=(1, 2))
            self._hfns[key] = fn
        return self._hfns[key]

    def _iidx(self, idx: List[int]):
        return _cached_iidx(self._iidx_cache, idx)

    def launch_horizon(self, horizon: int,
                       buckets: Sequence[int] = ()) -> Tuple[Any,
                                                             Optional[List[int]],
                                                             bool]:
        """Device phase of a fused H-token decode: pick the batch bucket,
        gather the stepped slots' state (on device), launch ONE compiled
        ``lax.scan`` executable that advances them ``horizon`` tokens, and
        fold the updated state back into the resident arrays. Returns
        (device toks [width, horizon], stepped slot ids or None for full
        width, new-compile flag). Once an occupancy pattern and executable
        are warm this performs zero host↔device transfers — the caller's
        single ``np.asarray`` on the returned tokens is the only sync."""
        idx = self._decode_batch(buckets) if buckets else None
        width = self.n_slots if idx is None else len(idx)
        key = (width, int(horizon))
        new = key not in self._compiled_batches
        self._compiled_batches.add(key)
        fn = self._horizon_fn(horizon, bucketed=idx is not None)
        args = (self.params, self.cache, self.tokens)
        if self.gated:
            args += (self._gates_dev,)
        if idx is not None:
            args += (self._iidx(idx),)
        toks, cache, last = fn(*args)
        self.cache = cache
        self.tokens = last
        return toks, idx, new

    def decode_horizon(self, horizon: int,
                       buckets: Sequence[int] = ()) -> Tuple[np.ndarray,
                                                             bool]:
        """Advance every occupied slot ``horizon`` tokens; returns
        ([n_slots, horizon] tokens — unstepped rows are zero/garbage — and
        whether this call compiled a new executable)."""
        toks_dev, idx, new = self.launch_horizon(horizon, buckets)
        if idx is None:
            return np.asarray(toks_dev), new
        out = np.zeros((self.n_slots, int(horizon)), np.int32)
        out[np.asarray(idx)] = np.asarray(toks_dev)
        return out, new

    def decode_once(self, buckets: Sequence[int] = ()) -> Tuple[np.ndarray,
                                                                bool]:
        """Single-token compatibility wrapper over :meth:`decode_horizon`."""
        toks, new = self.decode_horizon(1, buckets)
        return toks[:, 0], new


# ---------------------------------------------------------------- protocol
class ModelExecutor:
    """Execution backend protocol for the engine.

    ``group_for`` resolves a keep-mask (+ cache length) to the slot group
    that will host the request; ``prefill_into`` seats a prefilled request;
    ``decode_horizon`` advances one group H tokens in one fused launch
    (``decode`` is the H=1 compatibility form). ``compile_events`` counts
    new executables (prefill shapes + decode (batch, horizon) buckets);
    ``launch_s`` accumulates wall time spent inside compiled-executable
    launches and their read-backs, so benchmarks can separate host
    orchestration overhead from device compute: it is the summed duration
    of the ``rap.dispatch`` and ``rap.readback`` spans that ``tracer`` (a
    :class:`~repro.runtime.tracing.Recorder`, the engine's during a run)
    records around them.

    ``paged`` marks backends whose KV lives in a :class:`KVPool`'s physical
    page arrays — the engine switches admission to the token-granular pool
    API and calls ``bind_pool`` per run. ``kv_utilization`` reports
    (used_bytes, physical_bytes) of the live KV storage so benchmarks can
    measure *physical* internal fragmentation, not just the ledger's."""

    compile_events: int = 0
    launch_s: float = 0.0
    paged: bool = False

    def group_for(self, mask: np.ndarray, cache_len: int) -> SlotGroup:
        raise NotImplementedError

    def prefill_into(self, group: SlotGroup, slots: List[int], rid: str,
                     prompt: np.ndarray, mask: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    # ------------------------------------------------- chunked prefill seam
    def supports_chunked_prefill(self, group: SlotGroup) -> bool:
        """Whether ``prefill_begin``/``prefill_step`` work for this group.
        Default False: backends without a chunked path fall back to
        monolithic ``prefill_into`` transparently."""
        return False

    def prefill_begin(self, group: SlotGroup, slots: List[int], rid: str,
                      prompt: np.ndarray, mask: np.ndarray, *,
                      max_chunk: int) -> _PrefillTask:
        """Reserve ``slots`` and open a chunked prefill over ``prompt``
        (pow2 widths per :func:`chunk_widths`). Advance it one chunk at a
        time with :meth:`prefill_step`."""
        raise NotImplementedError

    def prefill_step(self, task: _PrefillTask) -> Optional[np.ndarray]:
        """Process the task's next chunk. Returns None while the prompt is
        incomplete; on the final chunk, seats the request into its slots
        (``place``) and returns the first sampled tokens ``[b]`` — the
        same contract as monolithic ``prefill_into``'s return."""
        raise NotImplementedError

    # ------------------------------------------------- decode launch/finish
    def decode_launch(self, group: SlotGroup,
                      horizon: int) -> "_InFlightHorizon":
        """Dispatch one fused H-token decode for ``group`` and return
        WITHOUT syncing — JAX async dispatch means the host is free to do
        scheduling/admission work while the scan runs on device. Pair with
        :meth:`decode_finish` for the read-back."""
        raise NotImplementedError

    def decode_finish(self,
                      launch: "_InFlightHorizon") -> Tuple[np.ndarray, bool]:
        """Block on ``launch``'s device tokens (the tick's single sync) and
        fold them back to host form: ([n_slots, horizon] tokens,
        new-compile flag). Slots whose occupant changed since launch (host
        work seated a new request into a then-free padding slot) are left
        untouched."""
        raise NotImplementedError

    def _readback(self, launch: "_InFlightHorizon") -> np.ndarray:
        """The launch's single device→host sync, as a ``rap.readback``
        span that ``launch_s`` adds up."""
        with self.tracer.span("rap.readback") as sp:
            nxt = np.asarray(launch.toks_dev)
        self.launch_s += sp.seconds
        return nxt

    def decode_horizon(self, group: SlotGroup,
                       horizon: int) -> Tuple[np.ndarray, bool]:
        """Advance every occupied slot of ``group`` by ``horizon`` tokens;
        returns ([n_slots, horizon] next tokens, new-compile flag).
        Equivalent to ``decode_finish(decode_launch(...))`` with no host
        work in between."""
        raise NotImplementedError

    def decode(self, group: SlotGroup) -> Tuple[np.ndarray, bool]:
        toks, new = self.decode_horizon(group, 1)
        return toks[:, 0], new

    # ---------------------------------------------------- preemption seam
    def spill_state(self, group: SlotGroup, slots: List[int]) -> dict:
        """Host-side copy of everything the executor holds for the request
        resident in ``slots`` — enough for :meth:`restore_state` to reseat
        it bitwise after its device memory was reclaimed. Called BEFORE the
        group eviction / pool spill; pairs with ``KVPool.spill`` (which
        carries the physical page contents on paged backends)."""
        raise NotImplementedError

    def restore_state(self, group: SlotGroup, slots: List[int], rid: str,
                      state: dict, mask: Optional[np.ndarray],
                      rows: Optional[List[List[int]]] = None) -> None:
        """Reseat a previously spilled request into ``slots`` of ``group``
        from its :meth:`spill_state` snapshot. ``rows`` carries the
        re-granted page ids on paged backends (``KVPool.restore``'s
        return); slot backends reconstruct from the snapshot alone. The
        reseated decode state is exactly what an unpreempted run would
        hold, so the continued token stream is bitwise-identical."""
        raise NotImplementedError

    def groups(self) -> List[SlotGroup]:
        raise NotImplementedError

    def set_max_active(self, n_slots: int) -> None:
        raise NotImplementedError

    def drop_groups(self) -> None:
        """Invalidate every compiled group (capacity reshape)."""
        raise NotImplementedError

    def evict_all(self) -> None:
        for g in self.groups():
            g.evict(list(range(g.n_slots)))

    def kv_utilization(self) -> Tuple[float, float]:
        """(used_bytes, physical_bytes) of live KV storage; (0, 0) when the
        backend does not track it. ``used`` counts tokens actually written
        by resident requests; ``physical`` counts the allocated arrays
        backing them — their ratio is the *measured* (not analytical)
        internal fragmentation."""
        return 0.0, 0.0

    def stats(self) -> Dict[str, int]:
        return {"compile_events": self.compile_events}


# ------------------------------------------------------------------- local
class LocalExecutor(ModelExecutor):
    """Single-process slot-batched execution (the PR 1 path, extracted),
    plus dynamic decode-batch buckets, per-cache-length groups, and fused
    horizon decode."""

    def __init__(self, model, params, *, mode: str = "masked",
                 max_active: int = 8, kv_dtype=None,
                 decode_buckets: Sequence[int] = (1, 2, 4, 8),
                 bucket_quant: str = "none", max_groups: int = 0):
        if mode not in ("masked", "structural"):
            raise ValueError(f"unknown mode {mode!r}")
        if bucket_quant not in ("none", "layer", "pow2"):
            raise ValueError(f"unknown bucket_quant {bucket_quant!r}; "
                             "expected none|layer|pow2")
        self.model = model
        self.mcfg = model.cfg
        self.params = params
        self.mode = mode
        self.bucket_quant = bucket_quant
        self.max_groups = int(max_groups)   # structural group cap, 0 = ∞
        self.max_active = int(max_active)
        # canonical precision names ("fp32"/"bf16"/"int8"/"fp8") resolve to
        # their storage dtype so --kv-dtype works on the slot path too; raw
        # dtype objects (the historical API) pass through unchanged
        _, _store, _, _ = resolve_kv_dtype(kv_dtype)
        self.kv_dtype = _store if _store is not None else kv_dtype
        self.decode_buckets = tuple(int(b) for b in decode_buckets or ())
        self.compile_events = 0
        self.launch_s = 0.0
        self.tracer = tracing.Recorder()
        # structural groups are keyed by (gather_key, cache_len) — the
        # EXACT parameter rows they decode with — never by bucket_key
        # alone, which aliases different-layer drops onto one signature
        self._groups: Dict[Tuple, SlotGroup] = {}
        self._prefill_fns: Dict[Tuple, Any] = {}
        # one device-resident compacted stack per gather signature, shared
        # by every cache-length group of that bucket and refcounted so it
        # frees when its last group drops: gather_key -> [params, layout,
        # refs]
        self._resident: Dict[Tuple, list] = {}

    # ------------------------------------------------------------ capacity
    def _invalidate(self) -> None:
        """THE invalidation path: groups, their prefill executables, and
        the resident compacted stacks drop together. Any key kept behind a
        cleared group dict would pin dead XLA executables (or device
        params) for the executor's lifetime — capacity reshapes and bucket
        churn must not be able to strand them."""
        self._groups.clear()
        self._prefill_fns.clear()
        self._resident.clear()

    def set_max_active(self, n_slots: int) -> None:
        """Changing the slot count changes every cache's slot axis — the
        full compiled state drops (one unified invalidation path with
        :meth:`drop_groups`; re-minting a handful of prefill executables
        on the next admission is cheaper than auditing which stale keys
        are still reachable)."""
        if int(n_slots) == self.max_active:
            return
        self.max_active = int(n_slots)
        self._invalidate()

    def drop_groups(self) -> None:
        self._invalidate()

    # -------------------------------------------------------------- groups
    def groups(self) -> List[SlotGroup]:
        return list(self._groups.values())

    def _resident_acquire(self, rkey: Tuple, qmask: np.ndarray):
        """(params, layout) for a gather signature, minting the compacted
        device stack on first use and bumping its refcount."""
        ent = self._resident.get(rkey)
        if ent is None:
            small, layout = masks_lib.compact_params(self.params, self.mcfg,
                                                     qmask)
            ent = self._resident[rkey] = [small, layout, 0]
        ent[2] += 1
        return ent[0], ent[1]

    def _resident_release(self, rkey: Tuple) -> None:
        ent = self._resident.get(rkey)
        if ent is None:
            return
        ent[2] -= 1
        if ent[2] <= 0:
            del self._resident[rkey]

    def _drop_group(self, gkey: Tuple) -> None:
        """Drop one structural group: release its resident-params ref and,
        when it was the last group of its (signature, cache_len), the
        prefill executables compiled for that family."""
        g = self._groups.pop(gkey)
        self._resident_release(gkey[0])
        if not any(og.key == g.key and og.cache_len == g.cache_len
                   for og in self._groups.values()):
            dead = [k for k in self._prefill_fns
                    if (k[0] == g.key and k[1] == g.cache_len)
                    or (k[0] == "chunk" and k[1] == g.key
                        and k[2] == g.cache_len)]
            for k in dead:
                del self._prefill_fns[k]

    def _maybe_evict_structural(self) -> None:
        """Enforce the structural-group cap before minting a new group:
        evict idle (unoccupied, unreserved) structural groups in LRU
        order. Busy groups are never evicted — under a cap smaller than
        the working set the dict temporarily overshoots instead."""
        if self.max_groups <= 0:
            return
        n_struct = sum(1 for k in self._groups if k[0] != "masked")
        while n_struct >= self.max_groups:
            idle = [k for k, g in self._groups.items()
                    if k[0] != "masked" and not g.occupied()
                    and not g.reserved]
            if not idle:
                break
            self._drop_group(idle[0])
            n_struct -= 1

    def group_for(self, mask: np.ndarray, cache_len: int) -> SlotGroup:
        if self.mode == "masked":
            key = "masked"
            gkey = (key, cache_len)
            if gkey not in self._groups:
                self._groups[gkey] = SlotGroup(
                    key, self.params, None, self.mcfg, self.max_active,
                    cache_len, self.kv_dtype, gated=True)
            return self._groups[gkey]
        # bucket quantization first (identity under "none"), then key the
        # group by the exact gather indices: two masks dropping DIFFERENT
        # layers share a bucket_key (by design — one compiled family) but
        # must never share compacted params
        qmask = masks_lib.quantize_mask(self.mcfg, mask, self.bucket_quant)
        rkey = masks_lib.gather_key(self.mcfg, qmask)
        gkey = (rkey, cache_len)
        group = self._groups.get(gkey)
        if group is not None:
            self._groups[gkey] = self._groups.pop(gkey)   # LRU touch
            return group
        self._maybe_evict_structural()
        small, layout = self._resident_acquire(rkey, qmask)
        gated = self.bucket_quant != "none"
        # group.mask is engine-facing sticky-affinity metadata: store the
        # exact MINTING mask, not qmask — a rounded-up bucket mask would
        # make bucket affinity adopt a less-pruned (up to dense) decision,
        # diverging quantized runs from unquantized ones. Per-request
        # masks ride the slot gates, so correctness never reads this.
        group = SlotGroup(
            masks_lib.bucket_key(self.mcfg, qmask), small, layout,
            self.mcfg, self.max_active, cache_len, self.kv_dtype,
            gated=gated, mask=np.array(mask, copy=True),
            gate_rows=(masks_lib.keep_rows(self.mcfg, qmask) if gated
                       else None))
        self._groups[gkey] = group
        return group

    # ------------------------------------------------------------- prefill
    def _prefill_fn(self, group: SlotGroup, b: int, S: int):
        key = (group.key, group.cache_len, b, S)
        if key not in self._prefill_fns:
            cfg, max_len = self.mcfg, group.cache_len
            kv_dtype, layout = self.kv_dtype, group.layout
            if group.gated:
                # same-signature buckets share this executable: their
                # (compacted) layouts are identical tuples and the params
                # arrive as jit arguments, never closure constants
                def rap_slot_prefill(p, tokens, gm, gf):
                    return decoder.prefill(p, cfg, tokens, max_len,
                                           gates={"mixer": gm, "ffn": gf},
                                           layout=layout, kv_dtype=kv_dtype)
            else:
                def rap_slot_prefill(p, tokens):
                    return decoder.prefill(p, cfg, tokens, max_len,
                                           layout=layout, kv_dtype=kv_dtype)
            self._prefill_fns[key] = jax.jit(rap_slot_prefill)
            self.compile_events += 1
        return self._prefill_fns[key]

    def prefill_into(self, group: SlotGroup, slots: List[int], rid: str,
                     prompt: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Prefill the request and seat it; returns token #1 per row [b]."""
        b, S = prompt.shape
        tokens = jnp.asarray(prompt, jnp.int32)
        c0 = self.compile_events
        fn = self._prefill_fn(group, b, S)
        with self.tracer.span("rap.dispatch") as sp:
            _mark_compile(sp, self, c0, f"prefill:{group.key}:{b}x{S}")
            if group.gated:
                cols = _gate_cols(mask, group.gate_rows)
                logits, cache = fn(group.params, tokens, cols[0], cols[1])
            else:
                logits, cache = fn(group.params, tokens)
            first = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        self.launch_s += sp.seconds
        cache.pop("pos")
        group.place(rid, slots, cache, mask if group.gated else None, S,
                    first)
        return first

    # ----------------------------------------------------- chunked prefill
    def supports_chunked_prefill(self, group: SlotGroup) -> bool:
        """Chunked prefill resumes a positional KV write frontier — only
        uniform all-attention layouts have one (recurrent/SSD state can't
        be re-entered mid-prompt)."""
        layout = group.layout or decoder.default_layout(self.mcfg)
        return bool(layout) and decoder._is_uniform(layout) \
            and layout[0].mixer == "attn"

    def _chunk_fn(self, group: SlotGroup, b: int, C: int):
        """Jitted one-chunk prefill step, keyed by chunk *width* only (the
        chunk's absolute offset is a traced int32 scalar): a prompt split
        into pow2 widths reuses log2(cap)+1 executables per (group, b)
        regardless of prompt length or how far along the chunk sits."""
        key = ("chunk", group.key, group.cache_len, b, C)
        if key not in self._prefill_fns:
            cfg, layout = self.mcfg, group.layout
            if group.gated:
                def rap_slot_prefill_chunk(p, attn, tokens, start, gm, gf):
                    logits, cache = decoder.prefill_chunk(
                        p, cfg, {"attn": attn}, tokens, start,
                        gates={"mixer": gm, "ffn": gf}, layout=layout)
                    return logits, cache["attn"]
            else:
                def rap_slot_prefill_chunk(p, attn, tokens, start):
                    logits, cache = decoder.prefill_chunk(
                        p, cfg, {"attn": attn}, tokens, start,
                        layout=layout)
                    return logits, cache["attn"]
            self._prefill_fns[key] = jax.jit(rap_slot_prefill_chunk,
                                             donate_argnums=(1,))
            self.compile_events += 1
        return self._prefill_fns[key]

    def prefill_begin(self, group: SlotGroup, slots: List[int], rid: str,
                      prompt: np.ndarray, mask: np.ndarray, *,
                      max_chunk: int) -> _PrefillTask:
        """Open a chunked prefill: reserve the slots and mint the
        request-sized partial cache the chunks accumulate into (placed
        into the group only when the last chunk lands)."""
        prompt = np.asarray(prompt, np.int32)
        b, S = prompt.shape
        attn = decoder.init_cache(self.mcfg, b, group.cache_len,
                                  group.layout, self.kv_dtype)["attn"]
        group.reserved.update(slots)
        gates = None
        if group.gated:
            cols = _gate_cols(mask, group.gate_rows)
            gates = {"mixer": cols[0], "ffn": cols[1]}
        return _PrefillTask(group=group, slots=list(slots), rid=rid,
                            prompt=prompt, mask=mask, gates=gates,
                            widths=chunk_widths(S, max_chunk), state=attn)

    def prefill_step(self, task: _PrefillTask) -> Optional[np.ndarray]:
        group = task.group
        b, S = task.prompt.shape
        c = task.widths[task.step]
        tokens = jnp.asarray(task.prompt[:, task.pos:task.pos + c],
                             jnp.int32)
        c0 = self.compile_events
        fn = self._chunk_fn(group, b, c)
        with self.tracer.span("rap.dispatch") as sp:
            _mark_compile(sp, self, c0, f"chunk:{group.key}:{b}x{c}")
            if group.gated:
                logits, task.state = fn(group.params, task.state, tokens,
                                        np.int32(task.pos),
                                        task.gates["mixer"],
                                        task.gates["ffn"])
            else:
                logits, task.state = fn(group.params, task.state, tokens,
                                        np.int32(task.pos))
            task.pos += c
            task.step += 1
            first = (np.asarray(jnp.argmax(logits, axis=-1)
                                .astype(jnp.int32)) if task.done else None)
        self.launch_s += sp.seconds
        if first is None:
            return None
        group.place(task.rid, task.slots, {"attn": task.state},
                    task.mask if group.gated else None, S, first)
        task.state = None
        return first

    # ---------------------------------------------------- preemption seam
    def spill_state(self, group: SlotGroup, slots: List[int]) -> dict:
        """Gather the request's slot-cache rows (every cache leaf,
        positions, seed tokens) to host arrays. The gather reuses the
        group's cached device index vector; ``np.asarray`` round-trips
        f32/bf16/int8 exactly, so reseating is bitwise. Works unchanged on
        mesh-resident groups — the host copy implicitly gathers shards."""
        iidx = group._iidx(list(slots))
        cache = {}
        for k, v in group.cache.items():
            if k == "pos":
                continue
            cache[k] = jax.tree.map(lambda a: np.asarray(a[:, iidx]), v)
        pos = np.asarray(group.cache["pos"])
        return {"cache": cache,
                # one request's rows share one position (placed together,
                # stepped together)
                "pos": int(pos[slots[0]]),
                "first": np.asarray(group.tokens)[np.asarray(slots), 0]}

    def restore_state(self, group: SlotGroup, slots: List[int], rid: str,
                      state: dict, mask: Optional[np.ndarray],
                      rows: Optional[List[List[int]]] = None) -> None:
        """Reseat via the ordinary fused placement update: the snapshot's
        cache rows have the same shapes a monolithic prefill produces, so
        this reuses the compiled placement executable (and, on sharded
        groups, its pinned output shardings)."""
        group.place(rid, list(slots), state["cache"],
                    mask if group.gated else None, state["pos"],
                    state["first"])

    # -------------------------------------------------------------- decode
    def decode_launch(self, group: SlotGroup,
                      horizon: int) -> _InFlightHorizon:
        tr = self.tracer
        with tr.span("rap.decode_launch", horizon=int(horizon)) as dl:
            with tr.span("rap.dispatch") as sp:
                toks_dev, idx, new = group.launch_horizon(
                    horizon, self.decode_buckets)
                width = group.n_slots if idx is None else len(idx)
                if new:
                    self.compile_events += 1
                    sp.set(compiled=1,
                           key=f"decode:{group.key}:{width}x{horizon}")
            self.launch_s += sp.seconds
            dl.set(width=width)
            occ = (list(group.occupants) if idx is None
                   else [group.occupants[s] for s in idx])
        return _InFlightHorizon(group=group, horizon=int(horizon),
                                toks_dev=toks_dev, idx=idx, occupants=occ,
                                new=new)

    def decode_finish(self,
                      launch: _InFlightHorizon) -> Tuple[np.ndarray, bool]:
        nxt = self._readback(launch)
        if launch.idx is None:
            return nxt, launch.new
        out = np.zeros((launch.group.n_slots, launch.horizon), np.int32)
        out[np.asarray(launch.idx)] = nxt
        return out, launch.new

    def decode_horizon(self, group: SlotGroup,
                       horizon: int) -> Tuple[np.ndarray, bool]:
        return self.decode_finish(self.decode_launch(group, horizon))

    # ---------------------------------------------------------- utilization
    def kv_utilization(self) -> Tuple[float, float]:
        """Slot caches are dense ``[n_slots, cache_len]`` arrays: physical
        bytes exist for every minted group whether or not its slots are
        occupied, and an occupied slot pins ``cache_len`` tokens while using
        only its current position. Only attention KV (the per-token state)
        is counted; fixed-size recurrent state is excluded from both
        sides."""
        used = phys = 0.0
        for g in self.groups():
            entry = g.cache.get("attn")
            if entry is None:     # windowed/recurrent state is fixed-size
                continue
            attn_bytes = sum(int(v.size) * v.dtype.itemsize
                             for v in entry.values())
            if attn_bytes == 0:
                continue
            phys += attn_bytes
            occ = g.occupied_slots()
            if occ:
                per_tok = attn_bytes / (g.n_slots * g.cache_len)
                pos = np.asarray(g.cache["pos"])[np.asarray(occ)]
                # a just-finished slot may have over-advanced inside its
                # final horizon (truncated tokens); its cache writes past
                # cache_len were dropped, so clamp the used-token count
                pos = np.minimum(pos, g.cache_len)
                used += float(pos.sum()) * per_tok
        return used, phys

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict[str, int]:
        return {
            "groups": len(self._groups),
            # distinct parameter gathers resident — NOT (gather, cache_len)
            # entries, which pow2 length bucketing would overcount
            "structural_buckets": len({k for k, _ in self._groups
                                       if k != "masked"}),
            # distinct compiled families (bucket signatures): what bucket
            # quantization bounds — many gathers may share one signature
            "bucket_signatures": len({g.key for g in self._groups.values()
                                      if g.key != "masked"}),
            "resident_param_stacks": len(self._resident),
            "prefill_executables": len(self._prefill_fns),
            "masked_prefill_executables": sum(
                1 for k in self._prefill_fns if k[0] == "masked"),
            "compile_events": self.compile_events,
        }


# ------------------------------------------------------------------- paged
class PagedGroup:
    """One paged executable family: occupancy + page tables, no slot cache.

    Satisfies the slice of the ``SlotGroup`` surface the engine touches
    (``free_slots`` / ``occupied_slots`` / ``occupied`` / ``evict`` /
    ``n_slots`` / ``key`` / ``mask``). KV lives in the bound pool's page
    arrays; this object owns the per-slot decode state around them —
    int32 page-table rows, write positions, next tokens, and gates — as
    **device-resident** arrays (``table_dev``/``pos_dev``/``tokens_dev``/
    ``gates_dev``) updated incrementally at placement, eviction, and page
    grants, plus host numpy mirrors (``table``/``pos``/``tokens``) for
    the engine's occupancy bookkeeping and utilization sampling."""

    def __init__(self, cfg_model, n_slots: int, max_row_pages: int,
                 scratch_page: int, *, key="paged", mask=None, layout=None,
                 params=None, gate_rows: Optional[np.ndarray] = None):
        self.key = key                 # "paged" | structural bucket signature
        self.mask = mask               # structural: the bucket's keep-mask
        self.layout = layout           # structural: compacted LayerSlots
        self.params = params           # structural: compacted param stack
        self.gate_rows = gate_rows     # structural: original rows per slot
        self.cache_len = 0             # no dense cache — pages grow per token
        self.n_slots = n_slots
        self.max_row_pages = max_row_pages
        self.scratch_page = scratch_page
        self.occupants: List[Optional[str]] = [None] * n_slots
        # slots held by an in-flight chunked prefill (see SlotGroup.reserved)
        self.reserved: set = set()
        # padded decode rows write their garbage KV into the scratch page
        self.table = np.full((n_slots, max_row_pages), scratch_page, np.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        self.tokens = np.zeros((n_slots,), np.int32)
        # gates are indexed by layout position (see SlotGroup)
        Lg = len(layout) if layout is not None else cfg_model.n_layers
        self.table_dev = jnp.asarray(self.table)
        self.pos_dev = jnp.asarray(self.pos)
        self.tokens_dev = jnp.asarray(self.tokens)
        self.gates_dev = jnp.ones((2, Lg, n_slots), jnp.float32)
        self._iidx_cache: Dict[Tuple[int, ...], Any] = {}

    def free_slots(self) -> List[int]:
        return [i for i, o in enumerate(self.occupants)
                if o is None and i not in self.reserved]

    def occupied_slots(self) -> List[int]:
        return [i for i, o in enumerate(self.occupants) if o is not None]

    def occupied(self) -> bool:
        return any(o is not None for o in self.occupants)

    def iidx(self, idx: List[int]):
        return _cached_iidx(self._iidx_cache, idx)

    def place(self, rid: str, slots: List[int], rows_np: np.ndarray,
              prompt_len: int, first: np.ndarray, gm: np.ndarray,
              gf: np.ndarray) -> None:
        """Seat a prefilled request: host mirrors plus ONE fused jitted
        update writing the placed rows of every resident tensor (nothing
        is re-uploaded beyond the new rows themselves)."""
        npg = rows_np.shape[1]
        full_rows = np.full((len(slots), self.max_row_pages),
                            self.scratch_page, np.int32)
        full_rows[:, :npg] = rows_np
        self.reserved.difference_update(slots)
        for i, s in enumerate(slots):
            self.occupants[s] = rid
            self.table[s] = full_rows[i]
            self.pos[s] = prompt_len
            self.tokens[s] = first[i]
        cols = np.stack([np.asarray(gm, np.float32),
                         np.asarray(gf, np.float32)])
        (self.table_dev, self.pos_dev, self.tokens_dev,
         self.gates_dev) = rap_paged_place(
            self.table_dev, self.pos_dev, self.tokens_dev, self.gates_dev,
            self.iidx(slots), full_rows, int(prompt_len),
            np.asarray(first, np.int32), cols)

    def grant_pages(self, entries: List[Tuple[int, int, int]]) -> None:
        """Extend page-table rows with freshly granted pages:
        ``entries`` = (slot, column, page id). One fused scatter updates
        the device table; the host mirror tracks it."""
        if not entries:
            return
        rows = np.asarray([e[0] for e in entries], np.int32)
        cols = np.asarray([e[1] for e in entries], np.int32)
        vals = np.asarray([e[2] for e in entries], np.int32)
        self.table[rows, cols] = vals
        self.table_dev = rap_paged_grant(self.table_dev, rows, cols, vals)

    def evict(self, slots: List[int]) -> None:
        self.reserved.difference_update(slots)
        for s in slots:
            self.occupants[s] = None
            self.table[s] = self.scratch_page
            self.pos[s] = 0
            self.tokens[s] = 0
        if slots:
            (self.table_dev, self.pos_dev, self.tokens_dev,
             self.gates_dev) = rap_paged_evict(
                self.table_dev, self.pos_dev, self.tokens_dev,
                self.gates_dev, self.iidx(slots), self.scratch_page)


class PagedExecutor(ModelExecutor):
    """Physically paged KV execution.

    The engine's :class:`~repro.runtime.kv_pool.KVPool` owns the page
    arrays (``bind_pool`` materializes them at pool capacity, once per
    run); this executor owns the executables around them:

      * **prefill** runs the gated full-sequence pass with its cache sized
        to the request's granted pages and scatters the KV *directly into
        those pages* inside the same jitted call (the pool arrays are
        donated through it);
      * **decode** batches any mix of cache lengths through one fused
        paged horizon (``repro.models.decoder.paged_decode_horizon``):
        per-slot page-table rows + write positions replace the pow2
        cache-length group machinery entirely — there is ONE group
        regardless of request length. Pages for the whole horizon are
        pre-granted in one bulk ``KVPool.extend`` *before* the launch
        (:meth:`pre_extend_horizon`); the admission-time worst-case
        commitment guarantees the grant cannot fail, so the fused loop
        never pages mid-flight and the page table is constant across it.

    Dynamic decode-batch buckets work as in ``LocalExecutor``: occupied
    slots are stepped in the smallest bucket that holds them, padded with
    free slots whose page-table rows point at the pool's scratch page (so
    their garbage writes land in a write sink no request reads).

    Structural mode runs per-bucket compacted layer stacks over the SAME
    shared pool: groups are keyed by the exact parameter gather (as in
    ``LocalExecutor`` — bucket signatures share executables, never
    params), a bucket with L' retained layers reads/writes pool layers
    0..L'-1 of its request-exclusive pages (the pool stays full-depth, so
    spill/restore and admission accounting are mode-blind and
    conservative), and per-slot gates realize each request's exact mask
    inside its bucket. Structural buckets are always *gated* whole-layer
    buckets (``bucket_quant`` floors at "layer"): the paged decoder
    serves uniform all-attention layouts, so half-layer drops become
    gates — which is bitwise-identical to dropping them structurally.
    Uniform all-attention models only — ``LocalExecutor`` is the
    reference backend for everything else.

    ``kv_dtype`` accepts the canonical precision names (``fp32``/``bf16``/
    ``int8``/``fp8``) or a jnp dtype: quantized precisions store int8/fp8
    pages plus per-(page, kv-head) scale pools, quantize on every write
    seam (monolithic prefill, chunked prefill, horizon decode) and fuse
    dequant into the Pallas kernel / mirror it in the XLA gather.
    """

    paged = True

    def __init__(self, model, params, *, mode: str = "masked",
                 max_active: int = 8, kv_dtype=None,
                 decode_buckets: Sequence[int] = (1, 2, 4, 8),
                 bucket_quant: str = "none"):
        if mode not in ("masked", "structural"):
            raise ValueError(f"unknown mode {mode!r}")
        if bucket_quant not in ("none", "layer", "pow2"):
            raise ValueError(f"unknown bucket_quant {bucket_quant!r}; "
                             "expected none|layer|pow2")
        layout = decoder.default_layout(model.cfg)
        if not (len(layout) > 0
                and all(s.mixer == "attn" and s.ffn == layout[0].ffn
                        for s in layout)):
            raise NotImplementedError(
                "PagedExecutor serves uniform all-attention layouts; "
                f"{model.cfg.name!r} mixes "
                f"{sorted({str(s.mixer) for s in layout})} — use "
                "LocalExecutor (slot caches) for heterogeneous models")
        self.model = model
        self.mcfg = model.cfg
        self.params = params
        self.mode = mode
        # the paged decoder requires uniform layouts, so structural
        # buckets are always whole-layer gated buckets: "none" floors at
        # "layer" (bitwise-identical — half-layer drops run as 0-gates)
        if mode == "structural" and bucket_quant == "none":
            bucket_quant = "layer"
        self.bucket_quant = bucket_quant
        self.max_active = int(max_active)
        name, store, quantized, _ = resolve_kv_dtype(kv_dtype)
        self.kv_dtype_name = name            # canonical, None = model dtype
        self.kv_quantized = quantized
        self.kv_dtype = (store if store is not None
                         else model.cfg.jnp_dtype())   # page storage dtype
        self.decode_buckets = tuple(int(b) for b in decode_buckets or ())
        self.compile_events = 0
        self.launch_s = 0.0
        self.tracer = tracing.Recorder()
        self.pool = None               # bound per engine run
        # "masked" -> the single gated group; structural mode keys groups
        # by gather_key (exact parameter rows), as in LocalExecutor
        self._groups: Dict[Any, PagedGroup] = {}
        self._prefill_fns: Dict[Tuple, Any] = {}
        self._hfns: Dict[Tuple, Any] = {}
        self._decode_widths: set = set()    # (width, horizon) pairs
        # "pallas" routes decode through the paged flash-decode kernel on
        # TPU; elsewhere the XLA gather fallback is the fast path (the
        # kernel still runs in CI via interpret-mode equivalence tests)
        self._impl = ("pallas" if jax.default_backend() == "tpu" else "xla")

    # ------------------------------------------------------------- binding
    def page_phys_bytes(self, tokens_per_page: int) -> int:
        """Exact bytes of one physical page across all layers (K and V).

        Quantized pools charge the narrow storage width *plus* the page's
        per-(layer, kv-head) f32 scale rows — admission and the pool
        ledger see true bytes, so an int8 request admits ~2× the sequence
        (not exactly 4×: the scales claw a sliver back) under one budget."""
        cfg = self.mcfg
        itemsize = jnp.dtype(self.kv_dtype).itemsize
        n = (2 * cfg.n_layers * int(tokens_per_page) * cfg.n_kv_heads
             * cfg.dh * itemsize)
        if self.kv_quantized:
            n += 2 * cfg.n_layers * cfg.n_kv_heads * 4    # K + V scale rows
        return n

    def bind_pool(self, pool, max_len: int) -> None:
        """Attach this run's KVPool: materialize its page arrays (and, for
        quantized precisions, the scale pools) and size the page-table
        width for ``max_len``-token requests."""
        pool.allocate_physical(n_layers=self.mcfg.n_layers,
                               n_kv_heads=self.mcfg.n_kv_heads,
                               head_dim=self.mcfg.dh,
                               dtype=self.mcfg.jnp_dtype(),
                               kv_dtype=(self.kv_dtype_name
                                         or self.kv_dtype))
        self.pool = pool
        self.max_row_pages = -(-int(max_len) // pool.tokens_per_page)
        # groups reference the previous pool's scratch page/table geometry;
        # compiled executables stay (keys carry their shapes)
        self._groups.clear()

    def _pool_leaves(self) -> Dict[str, Any]:
        """The pool's device arrays as one pytree (pages + scales when
        quantized) — jitted calls donate and return the whole dict."""
        pools = {"k": self.pool.k_pages, "v": self.pool.v_pages}
        if self.kv_quantized:
            pools["ks"] = self.pool.k_scales
            pools["vs"] = self.pool.v_scales
        return pools

    def _store_leaves(self, pools: Dict[str, Any]) -> None:
        self.pool.k_pages = pools["k"]
        self.pool.v_pages = pools["v"]
        if self.kv_quantized:
            self.pool.k_scales = pools["ks"]
            self.pool.v_scales = pools["vs"]

    # ------------------------------------------------------------ capacity
    def _invalidate(self) -> None:
        """Unified invalidation (see ``LocalExecutor._invalidate``):
        groups and every compiled-executable cache drop together."""
        self._groups.clear()
        self._prefill_fns.clear()
        self._hfns.clear()
        self._decode_widths.clear()

    def set_max_active(self, n_slots: int) -> None:
        if int(n_slots) == self.max_active:
            return
        self.max_active = int(n_slots)
        self._invalidate()

    def drop_groups(self) -> None:
        self._invalidate()

    # -------------------------------------------------------------- groups
    def groups(self) -> List[PagedGroup]:
        return list(self._groups.values())

    def group_for(self, mask: np.ndarray, cache_len: int) -> PagedGroup:
        """Masked mode: ONE group hosts every request — pages make cache
        length a per-slot property, so there is nothing to key groups by.
        Structural mode: one group per parameter gather (quantized bucket),
        all decoding over the same shared pool."""
        if self.pool is None:
            raise RuntimeError("PagedExecutor has no bound pool — the "
                               "engine calls bind_pool() per run")
        if self.mode == "masked":
            group = self._groups.get("masked")
            if group is None:
                group = self._groups["masked"] = PagedGroup(
                    self.mcfg, self.max_active, self.max_row_pages,
                    self.pool.scratch_page)
            return group
        qmask = masks_lib.quantize_mask(self.mcfg, mask, self.bucket_quant)
        rkey = masks_lib.gather_key(self.mcfg, qmask)
        group = self._groups.get(rkey)
        if group is None:
            small, layout = masks_lib.compact_params(self.params, self.mcfg,
                                                     qmask)
            # mask: the exact MINTING mask (sticky-affinity metadata, see
            # LocalExecutor.group_for) — per-request masks ride the gates
            group = self._groups[rkey] = PagedGroup(
                self.mcfg, self.max_active, self.max_row_pages,
                self.pool.scratch_page,
                key=masks_lib.bucket_key(self.mcfg, qmask),
                mask=np.array(mask, copy=True), layout=layout,
                params=small,
                gate_rows=masks_lib.keep_rows(self.mcfg, qmask))
        return group

    def _group_params(self, group: PagedGroup):
        return group.params if group.params is not None else self.params

    # ------------------------------------------------------------- prefill
    def _prefill_fn(self, group: PagedGroup, b: int, S: int, npg: int):
        key = (group.key, b, S, npg)
        if key not in self._prefill_fns:
            cfg = self.mcfg
            pt = self.pool.tokens_per_page
            layout = group.layout
            Lp = len(layout) if layout is not None else cfg.n_layers
            quantized = self.kv_quantized
            # quantized pools prefill at model width inside the jit and
            # page-quantize during the scatter: every granted page is
            # fresh (offset 0), so scales are set, never floored
            cache_dtype = None if quantized else self.kv_dtype

            # a compacted bucket prefills an Lp-layer cache and scatters
            # into pool layers [0, Lp) of its granted pages — pages are
            # request-exclusive, so the untouched upper layers are never
            # read. Same-signature buckets share this executable (params
            # are jit arguments; equal-signature layouts are identical).
            @functools.partial(jax.jit, donate_argnums=(4,))
            def rap_paged_prefill(p, tokens, gm, gf, pools, rows):
                logits, cache = decoder.prefill(
                    p, cfg, tokens, npg * pt,
                    gates={"mixer": gm, "ffn": gf}, layout=layout,
                    kv_dtype=cache_dtype)
                kp, vp = pools["k"], pools["v"]
                # [Lp, b, S, K, D] → head-major pages [Lp, b, npg, K, pt, D]
                K, D = kp.shape[2], kp.shape[4]
                k, v = (jnp.swapaxes(cache["attn"][n].reshape(
                    Lp, b, npg, pt, K, D), 3, 4) for n in ("k", "v"))
                pools = dict(pools)
                if quantized:
                    qk, sk = attention.page_quant(
                        k.astype(jnp.float32), kp.dtype)
                    qv, sv = attention.page_quant(
                        v.astype(jnp.float32), vp.dtype)
                    pools["k"] = kp.at[:Lp, rows].set(qk)
                    pools["v"] = vp.at[:Lp, rows].set(qv)
                    pools["ks"] = pools["ks"].at[:Lp, rows].set(sk)
                    pools["vs"] = pools["vs"].at[:Lp, rows].set(sv)
                else:
                    pools["k"] = kp.at[:Lp, rows].set(k.astype(kp.dtype))
                    pools["v"] = vp.at[:Lp, rows].set(v.astype(vp.dtype))
                return logits, pools

            self._prefill_fns[key] = rap_paged_prefill
            self.compile_events += 1
        return self._prefill_fns[key]

    def prefill_into(self, group: PagedGroup, slots: List[int], rid: str,
                     prompt: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Prefill the request, writing its KV straight into the pages the
        pool granted at admission; seat its rows in ``slots``."""
        b, S = prompt.shape
        rows = self.pool.row_pages(rid)            # [b][npg] page ids
        npg = len(rows[0])
        rows_np = np.asarray(rows, np.int32)
        c0 = self.compile_events
        fn = self._prefill_fn(group, b, S, npg)
        # one gate-column stack serves both the jitted call and the
        # group's resident gate columns
        cols = _gate_cols(mask, group.gate_rows)
        with self.tracer.span("rap.dispatch") as sp:
            _mark_compile(sp, self, c0, f"prefill:{group.key}:{b}x{S}")
            logits, pools = fn(self._group_params(group),
                               jnp.asarray(prompt, jnp.int32),
                               cols[0], cols[1], self._pool_leaves(),
                               jnp.asarray(rows_np))
            self._store_leaves(pools)
            first = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        self.launch_s += sp.seconds
        group.place(rid, slots, rows_np, S, first, cols[0], cols[1])
        return first

    # ----------------------------------------------------- chunked prefill
    def supports_chunked_prefill(self, group: PagedGroup) -> bool:
        # the constructor pins uniform all-attention models, and
        # structural buckets are whole-layer (still uniform) — exactly
        # what the paged chunk path serves (quantized pools requantize
        # the chunk's touched pages in the same call)
        return True

    def _chunk_fn(self, group: PagedGroup, b: int, C: int):
        """Jitted paged one-chunk prefill, keyed by chunk width (offset is
        traced): the chunk's K/V scatter straight into the granted pages
        (pool arrays donated through the call, as in monolithic paged
        prefill)."""
        scratch = self.pool.scratch_page
        key = ("chunk", group.key, b, C, scratch)
        if key not in self._prefill_fns:
            cfg = self.mcfg
            layout = group.layout

            @functools.partial(jax.jit, donate_argnums=(1,))
            def rap_paged_prefill_chunk(p, pools, table, tokens, start, gm,
                                        gf):
                logits, pools = decoder.paged_prefill_chunk(
                    p, cfg, pools, table, tokens, start,
                    scratch_page=scratch,
                    gates={"mixer": gm, "ffn": gf}, layout=layout)
                return logits, pools

            self._prefill_fns[key] = rap_paged_prefill_chunk
            self.compile_events += 1
        return self._prefill_fns[key]

    def prefill_begin(self, group: PagedGroup, slots: List[int], rid: str,
                      prompt: np.ndarray, mask: np.ndarray, *,
                      max_chunk: int) -> _PrefillTask:
        """Open a chunked paged prefill. The pool allocation (made at
        admission) covers only the first chunk; each later chunk extends
        the request's pages just before it runs, so a long prompt's pages
        materialize incrementally instead of all up front."""
        prompt = np.asarray(prompt, np.int32)
        b, S = prompt.shape
        group.reserved.update(slots)
        cols = _gate_cols(mask, group.gate_rows)
        return _PrefillTask(group=group, slots=list(slots), rid=rid,
                            prompt=prompt, mask=mask,
                            gates={"mixer": cols[0], "ffn": cols[1]},
                            widths=chunk_widths(S, max_chunk))

    def prefill_step(self, task: _PrefillTask) -> Optional[np.ndarray]:
        group, rid = task.group, task.rid
        b, S = task.prompt.shape
        c = task.widths[task.step]
        if task.pos > 0:
            # the admission alloc covered chunk 0; grant this chunk's pages
            self.pool.extend(rid, c)
        rows = self.pool.row_pages(rid)
        table = np.full((b, self.max_row_pages), self.pool.scratch_page,
                        np.int32)
        table[:, :len(rows[0])] = np.asarray(rows, np.int32)
        c0 = self.compile_events
        fn = self._chunk_fn(group, b, c)
        with self.tracer.span("rap.dispatch") as sp:
            _mark_compile(sp, self, c0, f"chunk:{group.key}:{b}x{c}")
            logits, pools = fn(
                self._group_params(group), self._pool_leaves(),
                jnp.asarray(table),
                jnp.asarray(task.prompt[:, task.pos:task.pos + c], jnp.int32),
                np.int32(task.pos), task.gates["mixer"], task.gates["ffn"])
            self._store_leaves(pools)
            task.pos += c
            task.step += 1
            first = (np.asarray(jnp.argmax(logits, axis=-1)
                                .astype(jnp.int32)) if task.done else None)
        self.launch_s += sp.seconds
        if first is None:
            return None
        rows_np = np.asarray(self.pool.row_pages(rid), np.int32)
        group.place(rid, task.slots, rows_np, S, first,
                    np.asarray(task.gates["mixer"]),
                    np.asarray(task.gates["ffn"]))
        return first

    # ---------------------------------------------------- preemption seam
    def spill_state(self, group: PagedGroup, slots: List[int]) -> dict:
        """Paged decode state outside the pool is tiny: the write position
        and the per-row seed token (the page contents travel with
        ``KVPool.spill``)."""
        return {"pos": int(group.pos[slots[0]]),
                "first": group.tokens[np.asarray(slots)].copy()}

    def restore_state(self, group: PagedGroup, slots: List[int], rid: str,
                      state: dict, mask: Optional[np.ndarray],
                      rows: Optional[List[List[int]]] = None) -> None:
        """Reseat with the re-granted page ids (``KVPool.restore``'s rows
        — same per-row layout, contents written back bitwise): one fused
        placement update rebuilds table/pos/tokens/gates exactly as an
        unpreempted resident would hold them."""
        if rows is None:
            rows = self.pool.row_pages(rid)
        cols = _gate_cols(mask, group.gate_rows)
        group.place(rid, list(slots), np.asarray(rows, np.int32),
                    state["pos"], state["first"], cols[0], cols[1])

    # -------------------------------------------------------------- decode
    def _decode_batch(self, group: PagedGroup) -> List[int]:
        idx = _bucket_batch(group.occupied_slots(), group.free_slots(),
                            group.n_slots, self.decode_buckets)
        # full width: every slot steps (free rows write the scratch page)
        return idx if idx is not None else list(range(group.n_slots))

    def _horizon_fn(self, group: PagedGroup, horizon: int, bucketed: bool):
        """Jitted fused paged horizon per (bucket signature, H, bucketed).
        The bucketed variant gathers the stepped rows from the full-width
        resident state and scatters positions/tokens back *inside* the
        compiled call (eager indexing would upload an index-normalization
        scalar per launch — the transfer-guard test forbids it)."""
        h = int(horizon)
        key = (group.key, h, bool(bucketed))
        if key not in self._hfns:
            cfg, impl = self.mcfg, self._impl
            layout = group.layout

            if not bucketed:
                @functools.partial(jax.jit, donate_argnums=(1, 3, 4))
                def rap_paged_decode_horizon(p, pools, table, pos, tok,
                                             gates):
                    toks, pools, pos_out = decoder.paged_decode_horizon(
                        p, cfg, pools, table, pos,
                        tok[:, None], h,
                        gates={"mixer": gates[0], "ffn": gates[1]},
                        impl=impl, layout=layout)
                    pos = _hold_free(pos, pos_out)
                    return toks, pools, pos, toks[:, -1]
                fn = rap_paged_decode_horizon
            else:
                @functools.partial(jax.jit, donate_argnums=(1, 3, 4))
                def rap_paged_decode_horizon_bucketed(p, pools, table, pos,
                                                      tok, gates, iidx):
                    g = gates[:, :, iidx]
                    toks, pools, pos_out = decoder.paged_decode_horizon(
                        p, cfg, pools, table[iidx], pos[iidx],
                        tok[iidx][:, None], h,
                        gates={"mixer": g[0], "ffn": g[1]}, impl=impl,
                        layout=layout)
                    pos = pos.at[iidx].set(_hold_free(pos[iidx], pos_out))
                    tok = tok.at[iidx].set(toks[:, -1])
                    return toks, pools, pos, tok
                fn = rap_paged_decode_horizon_bucketed

            self._hfns[key] = fn
        return self._hfns[key]

    def pre_extend_horizon(self, group: PagedGroup, horizon: int) -> int:
        """Pre-grant every page the coming horizon can touch: ONE bulk
        ``KVPool.extend`` per resident request (clamped to its admission
        commitment — ``alloc_tokens``' worst-case reservation guarantees
        the grant can't fail), folding any new page ids into the device
        page table in one scatter. Positions past the commitment (a
        request over-generating inside its final horizon) resolve to the
        scratch page / its own last page and are truncated by the engine.
        Returns the number of pages granted (0 in the steady state)."""
        occ = group.occupied_slots()
        entries: List[Tuple[int, int, int]] = []
        seen = set()
        for s in occ:
            rid = group.occupants[s]
            if rid in seen:
                continue
            seen.add(rid)
            n = min(int(horizon), self.pool.remaining_commitment(rid))
            if n <= 0:
                continue
            # pages currently held per row (alloc/extend keep rows at
            # exactly ceil(seq/page) — no need to copy the id lists)
            have = self.pool.pages_per_row(self.pool.seq_tokens(rid))
            new_rows = self.pool.extend(rid, n)    # [batch][granted pages]
            if not any(new_rows):
                continue
            rid_slots = [t for t in occ if group.occupants[t] == rid]
            for i, t in enumerate(rid_slots):
                for j, page in enumerate(new_rows[i]):
                    entries.append((t, have + j, page))
        group.grant_pages(entries)
        return len(entries)

    def launch_horizon(self, group: PagedGroup,
                       horizon: int) -> Tuple[Any, List[int], bool]:
        """Device phase of a fused paged horizon: gather the stepped
        slots' resident state, launch ONE compiled ``lax.scan`` that
        advances them ``horizon`` tokens against the page pools, and fold
        positions/tokens back. Pages must already be granted
        (:meth:`pre_extend_horizon`). Returns (device toks [width, H],
        stepped slot ids, new-compile flag); zero host↔device transfers
        once warm — the caller's single ``np.asarray`` is the only sync."""
        idx = self._decode_batch(group)
        width = len(idx)
        key = (group.key, width, int(horizon))
        new = key not in self._decode_widths
        self._decode_widths.add(key)
        if new:
            self.compile_events += 1
        full = width == group.n_slots
        fn = self._horizon_fn(group, horizon, bucketed=not full)
        args = (self._group_params(group), self._pool_leaves(),
                group.table_dev, group.pos_dev, group.tokens_dev,
                group.gates_dev)
        if not full:
            args += (group.iidx(idx),)
        toks, pools, pos, tok = fn(*args)
        self._store_leaves(pools)
        group.pos_dev = pos
        group.tokens_dev = tok
        return toks, idx, new

    def decode_launch(self, group: PagedGroup,
                      horizon: int) -> _InFlightHorizon:
        """Bulk page pre-grant + one fused launch, no sync: the host is
        free to schedule/admit while the scan runs on device. Records the
        launch's page walk (:class:`~repro.runtime.tracing.Launch`)."""
        tr, h = self.tracer, int(horizon)
        with tr.span("rap.decode_launch", horizon=h) as dl:
            with tr.span("rap.page_grant"):
                self.pre_extend_horizon(group, h)
            with tr.span("rap.dispatch") as sp:
                toks_dev, idx, new = self.launch_horizon(group, h)
                if new:
                    sp.set(compiled=1,
                           key=f"decode:{group.key}:{len(idx)}x{h}")
            self.launch_s += sp.seconds
            dl.set(width=len(idx))
            occupants = [group.occupants[s] for s in idx]
            rows = [s for s, o in zip(idx, occupants) if o is not None]
            pt = self.pool.tokens_per_page
            useful = sum(
                -(-min(int(group.pos[s]) + h,
                       self.pool.seq_tokens(group.occupants[s])) // pt)
                for s in rows)
            _, _, kv_heads, _, dh = self.pool.k_pages.shape
            ppb = paged_kernel.pages_per_block(
                kv_heads, pt, dh, self.pool.k_pages.dtype.itemsize,
                self.max_row_pages)
            # each stepped row's length at the horizon's last step (free
            # rows hold position 0, see _hold_free)
            walked = paged_kernel.pages_walked(
                [int(group.pos[s]) + h for s in idx], pt,
                self.max_row_pages, ppb)
            tr.launch(t=sp.end, horizon=h, rows_stepped=len(idx),
                      rows_occupied=len(rows), pages_walked=walked,
                      pages_with_tokens=useful)
        return _InFlightHorizon(group=group, horizon=h, toks_dev=toks_dev,
                                idx=idx, occupants=occupants, new=new)

    def decode_finish(self,
                      launch: _InFlightHorizon) -> Tuple[np.ndarray, bool]:
        group, h = launch.group, launch.horizon
        nxt = self._readback(launch)
        out = np.zeros((group.n_slots, h), np.int32)
        for j, s in enumerate(launch.idx):
            # fold back only slots whose occupant is unchanged since
            # launch: overlapped host admission may have re-seated a slot
            # that was free padding when the scan dispatched
            if (launch.occupants[j] is not None
                    and group.occupants[s] == launch.occupants[j]):
                out[s] = nxt[j]
                group.tokens[s] = nxt[j, -1]
                group.pos[s] += h
        return out, launch.new

    def decode_horizon(self, group: PagedGroup,
                       horizon: int) -> Tuple[np.ndarray, bool]:
        """Advance every occupied slot ``horizon`` tokens: bulk page
        pre-grant, one fused launch, one [width, horizon] read-back."""
        return self.decode_finish(self.decode_launch(group, horizon))

    # ---------------------------------------------------------- utilization
    def kv_utilization(self) -> Tuple[float, float]:
        """used = tokens actually written by resident requests; physical =
        bytes of the pages they hold. Waste is bounded by one partial page
        per row plus the pre-granted horizon tail — the whole point of
        paging."""
        if self.pool is None or not self._groups:
            return 0.0, 0.0
        pt = self.pool.tokens_per_page
        tok_bytes = self.pool.page_bytes / pt
        used = 0.0
        for group in self._groups.values():
            for s in group.occupied_slots():
                rid = group.occupants[s]
                # clamp to the granted backing: a request over-generating
                # in its final horizon advances pos past its page-backed
                # tokens
                used += min(int(group.pos[s]),
                            self.pool.seq_tokens(rid)) * tok_bytes
        return used, self.pool.bytes_reserved

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict[str, int]:
        return {
            "groups": len(self._groups),
            "structural_buckets": len({k for k in self._groups
                                       if k != "masked"}),
            "bucket_signatures": len({g.key for g in self._groups.values()
                                      if g.key != "paged"}),
            "prefill_executables": len(self._prefill_fns),
            "decode_widths": len(self._decode_widths),
            "compile_events": self.compile_events,
        }


# ----------------------------------------------------------------- sharded
class ShardedSlotGroup(SlotGroup):
    """A :class:`SlotGroup` whose decode state is **mesh-resident**
    (DESIGN.md §7 "Sharded serving").

    The slot axis is the mesh's data-parallel dimension: the KV cache is
    sharded over slots ("data") and KV heads ("model"), positions and
    seed tokens over slots, gates replicated — the partition rules from
    ``repro.parallel.sharding.serve_state_pspecs``, with per-axis
    divisibility fallback so smoke shapes degrade to replication instead
    of GSPMD errors. The fused placement update and the horizon scan are
    re-jitted with explicit ``out_shardings`` pinned to that layout, so
    placement writes only the placed columns of the *sharded* arrays and
    a warmed horizon launch never re-shards (or re-uploads) the resident
    state. Groups always step full width — the slot axis IS the mesh
    axis, so there is no bucketed gather variant (``ShardedExecutor``
    passes ``decode_buckets=()``)."""

    def __init__(self, key, params, layout, cfg_model, n_slots: int,
                 cache_len: int, kv_dtype, gated: bool, mesh,
                 mask: Optional[np.ndarray] = None):
        if not gated:
            raise NotImplementedError(
                "sharded slot groups are gated (masked mode) only — "
                "structural sharded buckets (per-bucket compacted params "
                "re-placed on the mesh) are a ROADMAP item")
        super().__init__(key, params, layout, cfg_model, n_slots, cache_len,
                         kv_dtype, gated, mask=mask)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.parallel import (serve_slot_pspec, serve_state_pspecs,
                                    shardings_for)
        self.mesh = mesh
        self._rep = NamedSharding(mesh, P())
        self._cache_sh = shardings_for(
            serve_state_pspecs(self.cache, mesh, n_slots=n_slots), mesh)
        self.cache = jax.device_put(self.cache, self._cache_sh)
        self._tok_sh = NamedSharding(mesh,
                                     serve_slot_pspec(self.tokens.shape,
                                                      mesh))
        self.tokens = jax.device_put(self.tokens, self._tok_sh)
        # gates are replicated: [2, L, n_slots] is tiny, placement updates
        # single columns, and every TP shard reads every layer's gate row
        self._gates_dev = jax.device_put(self._gates_dev, self._rep)
        self._place_fns: Dict[bool, Any] = {}

    def _iidx(self, idx: List[int]):
        key = tuple(idx)
        dev = self._iidx_cache.get(key)
        if dev is None:
            if len(self._iidx_cache) >= _IIDX_CACHE_CAP:
                self._iidx_cache.pop(next(iter(self._iidx_cache)))
            dev = jax.device_put(np.asarray(idx, np.int32), self._rep)
            self._iidx_cache[key] = dev
        return dev

    def _place_fn(self, with_gates: bool):
        fn = self._place_fns.get(with_gates)
        if fn is None:
            fn = jax.jit(rap_slot_place, donate_argnums=(0, 1, 7),
                         out_shardings=(self._cache_sh, self._tok_sh,
                                        self._rep if with_gates else None))
            self._place_fns[with_gates] = fn
        return fn

    def _horizon_fn(self, horizon: int, bucketed: bool):
        """Fused horizon lowered under the mesh: the SAME full-width
        horizon body as the local path (``_full_width_horizon``), jitted
        with the resident state's shardings pinned on the outputs (the
        inputs carry theirs), so ONE mesh-partitioned ``lax.scan``
        executable advances every slot H tokens and pays its collectives
        once per horizon. Tokens come back replicated — the macro-tick's
        single read-back."""
        if bucketed:
            raise NotImplementedError(
                "sharded slot groups always step full width — the slot "
                "axis is the mesh's DP dimension (ShardedExecutor runs "
                "with decode_buckets=())")
        h = int(horizon)
        key = (h, False)
        if key not in self._hfns:
            self._hfns[key] = jax.jit(
                self._full_width_horizon(h), donate_argnums=(1, 2),
                out_shardings=(self._rep, self._cache_sh, self._tok_sh))
        return self._hfns[key]


class ShardedExecutor(LocalExecutor):
    """Mesh-resident slot-group execution (DESIGN.md §7 "Sharded serving").

    Owns both mesh roles of the serving stack:

      * **placement / lowering** — parameters placed under the production
        partition rules (``repro.parallel.sharding.param_pspecs``: TP over
        feature dims, optional ZeRO-3 over "data") and a sharded decode
        step lowered for HLO cost / memory / collective analysis
        (:meth:`lower_decode`, the path ``launch/rap_sweep.py`` drives);
      * **the slot-batched serve path** — groups are
        :class:`ShardedSlotGroup`: decode state lives sharded on the mesh
        (KV over slots=DP and heads=TP, gates replicated), placement /
        eviction stay fused column updates of the sharded arrays, and
        each engine macro-tick launches ONE mesh-lowered horizon scan, so
        TP collectives are paid once per H tokens instead of per token
        (the PR 4 horizon decode is what makes sharded ticks affordable).

    Masked mode only — one gated group serves every keep-mask, which is
    exactly what keeps the sharded executable set small. Structural
    sharded buckets are a ROADMAP item; use ``LocalExecutor`` for
    structural serving. Works on CPU via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
    multi-device CI job) as well as on real accelerator meshes.
    """

    def __init__(self, model, mesh, *, params=None, fsdp: bool = False,
                 shard_seq: bool = False, kv_int8: bool = False,
                 mode: str = "masked", max_active: int = 8, kv_dtype=None):
        if mode != "masked":
            raise NotImplementedError(
                f"sharded serving is masked-mode only (got {mode!r}); "
                "structural sharded buckets are a ROADMAP item — use "
                "LocalExecutor for structural serving")
        self.mesh = mesh
        self.policy = {"fsdp": bool(fsdp), "shard_seq": bool(shard_seq),
                       "kv_int8": bool(kv_int8)}
        self.model = model          # place_params resolves shapes via model
        placed = self.place_params(params) if params is not None else None
        # decode_buckets=(): sharded groups step full width — the slot
        # axis is the mesh's DP dimension, and a bucketed gather would
        # change the sharded state shape per occupancy pattern
        super().__init__(model, placed, mode="masked",
                         max_active=max_active, kv_dtype=kv_dtype,
                         decode_buckets=())

    # ----------------------------------------------------------- placement
    def param_shardings(self):
        from repro.parallel import param_pspecs, shardings_for
        shapes = jax.eval_shape(lambda: self.model.init(jax.random.key(0)))
        return shardings_for(param_pspecs(shapes, self.mesh,
                                          fsdp=self.policy["fsdp"]),
                             self.mesh)

    def place_params(self, params):
        """Place a params pytree on the mesh under the production rules."""
        return jax.device_put(params, self.param_shardings())

    def lower_decode(self, shape):
        """Lower one sharded fused decode step for ``shape`` (a
        ``repro.configs`` request shape) and return the ``Lowered`` —
        callers compile it for HLO cost / memory / collective analysis."""
        from repro.parallel import (batch_pspecs, cache_pspecs, param_pspecs,
                                    shardings_for)
        from repro.parallel import activation as act
        from repro.runtime import steps as steps_lib
        model, mesh, policy = self.model, self.mesh, self.policy
        with act.use(mesh, shard_seq=policy["shard_seq"],
                     fsdp=policy["fsdp"]):
            params_shape = jax.eval_shape(
                lambda: model.init(jax.random.key(0)))
            psh = shardings_for(param_pspecs(params_shape, mesh,
                                             fsdp=policy["fsdp"]), mesh)
            specs = model.input_specs(shape)
            bsh = shardings_for(batch_pspecs(specs, mesh), mesh)
            kv_dtype = jnp.int8 if policy["kv_int8"] else None
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         kv_dtype=kv_dtype))
            csh = shardings_for(
                cache_pspecs(cache_shape, mesh, batch=shape.global_batch,
                             shard_seq=policy["shard_seq"]), mesh)
            fn = steps_lib.make_decode_step(model)
            jfn = jax.jit(fn, in_shardings=(psh, csh, bsh["tokens"]),
                          out_shardings=(None, csh), donate_argnums=(1,))
            return jfn.lower(params_shape, cache_shape, specs["tokens"])

    # ------------------------------------------------------------ serve API
    def group_for(self, mask: np.ndarray, cache_len: int) -> SlotGroup:
        """One gated mesh-resident group per cache length (masked mode:
        every keep-mask shares it, exactly as on the local path)."""
        if self.params is None:
            raise RuntimeError(
                "ShardedExecutor has no params — construct with params= "
                "to serve (mesh cost analysis via lower_decode() does not "
                "need them)")
        gkey = ("masked", cache_len)
        if gkey not in self._groups:
            self._groups[gkey] = ShardedSlotGroup(
                "masked", self.params, None, self.mcfg, self.max_active,
                cache_len, self.kv_dtype, gated=True, mesh=self.mesh)
        return self._groups[gkey]

    # --------------------------------------------------------------- stats
    def stats(self) -> Dict[str, int]:
        s = super().stats()
        s["mesh_devices"] = int(self.mesh.size)
        return s
