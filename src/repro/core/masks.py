"""Block masks ↔ gates ↔ structural compaction.

A *mask* is a boolean [2L] vector (True = keep), indexed per
``repro.core.memory``. Two execution forms:

* masked mode   — ``mask_to_gates`` produces the runtime 0/1 gate inputs for
                  the single compiled executable (no memory savings);
* structural    — ``compact_params`` gathers the per-kind parameter stacks
                  along the layer axis, yielding genuinely smaller params, a
                  new layout, and a smaller KV cache. Executables are cached
                  per ``bucket_key`` (the retained-layout signature), vLLM
                  shape-bucket style.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.decoder import LayerSlot, default_layout, layout_counts


def full_mask(n_layers: int) -> np.ndarray:
    return np.ones(2 * n_layers, bool)


def mask_to_gates(mask) -> Dict[str, jnp.ndarray]:
    m = jnp.asarray(mask)
    L = m.shape[0] // 2
    return {"mixer": m[:L].astype(jnp.float32),
            "ffn": m[L:].astype(jnp.float32)}


def remove_block(mask: np.ndarray, block: int) -> np.ndarray:
    out = np.array(mask, copy=True)
    out[block] = False
    return out


def active_blocks(mask: np.ndarray) -> np.ndarray:
    return np.nonzero(np.asarray(mask))[0]


def compact_layout(cfg, mask: np.ndarray) -> Tuple[Tuple[LayerSlot, ...], Dict]:
    """Retained layout: drop layers where both blocks are pruned; keep gate
    info for half-pruned layers. Returns (layout, per-kind gather indices)."""
    base = default_layout(cfg)
    L = len(base)
    m = np.asarray(mask)
    keep_rows = [i for i in range(L) if m[i] or m[L + i]]
    gather: Dict[str, list] = {}
    slots = []
    counters: Dict[str, int] = {}
    for i in keep_rows:
        s = base[i]
        mixer = s.mixer if m[i] else None
        f = s.ffn if m[L + i] else None
        mi = fi = 0
        if mixer is not None:
            mk = "attn" if mixer == "local_attn" else mixer
            gather.setdefault(mk, []).append(s.mixer_idx)
            mi = counters.get(mk, 0)
            counters[mk] = mi + 1
        if f is not None:
            gather.setdefault(f, []).append(s.ffn_idx)
            fi = counters.get(f, 0)
            counters[f] = fi + 1
        slots.append(LayerSlot(mixer, mi, f, fi))
    return tuple(slots), gather


def compact_params(params: dict, cfg, mask: np.ndarray):
    """Gather stacks per the mask. Returns (small_params, layout).

    Masking became structure: the compacted stacks hold only retained
    blocks, so callers run forward/decode with ``layout`` and no gates
    (or all-ones gates over the compacted layout).

    A kind whose gather keeps every row in order (the full mask keeps all
    of them) returns the dense stack itself, not a copy: a full-depth
    bucket must not hold a second set of weights beside ``params``.
    """
    layout, gather = compact_layout(cfg, mask)
    new_stacks = {}
    for kind, idxs in gather.items():
        stack = params["stacks"][kind]
        if list(idxs) == list(range(jax.tree.leaves(stack)[0].shape[0])):
            new_stacks[kind] = stack
            continue
        idx = jnp.asarray(idxs, jnp.int32)
        new_stacks[kind] = jax.tree.map(lambda x: jnp.take(x, idx, axis=0),
                                        stack)
    small = dict(params)
    small["stacks"] = new_stacks
    return small, layout


def bucket_key(cfg, mask: np.ndarray) -> Tuple:
    """Executable-cache key: the retained layout signature (kinds sequence).

    Whole-layer drops on uniform architectures collapse by count — any mask
    removing k full layers maps to the same (L-k)-layer signature, so those
    masks share one compiled program (vLLM-shape-bucket-style). Half-layer
    drops keep their position (the block sequence differs structurally).
    """
    layout, _ = compact_layout(cfg, mask)
    return tuple((s.mixer, s.ffn) for s in layout)


def gather_key(cfg, mask: np.ndarray) -> Tuple:
    """Identity key for the *exact* compacted parameter stack.

    ``bucket_key`` deliberately collapses any k whole-layer drops to one
    (L-k)-layer signature so those masks share a compiled executable —
    but masks dropping *different* layers gather *different* rows of the
    parameter stacks. Resident compacted params (and the slot groups
    holding them) must therefore be keyed on the gather indices, never on
    the signature alone (see DESIGN.md §9 on the aliasing bug this fixes).
    """
    _, gather = compact_layout(cfg, mask)
    return tuple(sorted((kind, tuple(idxs)) for kind, idxs in gather.items()))


def keep_rows(cfg, mask: np.ndarray) -> np.ndarray:
    """Original layer indices retained by ``mask`` (either block kept)."""
    L = cfg.n_layers
    m = np.asarray(mask)
    return np.asarray([i for i in range(L) if m[i] or m[L + i]], np.int64)


def quantize_mask(cfg, mask: np.ndarray, mode: str) -> np.ndarray:
    """Snap a mask onto a bucket-shape ladder; returns the *bucket* mask.

    An adaptive policy emits a stream of distinct masks; compiling one
    structural executable per mask is unbounded. Quantization rounds the
    retained-layer count UP onto a small ladder and keeps *whole layers*
    (both blocks) at every retained row, so the request's exact mask is
    realized as per-slot 0/1 gates inside the bucket. Gating a block off
    is bitwise-identical to dropping it structurally (``h + 0*out == h``
    for finite outputs, and ``1.0*out == out`` exactly), so bucket streams
    match pure-structural streams token for token.

    Modes:
      * ``none``  — identity; each exact mask compiles its own bucket.
      * ``layer`` — whole-layer bucket over the exact retained-row set
                    (half-layer drops become gates; row sets still vary).
      * ``pow2``  — like ``layer`` but the row count is rounded up to the
                    next power of two (extra rows realized from the
                    lowest-indexed fully-dropped layers, gated off), so at
                    most ceil(log2 L)+1 compiled families exist.
    """
    if mode == "none":
        return np.array(mask, copy=True)
    if mode not in ("layer", "pow2"):
        raise ValueError(f"unknown bucket_quant mode {mode!r}; "
                         "expected none|layer|pow2")
    L = cfg.n_layers
    m = np.asarray(mask)
    rows = [i for i in range(L) if m[i] or m[L + i]]
    k = max(len(rows), 1)
    if mode == "pow2":
        target = min(1 << (k - 1).bit_length(), L)
        extras = [i for i in range(L) if not (m[i] or m[L + i])]
        rows = sorted(rows + extras[: target - len(rows)])
    elif not rows:
        rows = [0]
    out = np.zeros(2 * L, bool)
    for i in rows:
        out[i] = out[L + i] = True
    return out


def mask_param_fraction(cfg, mask: np.ndarray) -> float:
    """Fraction of block params retained (excludes embeddings) — Table 4."""
    mix, ffn = cfg.block_param_counts()
    L = cfg.n_layers
    m = np.asarray(mask)
    tot = float(np.sum(mix) + np.sum(ffn))
    kept = float(np.asarray(mix) @ m[:L] + np.asarray(ffn) @ m[L:])
    return kept / max(tot, 1.0)
