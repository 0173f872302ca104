"""Generic decoder-only LM supporting every assigned family.

Parameters live in per-kind *stacks* (leading axis = number of layers of that
kind, MaxText-style). Uniform architectures run as ``lax.scan`` over the
stack; heterogeneous ones (Griffin's rglru/rglru/attn pattern) unroll a
Python loop with static per-layer indices into the stacks.

RAP hooks:
  * ``gates`` — dict {'mixer': f32[L], 'ffn': f32[L]} of 0/1 runtime gates.
    Masked-mode pruning multiplies each residual branch; one executable serves
    every pruning pattern (no memory savings — used by GSI scoring).
  * structural compaction (see ``repro.core.masks``) gathers the stacks along
    the layer axis, producing genuinely smaller params + KV cache.
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, ffn as ffn_mod, layers, moe as moe_mod
from repro.models import rglru as rglru_mod, ssm as ssm_mod
from repro.parallel import activation as act


class LayerSlot(NamedTuple):
    mixer: Optional[str]   # attn|local_attn|rglru|ssd|None
    mixer_idx: int         # index into the kind's stack
    ffn: Optional[str]     # dense|moe|None
    ffn_idx: int


def default_layout(cfg) -> Tuple[LayerSlot, ...]:
    slots = []
    counts: Dict[str, int] = {}
    for mixer, f in cfg.layer_specs():
        mk = "attn" if mixer == "local_attn" else mixer  # shared param stack
        mi = counts.get(mk, 0)
        counts[mk] = mi + 1
        if f == "none":
            fk, fi = None, 0
        else:
            fi = counts.get(f, 0)
            counts[f] = fi + 1
            fk = f
        slots.append(LayerSlot(mixer, mi, fk, fi))
    return tuple(slots)


def layout_counts(layout) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for s in layout:
        if s.mixer is not None:
            mk = "attn" if s.mixer == "local_attn" else s.mixer
            counts[mk] = max(counts.get(mk, 0), s.mixer_idx + 1)
        if s.ffn is not None:
            counts[s.ffn] = max(counts.get(s.ffn, 0), s.ffn_idx + 1)
    return counts


# --------------------------------------------------------------------- params
_MIXER_INIT = {
    "attn": attention.init_attn_params,
    "rglru": rglru_mod.init_rglru_params,
    "ssd": ssm_mod.init_ssd_params,
}
_FFN_INIT = {
    "dense": ffn_mod.init_ffn_params,
    "moe": moe_mod.init_moe_params,
}


def _stack_init(rng, n: int, init_fn, cfg):
    """``n`` layers' params stacked on a leading axis, layer ``i`` drawn
    from the ``i``-th split of ``rng``. One vmapped draw builds each
    stacked leaf directly (no per-layer trees to stack): a jitted
    ``init`` writes every weight once, with one op per leaf rather than
    one per layer, which keeps its compile short at full depth."""
    keys = jax.random.split(rng, n)
    return jax.vmap(
        lambda k: dict(norm=layers.init_norm(cfg), **init_fn(k, cfg)))(keys)


def init_params(rng, cfg) -> dict:
    layout = default_layout(cfg)
    counts = layout_counts(layout)
    k_embed, k_head, k_rest = jax.random.split(rng, 3)
    params: dict = {
        "embed": layers.embed_init(k_embed, cfg.vocab_padded, cfg.d_model,
                                   cfg.jnp_param_dtype()),
        "final_norm": layers.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(k_head, cfg.d_model,
                                              cfg.vocab_padded,
                                              cfg.jnp_param_dtype())
    stacks = {}
    kinds = sorted(counts)
    keys = jax.random.split(k_rest, max(len(kinds), 1))
    for key, kind in zip(keys, kinds):
        init_fn = _MIXER_INIT.get(kind) or _FFN_INIT[kind]
        stacks[kind] = _stack_init(key, counts[kind], init_fn, cfg)
    params["stacks"] = stacks
    return params


def tree_slice(tree, idx: int):
    return jax.tree.map(lambda x: x[idx], tree)


# --------------------------------------------------------------- mixer apply
def _apply_mixer(kind: str, p, cfg, h, positions, *, impl: str):
    hn = layers.apply_norm(cfg, p["norm"], h)
    if kind in ("attn", "local_attn"):
        window = cfg.attn_window if kind == "local_attn" else 0
        out, kv = attention.attention(p, cfg, hn, positions, window=window,
                                      impl=impl)
        return out, kv
    if kind == "rglru":
        return rglru_mod.rglru_mixer(p, cfg, hn, impl=impl), None
    if kind == "ssd":
        return ssm_mod.ssd_mixer(p, cfg, hn, impl=impl), None
    raise ValueError(kind)


def _apply_ffn(kind: str, p, cfg, h, *, impl: str):
    hn = layers.apply_norm(cfg, p["norm"], h)
    if kind == "dense":
        return ffn_mod.ffn(p, cfg, hn, impl=impl)
    if kind == "moe":
        return moe_mod.moe_ffn(p, cfg, hn,
                               impl="dense" if impl == "oracle" else "scatter")
    raise ValueError(kind)


def _embed(params, cfg, tokens, extra_embeds):
    h = params["embed"][tokens].astype(cfg.jnp_dtype())
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), h.dtype)
    if extra_embeds is not None:
        h = jnp.concatenate([extra_embeds.astype(h.dtype), h], axis=1)
    return act.hidden(h)


def _unembed(params, cfg, h):
    h = layers.apply_norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        lg = jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
    else:
        lg = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
    return act.logits(lg)


def force_unroll() -> bool:
    """REPRO_UNROLL=1 lowers layer loops unrolled instead of lax.scan —
    XLA's cost_analysis counts a while-loop body once regardless of trip
    count, so the roofline dry-run unrolls to get exact per-op FLOPs /
    bytes / collective counts in the HLO."""
    return os.environ.get("REPRO_UNROLL", "0") == "1"


def _ones_gates(n_layers: int):
    return {"mixer": jnp.ones((n_layers,), jnp.float32),
            "ffn": jnp.ones((n_layers,), jnp.float32)}


def _bgate(g, ref):
    """Broadcast one layer's gate against an activation [B, S, D].

    Gates are scalars in one-shot serving ([L] per-layer vectors) and
    per-request rows in the continuous-batching engine ([L, B]: each cache
    slot runs its own keep-mask). Scalars broadcast as before; [B] rows gain
    trailing axes so slot b's residual branch is scaled by its own gate.
    """
    g = g.astype(ref.dtype)
    if g.ndim == 0:
        return g
    return g.reshape(g.shape + (1,) * (ref.ndim - g.ndim))


# -------------------------------------------------------------------- forward
def forward(params, cfg, tokens, *, gates=None, extra_embeds=None,
            impl: str = "xla", remat: bool = False, layout=None,
            collect_kv: bool = False, unembed: bool = True):
    """Full-sequence forward. Returns (logits f32 [B,S,Vp], kv or None);
    ``unembed=False`` returns the pre-final-norm hidden state instead (the
    chunked-CE path computes logits blockwise to avoid materializing the
    [B,S,V] f32 tensor)."""
    use_groups = (layout is None and bool(cfg.block_pattern)
                  and not force_unroll() and not collect_kv
                  and cfg.n_layers >= 2 * len(cfg.block_pattern))
    layout = layout or default_layout(cfg)
    L = len(layout)
    gates = gates or _ones_gates(L)
    h = _embed(params, cfg, tokens, extra_embeds)
    positions = jnp.arange(h.shape[1])[None, :]

    uniform = (all(s.mixer == layout[0].mixer and s.ffn == layout[0].ffn
                   for s in layout) and L > 0 and not force_unroll())
    kvs = None
    if use_groups:
        return _forward_pattern_groups(params, cfg, h, positions, gates,
                                       impl=impl, remat=remat,
                                       unembed=unembed)
    if uniform and not collect_kv:
        mk = "attn" if layout[0].mixer == "local_attn" else layout[0].mixer
        mixer_stack = params["stacks"][mk]
        ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None

        def body(carry, xs):
            h = act.hidden(carry)
            pm, pf, gm, gf = xs
            out, _ = _apply_mixer(layout[0].mixer, pm, cfg, h, positions,
                                  impl=impl)
            h = h + gm.astype(h.dtype) * out
            if pf is not None:
                h = h + gf.astype(h.dtype) * _apply_ffn(layout[0].ffn, pf, cfg,
                                                        h, impl=impl)
            return h, None

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        h, _ = jax.lax.scan(body, h,
                            (mixer_stack, ffn_stack, gates["mixer"],
                             gates["ffn"]))
    else:
        if collect_kv:
            kvs = []
        for i, slot in enumerate(layout):
            # NB: prevent_cse stays True here — in UNROLLED code,
            # prevent_cse=False lets XLA CSE re-merge the rematerialized
            # values with the forward ones, silently disabling remat
            # (observed: 294 GB/device on recurrentgemma × train_4k).
            # Inside lax.scan bodies the loop boundary blocks CSE, so the
            # scan paths keep prevent_cse=False for cheaper HLO.
            if slot.mixer is not None:
                mk = "attn" if slot.mixer == "local_attn" else slot.mixer
                pm = tree_slice(params["stacks"][mk], slot.mixer_idx)
                step = lambda h, pm=pm, slot=slot: _apply_mixer(
                    slot.mixer, pm, cfg, h, positions, impl=impl)
                if remat:
                    step = jax.checkpoint(step)
                out, kv = step(h)
                h = act.hidden(h + gates["mixer"][i].astype(h.dtype) * out)
                if collect_kv and kv is not None:
                    kvs.append(kv)
            if slot.ffn is not None:
                pf = tree_slice(params["stacks"][slot.ffn], slot.ffn_idx)
                fstep = lambda h, pf=pf, slot=slot: _apply_ffn(
                    slot.ffn, pf, cfg, h, impl=impl)
                if remat:
                    fstep = jax.checkpoint(fstep)
                h = act.hidden(h + gates["ffn"][i].astype(h.dtype) * fstep(h))
    if not unembed:
        return h, kvs
    logits = _unembed(params, cfg, h)
    return logits, kvs


def _forward_pattern_groups(params, cfg, h, positions, gates, *, impl,
                            remat, unembed):
    """Patterned architectures (Griffin's rglru/rglru/local_attn) as a
    ``lax.scan`` over repeating GROUPS of stacked params — the MaxText
    "repeat block" trick. A fully unrolled 38-layer train graph keeps every
    layer's backward residuals live simultaneously (86–294 GB/device on
    recurrentgemma × train_4k depending on remat details) and compiles for
    minutes; the group scan restores while-loop double-buffering and
    O(pattern) HLO. Trailing layers that do not complete a group unroll.
    """
    pattern = cfg.layer_specs()[0:len(cfg.block_pattern)]
    pattern = [m for m, _ in cfg.layer_specs()][:len(cfg.block_pattern)]
    plen = len(pattern)
    L = cfg.n_layers
    n_groups = L // plen
    rem = L - n_groups * plen

    # per-kind count inside one pattern repetition
    c_kind: Dict[str, int] = {}
    for m in pattern:
        mk = "attn" if m == "local_attn" else m
        c_kind[mk] = c_kind.get(mk, 0) + 1

    # grouped param stacks: position j of every group, stacked over groups
    grouped = []
    occ: Dict[str, int] = {}
    for j, m in enumerate(pattern):
        mk = "attn" if m == "local_attn" else m
        off = occ.get(mk, 0)
        occ[mk] = off + 1
        idx = off + c_kind[mk] * jnp.arange(n_groups)
        mix_j = jax.tree.map(lambda x, i=idx: x[i], params["stacks"][mk])
        ffn_idx = j + plen * jnp.arange(n_groups)
        ffn_j = jax.tree.map(lambda x, i=ffn_idx: x[i],
                             params["stacks"]["dense"])
        grouped.append((mix_j, ffn_j))

    gm = gates["mixer"][: n_groups * plen].reshape(n_groups, plen)
    gf = gates["ffn"][: n_groups * plen].reshape(n_groups, plen)

    def body(carry, xs):
        h = act.hidden(carry)
        trees, gm_g, gf_g = xs
        for j, m in enumerate(pattern):
            mix_j, ffn_j = trees[j]
            out, _ = _apply_mixer(m, mix_j, cfg, h, positions, impl=impl)
            h = h + gm_g[j].astype(h.dtype) * out
            h = h + gf_g[j].astype(h.dtype) * _apply_ffn(
                "dense", ffn_j, cfg, h, impl=impl)
        return h, None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(body, h, (tuple(grouped), gm, gf))

    # remainder layers (pattern prefix), unrolled with safe remat
    occ = {}
    for r in range(rem):
        m = pattern[r]
        mk = "attn" if m == "local_attn" else m
        off = occ.get(mk, 0)
        occ[mk] = off + 1
        mix_r = tree_slice(params["stacks"][mk],
                           c_kind.get(mk, 0) * n_groups + off)
        ffn_r = tree_slice(params["stacks"]["dense"], n_groups * plen + r)
        i = n_groups * plen + r

        def step(h, mix_r=mix_r, m=m):
            return _apply_mixer(m, mix_r, cfg, h, positions, impl=impl)[0]

        def fstep(h, ffn_r=ffn_r):
            return _apply_ffn("dense", ffn_r, cfg, h, impl=impl)

        if remat:
            step, fstep = jax.checkpoint(step), jax.checkpoint(fstep)
        h = act.hidden(h + gates["mixer"][i].astype(h.dtype) * step(h))
        h = h + gates["ffn"][i].astype(h.dtype) * fstep(h)

    if not unembed:
        return h, None
    return _unembed(params, cfg, h), None


# ---------------------------------------------------------------------- cache
def init_cache(cfg, batch: int, max_len: int, layout=None,
               kv_dtype=None) -> dict:
    """Pre-allocated decode state for every stateful kind in the layout."""
    layout = layout or default_layout(cfg)
    kv_dtype = kv_dtype or cfg.jnp_dtype()
    n_global = sum(1 for s in layout if s.mixer == "attn")
    n_local = sum(1 for s in layout if s.mixer == "local_attn")
    n_rglru = sum(1 for s in layout if s.mixer == "rglru")
    n_ssd = sum(1 for s in layout if s.mixer == "ssd")
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    if n_global:
        cache["attn"] = attention.init_kv_cache(cfg, batch, max_len,
                                                n_global, kv_dtype)
    if n_local:
        w = min(cfg.attn_window, max_len)
        cache["local_attn"] = attention.init_kv_cache(cfg, batch, w,
                                                      n_local, kv_dtype)
    if n_rglru:
        cache["rglru"] = rglru_mod.init_rglru_cache(cfg, batch, n_rglru)
    if n_ssd:
        cache["ssd"] = ssm_mod.init_ssd_cache(cfg, batch, n_ssd)
    return cache


def _cache_indices(layout):
    """Per-layer index into each kind's cache stack."""
    counters: Dict[str, int] = {}
    idx = []
    for s in layout:
        if s.mixer is None:
            idx.append(-1)
            continue
        i = counters.get(s.mixer, 0)
        counters[s.mixer] = i + 1
        idx.append(i)
    return idx


def _is_uniform(layout) -> bool:
    if force_unroll():
        return False
    return len(layout) > 0 and all(
        s.mixer == layout[0].mixer and s.ffn == layout[0].ffn for s in layout)


# -------------------------------------------------------------------- prefill
def prefill(params, cfg, tokens, max_len: int, *, gates=None,
            extra_embeds=None, impl: str = "xla", layout=None,
            kv_dtype=None) -> Tuple[jnp.ndarray, dict]:
    """Process the prompt; return (last-position logits [B,Vp], filled cache).

    Stateful mixers run their sequence form and we extract final state; the
    attention KV collected during the pass is written into the cache.
    Uniform architectures run as one ``lax.scan`` (small HLO, fast compiles
    at 512-device GSPMD); heterogeneous ones unroll.
    """
    layout = layout or default_layout(cfg)
    B, S = tokens.shape
    if extra_embeds is not None:
        S = S + extra_embeds.shape[1]
    L = len(layout)
    gates = gates or _ones_gates(L)
    h = _embed(params, cfg, tokens, extra_embeds)
    positions = jnp.arange(S)[None, :]
    cidx = _cache_indices(layout)

    if _is_uniform(layout) and layout[0].mixer == "attn":
        mixer_stack = params["stacks"]["attn"]
        ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None

        def body(h, xs):
            h = act.hidden(h)
            pm, pf, gm, gf = xs
            hn = layers.apply_norm(cfg, pm["norm"], h)
            out, kv = attention.attention(pm, cfg, hn, positions, impl=impl)
            h = h + gm.astype(h.dtype) * out
            if pf is not None:
                h = h + gf.astype(h.dtype) * _apply_ffn(layout[0].ffn, pf,
                                                        cfg, h, impl=impl)
            return h, kv

        h, kvs = jax.lax.scan(body, h, (mixer_stack, ffn_stack,
                                        gates["mixer"], gates["ffn"]))
        cache = init_cache(cfg, B, max_len, layout, kv_dtype)
        stored = attention.store_kv(cache["attn"], kvs["k"], kvs["v"])
        for key, val in stored.items():
            cache["attn"][key] = jax.lax.dynamic_update_slice(
                cache["attn"][key], val, (0,) * cache["attn"][key].ndim)
        logits = _unembed(params, cfg, h[:, -1:, :])[:, 0]
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return logits, cache

    if _is_uniform(layout) and layout[0].mixer == "ssd":
        mixer_stack = params["stacks"]["ssd"]
        ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None

        def body(h, xs):
            h = act.hidden(h)
            pm, pf, gm, gf = xs
            hn = layers.apply_norm(cfg, pm["norm"], h)
            out, sstate, conv = _ssd_prefill(pm, cfg, hn)
            h = h + gm.astype(h.dtype) * out
            if pf is not None:
                h = h + gf.astype(h.dtype) * _apply_ffn(layout[0].ffn, pf,
                                                        cfg, h, impl=impl)
            return h, (sstate, conv)

        h, (states, convs) = jax.lax.scan(
            body, h, (mixer_stack, ffn_stack, gates["mixer"], gates["ffn"]))
        cache = init_cache(cfg, B, max_len, layout, kv_dtype)
        cache["ssd"]["state"] = states
        cache["ssd"]["conv"] = convs.astype(cache["ssd"]["conv"].dtype)
        logits = _unembed(params, cfg, h[:, -1:, :])[:, 0]
        cache["pos"] = jnp.asarray(S, jnp.int32)
        return logits, cache

    cache = init_cache(cfg, B, max_len, layout, kv_dtype)

    for i, slot in enumerate(layout):
        if slot.mixer is not None:
            mk = "attn" if slot.mixer == "local_attn" else slot.mixer
            pm = tree_slice(params["stacks"][mk], slot.mixer_idx)
            hn = layers.apply_norm(cfg, pm["norm"], h)
            if slot.mixer in ("attn", "local_attn"):
                window = cfg.attn_window if slot.mixer == "local_attn" else 0
                out, kv = attention.attention(pm, cfg, hn, positions,
                                              window=window, impl=impl)
                ci = cidx[i]
                k, v = kv["k"], kv["v"]
                if slot.mixer == "local_attn":
                    w = cache["local_attn"]["k"].shape[2]
                    if S >= w:
                        # keep last `w` positions; element i holds position
                        # (S-w+i) whose ring slot is (S-w+i) % w → roll by
                        # (S-w) % w so slot = pos % w stays valid.
                        k, v = k[:, S - w:], v[:, S - w:]
                        roll = (S - w) % w
                        k = jnp.roll(k, roll, axis=1)
                        v = jnp.roll(v, roll, axis=1)
                    else:
                        pad = ((0, 0), (0, w - S), (0, 0), (0, 0))
                        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                stored = attention.store_kv(cache[slot.mixer], k, v)
                for key, val in stored.items():
                    arr = cache[slot.mixer][key]
                    cache[slot.mixer][key] = jax.lax.dynamic_update_slice(
                        arr, val[None], (ci,) + (0,) * (arr.ndim - 1))
            elif slot.mixer == "rglru":
                out, hstate, conv = _rglru_prefill(pm, cfg, hn)
                ci = cidx[i]
                cache["rglru"]["h"] = cache["rglru"]["h"].at[ci].set(hstate)
                cache["rglru"]["conv"] = cache["rglru"]["conv"].at[ci].set(conv)
            else:  # ssd
                out, sstate, conv = _ssd_prefill(pm, cfg, hn)
                ci = cidx[i]
                cache["ssd"]["state"] = cache["ssd"]["state"].at[ci].set(sstate)
                cache["ssd"]["conv"] = cache["ssd"]["conv"].at[ci].set(conv)
            h = act.hidden(h + gates["mixer"][i].astype(h.dtype) * out)
        if slot.ffn is not None:
            pf = tree_slice(params["stacks"][slot.ffn], slot.ffn_idx)
            h = h + gates["ffn"][i].astype(h.dtype) * _apply_ffn(
                slot.ffn, pf, cfg, h, impl=impl)

    logits = _unembed(params, cfg, h[:, -1:, :])[:, 0]
    cache["pos"] = jnp.asarray(S, jnp.int32)
    return logits, cache


def _rglru_prefill(pm, cfg, hn):
    """Run sequence rglru and recover final recurrent + conv state."""
    out = rglru_mod.rglru_mixer(pm, cfg, hn)
    # recompute final state cheaply: redo gate path on the last CONV window
    u = act.width(jnp.einsum("btd,dw->btw", hn, pm["wx"].astype(hn.dtype)))
    K = pm["conv_w"].shape[0]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    uc = act.width(
        sum(up[:, i:i + u.shape[1], :] * pm["conv_w"].astype(u.dtype)[i][None, None]
            for i in range(K)) + pm["conv_b"].astype(u.dtype))
    a, b = rglru_mod._gates(pm, uc)
    hseq = rglru_mod.blocked_scan(a, b)
    return out, hseq[:, -1], u[:, -(K - 1):, :]


def _ssd_prefill(pm, cfg, hn):
    out = ssm_mod.ssd_mixer(pm, cfg, hn)
    # recover final state by rerunning the scan's state path
    DI, N, H, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z, xBC, dt = ssm_mod._split_proj(pm, cfg, hn)
    xBC_conv = layers.silu(ssm_mod._causal_conv(
        xBC, pm["conv_w"].astype(hn.dtype), pm["conv_b"].astype(hn.dtype)))
    xc, Bm, Cm = jnp.split(xBC_conv, [DI, DI + N], axis=-1)
    dtf = jax.nn.softplus(dt.astype(jnp.float32) + pm["dt_bias"])
    A = -jnp.exp(pm["A_log"])
    log_a = dtf * A
    xh = xc.reshape(*xc.shape[:2], H, P).astype(jnp.float32) * dtf[..., None]
    _, final = ssm_mod._ssd_scan(xh, log_a, Bm.astype(jnp.float32),
                                 Cm.astype(jnp.float32), cfg.ssm_chunk)
    K = pm["conv_w"].shape[0]
    return out, final, xBC[:, -(K - 1):, :]


def prefill_chunk(params, cfg, cache, tokens, start, *, gates=None,
                  impl: str = "xla",
                  layout=None) -> Tuple[jnp.ndarray, dict]:
    """Process one prompt chunk against a partially filled slot cache.

    The chunked-prefill hot path (DESIGN.md §6): ``tokens`` [B, C] are C
    consecutive prompt tokens at absolute offset ``start`` (int32 scalar,
    traced — executables key on the chunk width, never the offset). Layers
    scan with the KV cache riding the carry exactly like
    :func:`decode_step`; each layer's chunk K/V lands at ``[start,
    start+C)`` and the chunk's queries attend everything written so far.
    Running a prompt chunk-by-chunk (any split) then reading the final
    chunk's last-position logits is bitwise-identical to :func:`prefill`.
    Returns (last-position logits [B, Vp], cache).

    Uniform all-attention layouts only — recurrent/SSD state has no
    positional write frontier to resume from; heterogeneous models stay
    on the monolithic prefill.
    """
    layout = layout or default_layout(cfg)
    if not (_is_uniform(layout) and layout[0].mixer == "attn"):
        raise NotImplementedError(
            "prefill_chunk serves uniform all-attention layouts; "
            f"got mixers {sorted({str(s.mixer) for s in layout})} — use "
            "prefill (monolithic) for heterogeneous models")
    L = len(layout)
    gates = gates or _ones_gates(L)
    start = jnp.asarray(start, jnp.int32)
    h = _embed(params, cfg, tokens, None)
    mixer_stack = params["stacks"]["attn"]
    ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None
    state0 = cache["attn"]

    def body(carry, xs):
        h, state = carry
        pm, pf, gm, gf, i = xs
        hn = layers.apply_norm(cfg, pm["norm"], h)
        kv = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
            state)
        out, kv = attention.chunk_attention(pm, cfg, hn, kv, start, impl=impl)
        state = jax.tree.map(
            lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n, i, 0),
            state, kv)
        h = h + _bgate(gm, h) * out
        if pf is not None:
            h = h + _bgate(gf, h) * _apply_ffn(layout[0].ffn, pf, cfg, h,
                                               impl=impl)
        return (h, state), None

    xs = (mixer_stack, ffn_stack, gates["mixer"], gates["ffn"],
          jnp.arange(L, dtype=jnp.int32))
    (h, state), _ = jax.lax.scan(body, (h, state0), xs)
    cache["attn"] = state
    logits = _unembed(params, cfg, h[:, -1:, :])[:, 0]
    cache["pos"] = start + tokens.shape[1]
    return logits, cache


def _pool_layer(pools: dict, i) -> dict:
    """Layer ``i``'s slice of every pool leaf (pages and, when the pool is
    quantized, the per-page scales)."""
    return {name: jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
            for name, leaf in pools.items()}


def _pool_store(pools: dict, kv: dict, i) -> dict:
    """Write a layer's updated slices back into the stacked pools."""
    return {name: jax.lax.dynamic_update_index_in_dim(pools[name], kv[name],
                                                      i, 0)
            for name in pools}


def paged_prefill_chunk(params, cfg, pools: dict, page_table, tokens, start,
                        *, scratch_page: int, gates=None, impl: str = "xla",
                        layout=None) -> Tuple[jnp.ndarray, dict]:
    """Paged sibling of :func:`prefill_chunk`: one prompt chunk appended
    straight into granted pages.

    pools: {"k","v"} [L, n_pages, K, page_tokens, Dh] — quantized pools
    add per-page scale leaves {"ks","vs"} [L, n_pages, K]; page_table:
    int32 [B, max_pages]; tokens [B, C] at absolute offset ``start``. The
    pool arrays ride the layer scan's carry (donated, in-place) exactly
    like :func:`paged_decode_step`; the same uniform all-attention
    restriction applies. Returns (last-position logits [B, Vp], pools').
    """
    layout = layout or default_layout(cfg)
    if not (len(layout) > 0
            and all(s.mixer == "attn" and s.ffn == layout[0].ffn
                    for s in layout)):
        raise NotImplementedError(
            "paged prefill serves uniform all-attention layouts; "
            f"got mixers {sorted({str(s.mixer) for s in layout})} — use "
            "prefill (slot caches) for heterogeneous models")
    L = len(layout)
    gates = gates or _ones_gates(L)
    start = jnp.asarray(start, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    h = _embed(params, cfg, tokens, None)
    mixer_stack = params["stacks"]["attn"]
    ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None

    def body(carry, xs):
        h, pools = carry
        pm, pf, gm, gf, i = xs
        hn = layers.apply_norm(cfg, pm["norm"], h)
        kv = _pool_layer(pools, i)
        out, kv = attention.paged_chunk_attention(
            pm, cfg, hn, kv, page_table, start, scratch_page=scratch_page,
            impl=impl)
        pools = _pool_store(pools, kv, i)
        h = h + _bgate(gm, h) * out
        if pf is not None:
            h = h + _bgate(gf, h) * _apply_ffn(layout[0].ffn, pf, cfg, h,
                                               impl=impl)
        return (h, pools), None

    xs = (mixer_stack, ffn_stack, gates["mixer"], gates["ffn"],
          jnp.arange(L, dtype=jnp.int32))
    (h, pools), _ = jax.lax.scan(body, (h, dict(pools)), xs)
    logits = _unembed(params, cfg, h[:, -1:, :])[:, 0]
    return logits, pools


# --------------------------------------------------------------------- decode
def decode_step(params, cfg, cache, tokens, *, gates=None, impl: str = "xla",
                layout=None) -> Tuple[jnp.ndarray, dict]:
    """One autoregressive step. tokens: [B,1]. Returns (logits [B,1,Vp], cache).

    Continuous-batching form: ``cache["pos"]`` may be an int32 [B] vector
    (per-slot decode offsets) and ``gates`` entries may be [L, B] (per-slot
    keep-masks) — every slot of the engine's shared cache advances one token
    in a single fused step. Scalar pos / [L] gates remain the one-shot path.
    """
    layout = layout or default_layout(cfg)
    L = len(layout)
    gates = gates or _ones_gates(L)
    pos = cache["pos"]
    h = _embed(params, cfg, tokens, None)
    cidx = _cache_indices(layout)

    if _is_uniform(layout) and layout[0].mixer in ("attn", "ssd"):
        kind = layout[0].mixer
        mixer_stack = params["stacks"][kind]
        ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None

        # The layer-state buffer rides the scan CARRY with per-layer
        # dynamic(-update)-slice — in-place while-loop updates that alias
        # the donated input cache. (Passing it as scan xs/ys doubles the
        # live cache: the stacked ys staging buffer costs a full extra
        # copy — 11 GB/device on qwen1.5-32b × decode_32k.)
        state0 = cache["attn"] if kind == "attn" else cache["ssd"]

        def body(carry, xs):
            h, state = carry
            pm, pf, gm, gf, i = xs
            hn = layers.apply_norm(cfg, pm["norm"], h)
            if kind == "attn":
                kv = jax.tree.map(
                    lambda x: jax.lax.dynamic_index_in_dim(
                        x, i, 0, keepdims=False), state)
                out, kv = attention.decode_attention(pm, cfg, hn, kv, pos,
                                                     impl=impl)
                state = jax.tree.map(
                    lambda s, n: jax.lax.dynamic_update_index_in_dim(
                        s, n, i, 0), state, kv)
            else:
                ss = jax.lax.dynamic_index_in_dim(state["state"], i, 0,
                                                  keepdims=False)
                cb = jax.lax.dynamic_index_in_dim(state["conv"], i, 0,
                                                  keepdims=False)
                out, ss, cb = ssm_mod.ssd_decode_step(pm, cfg, hn, ss, cb)
                state = {
                    "state": jax.lax.dynamic_update_index_in_dim(
                        state["state"], ss, i, 0),
                    "conv": jax.lax.dynamic_update_index_in_dim(
                        state["conv"], cb, i, 0)}
            h = h + _bgate(gm, h) * out
            if pf is not None:
                h = h + _bgate(gf, h) * _apply_ffn(layout[0].ffn, pf,
                                                   cfg, h, impl=impl)
            return (h, state), None

        L_kind = len(layout)
        xs = (mixer_stack, ffn_stack, gates["mixer"], gates["ffn"],
              jnp.arange(L_kind, dtype=jnp.int32))
        (h, state), _ = jax.lax.scan(body, (h, state0), xs)
        if kind == "attn":
            cache["attn"] = state
        else:
            cache["ssd"] = state
        logits = _unembed(params, cfg, h)
        cache["pos"] = pos + 1
        return logits, cache

    for i, slot in enumerate(layout):
        if slot.mixer is not None:
            mk = "attn" if slot.mixer == "local_attn" else slot.mixer
            pm = tree_slice(params["stacks"][mk], slot.mixer_idx)
            hn = layers.apply_norm(cfg, pm["norm"], h)
            ci = cidx[i]
            if slot.mixer in ("attn", "local_attn"):
                kind = slot.mixer
                window = cfg.attn_window if kind == "local_attn" else 0
                kv = jax.tree.map(lambda x: x[ci], cache[kind])
                out, kv = attention.decode_attention(pm, cfg, hn, kv, pos,
                                                     window=window, impl=impl)
                cache[kind] = jax.tree.map(lambda c, n: c.at[ci].set(n),
                                           cache[kind], kv)
            elif slot.mixer == "rglru":
                out, hs, cb = rglru_mod.rglru_decode_step(
                    pm, cfg, hn, cache["rglru"]["h"][ci],
                    cache["rglru"]["conv"][ci])
                cache["rglru"]["h"] = cache["rglru"]["h"].at[ci].set(hs)
                cache["rglru"]["conv"] = cache["rglru"]["conv"].at[ci].set(cb)
            else:
                out, ss, cb = ssm_mod.ssd_decode_step(
                    pm, cfg, hn, cache["ssd"]["state"][ci],
                    cache["ssd"]["conv"][ci])
                cache["ssd"]["state"] = cache["ssd"]["state"].at[ci].set(ss)
                cache["ssd"]["conv"] = cache["ssd"]["conv"].at[ci].set(cb)
            h = h + _bgate(gates["mixer"][i], h) * out
        if slot.ffn is not None:
            pf = tree_slice(params["stacks"][slot.ffn], slot.ffn_idx)
            h = h + _bgate(gates["ffn"][i], h) * _apply_ffn(
                slot.ffn, pf, cfg, h, impl=impl)

    logits = _unembed(params, cfg, h)
    cache["pos"] = pos + 1
    return logits, cache


def decode_horizon(params, cfg, cache, tokens, horizon: int, *, gates=None,
                   impl: str = "xla",
                   layout=None) -> Tuple[jnp.ndarray, dict]:
    """Fuse ``horizon`` greedy decode ticks into one on-device loop.

    ``lax.scan`` over :func:`decode_step`: each iteration feeds the argmax
    token of the previous step back in, so a whole *horizon* of tokens is
    produced by ONE dispatched executable with ONE device→host read-back
    (the ``[B, horizon]`` token matrix) instead of ``horizon`` round trips.
    ``tokens`` is the int32 [B, 1] seed (the last emitted token per row);
    ``gates``/``pos`` semantics are exactly :func:`decode_step`'s — per-slot
    [L, B] gates and int32 [B] positions ride the scan unchanged/incremented.
    Returns (toks int32 [B, horizon], cache after ``horizon`` steps).
    Greedy only: the scan carries the argmax token, not logits.
    """
    horizon = int(horizon)
    if horizon < 1:
        raise ValueError(f"horizon must be >= 1, got {horizon}")

    def body(carry, _):
        cache, tok = carry
        logits, cache = decode_step(params, cfg, cache, tok, gates=gates,
                                    impl=impl, layout=layout)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (cache, nxt[:, None]), nxt

    seed = jnp.asarray(tokens, jnp.int32)
    (cache, _), toks = jax.lax.scan(body, (cache, seed), None, length=horizon)
    return jnp.moveaxis(toks, 0, 1), cache


def paged_decode_horizon(params, cfg, pools: dict, page_table, pos, tokens,
                         horizon: int, *, gates=None, impl: str = "xla",
                         layout=None) -> Tuple[jnp.ndarray, dict, jnp.ndarray]:
    """Fuse ``horizon`` paged decode ticks into one on-device loop.

    The paged sibling of :func:`decode_horizon`: scans
    :func:`paged_decode_step` with the page pools, per-row positions, and
    the fed-back argmax token riding the carry. The page table is
    *constant* across the horizon — callers pre-grant every page the
    horizon can touch (``KVPool.extend(rid, horizon)``) before launching,
    which the admission-time worst-case commitment guarantees can't fail.
    Returns (toks int32 [B, horizon], pools', pos + horizon).
    """
    horizon = int(horizon)
    if horizon < 1:
        raise ValueError(f"horizon must be >= 1, got {horizon}")
    pos = jnp.asarray(pos, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)

    def body(carry, _):
        pools, pos, tok = carry
        logits, pools = paged_decode_step(params, cfg, pools, page_table,
                                          pos, tok, gates=gates, impl=impl,
                                          layout=layout)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return (pools, pos + 1, nxt[:, None]), nxt

    seed = jnp.asarray(tokens, jnp.int32)
    (pools, pos, _), toks = jax.lax.scan(body, (pools, pos, seed), None,
                                         length=horizon)
    return jnp.moveaxis(toks, 0, 1), pools, pos


def paged_decode_step(params, cfg, pools: dict, page_table, pos, tokens, *,
                      gates=None, impl: str = "xla",
                      layout=None) -> Tuple[jnp.ndarray, dict]:
    """One autoregressive step against a *paged* KV pool.

    pools: {"k","v"} global head-major page arrays [L, n_pages, K,
    page_tokens, Dh]
    (one pool slice per attention layer, stacked — a page id is valid at
    every layer; quantized pools add {"ks","vs"} [L, n_pages, K] scales);
    page_table: int32 [B, max_pages]; pos: int32 [B] per-row
    write positions; tokens: [B, 1]. Returns (logits [B,1,Vp], pools').

    Only uniform all-attention layouts are supported (the llama/gemma/qwen
    families the paper evaluates): heterogeneous mixers keep their state in
    per-request slot caches and stay on :func:`decode_step` — paging
    recurrent/SSD state is a different (fixed-size) problem. Gates may be
    [L] (one-shot) or [L, B] (per-slot keep-masks), as in ``decode_step``.
    The pool arrays ride the layer scan's carry with per-layer
    dynamic(-update)-slice, aliasing the donated inputs exactly like the
    dense decode path.
    """
    layout = layout or default_layout(cfg)
    if not (len(layout) > 0
            and all(s.mixer == "attn" and s.ffn == layout[0].ffn
                    for s in layout)):
        raise NotImplementedError(
            "paged decode serves uniform all-attention layouts; "
            f"got mixers {sorted({str(s.mixer) for s in layout})} — use "
            "decode_step (slot caches) for heterogeneous models")
    L = len(layout)
    gates = gates or _ones_gates(L)
    pos = jnp.asarray(pos, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    h = _embed(params, cfg, tokens, None)
    mixer_stack = params["stacks"]["attn"]
    ffn_stack = params["stacks"][layout[0].ffn] if layout[0].ffn else None

    def body(carry, xs):
        h, pools = carry
        pm, pf, gm, gf, i = xs
        hn = layers.apply_norm(cfg, pm["norm"], h)
        kv = _pool_layer(pools, i)
        out, kv = attention.paged_decode_attention(pm, cfg, hn, kv,
                                                   page_table, pos,
                                                   impl=impl)
        pools = _pool_store(pools, kv, i)
        h = h + _bgate(gm, h) * out
        if pf is not None:
            h = h + _bgate(gf, h) * _apply_ffn(layout[0].ffn, pf, cfg, h,
                                               impl=impl)
        return (h, pools), None

    xs = (mixer_stack, ffn_stack, gates["mixer"], gates["ffn"],
          jnp.arange(L, dtype=jnp.int32))
    (h, pools), _ = jax.lax.scan(body, (h, dict(pools)), xs)
    logits = _unembed(params, cfg, h)
    return logits, pools
