"""GQA/MQA/MHA attention with RoPE, qk-norm, qkv-bias, local windows, caching.

Two data paths:
  * prefill/train — full-sequence causal (optionally banded) attention;
  * decode       — one query token against a pre-allocated KV cache.

The XLA path is the default (and the dry-run path); ``impl='pallas'`` routes
through the Pallas flash-attention kernels in ``repro.kernels``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models import layers

NEG_INF = -2.0e38


def init_attn_params(rng, cfg) -> dict:
    """Separate wq/wk/wv (not fused): the fused [D, q+2kv] layout puts the
    q|k|v split boundaries off the 16-way TP shard grid for most assigned
    head counts, forcing per-layer reshards. Separate projections shard
    their own feature dims cleanly (MaxText-style)."""
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    pd = cfg.jnp_param_dtype()
    p = {
        "wq": layers.dense_init(k1, cfg.d_model, cfg.q_dim, pd),
        "wk": layers.dense_init(k2, cfg.d_model, cfg.kv_dim, pd),
        "wv": layers.dense_init(k3, cfg.d_model, cfg.kv_dim, pd),
        "wo": layers.dense_init(k4, cfg.q_dim, cfg.d_model, pd,
                                scale=1.0 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), pd)
        p["bk"] = jnp.zeros((cfg.kv_dim,), pd)
        p["bv"] = jnp.zeros((cfg.kv_dim,), pd)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.dh,), pd)
        p["k_norm"] = jnp.zeros((cfg.dh,), pd)
    return p


def _row_major(w):
    """Pin ``w`` to the row-major device layout its stacked parameter is
    stored in. At decode's few-token batches the TPU compiler prefers the
    q/k/v projection weights transposed, and inside a layer scan that
    preference reaches the whole stacked parameter: it copies every
    layer's wq/wk/wv up front (3 GB at llama2-7b, more than one chip has
    beside the weights)."""
    return with_layout_constraint(
        w, Layout(major_to_minor=tuple(range(w.ndim))))


def _project_qkv(params, cfg, x):
    """x: [B, S, D] → q [B,S,H,Dh], k/v [B,S,K,Dh]."""
    q = jnp.einsum("bsd,de->bse", x,
                   _row_major(params["wq"]).astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", x,
                   _row_major(params["wk"]).astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", x,
                   _row_major(params["wv"]).astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    B, S = x.shape[:2]
    q = q.reshape(B, S, cfg.n_heads, cfg.dh)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.dh)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.dh)
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm"], cfg.norm_eps)
    return q, k, v


def _sdpa(cfg, q, k, v, mask):
    """q [B,Sq,H,Dh], k/v [B,Skv,K,Dh], mask broadcastable [B,1,Sq,Skv]."""
    B, Sq, H, Dh = q.shape
    K = k.shape[2]
    G = H // K  # queries per kv head
    q = q.reshape(B, Sq, K, G, Dh)
    scale = 1.0 / math.sqrt(Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = layers.softcap(logits, cfg.logit_softcap)
    logits = jnp.where(mask[:, :, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, Dh)


def _causal_mask(Sq: int, Skv: int, window: int, q_offset: int = 0):
    """[1, 1, Sq, Skv] causal (banded if window>0) mask."""
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Skv)[None, :]
    m = kpos <= qpos
    if window > 0:
        m = m & (kpos > qpos - window)
    return m[None, None, :, :]


# --------------------------------------------------- chunked (long-context)
_CHUNK_MIN_SEQ = 4096        # plain path below this — probs fit comfortably
_CHUNK_BYTE_BUDGET = 16e9    # global bytes for one chunk's f32 probs


def _pick_chunk(B, K, G, Skv, Sq):
    cq = 1024
    while cq > 64 and B * K * G * cq * Skv * 4 > _CHUNK_BYTE_BUDGET:
        cq //= 2
    while cq > 1 and Sq % cq:
        cq //= 2
    return cq


def _sdpa_chunked(cfg, q, k, v, *, window: int = 0, q_offset: int = 0,
                  causal: bool = True):
    """Memory-efficient exact causal attention: ``lax.map`` over query
    chunks, each chunk rematerialized (`jax.checkpoint`) so neither forward
    nor backward ever holds more than one chunk's [B,K,G,cq,Skv] probs —
    the XLA-native flash-attention dataflow (the Pallas kernel is the
    TPU-tiled version of the same thing). Banded (local) attention
    additionally slices KV to the ``window+cq`` live band, making local
    layers O(S·w) instead of O(S²)."""
    B, Sq, H, Dh = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    banded = window > 0 and window + 1024 <= Skv
    eff_kv = (window + 1024) if banded else Skv
    cq = _pick_chunk(B, K, G, eff_kv, Sq)
    if cq < 16:
        return _sdpa(cfg, q, k, v, _causal_mask(Sq, Skv, window, q_offset))
    nq = Sq // cq
    Wk = min(Skv, window + cq) if banded else Skv

    def chunk(qi):
        q_start = qi * cq
        qc = jax.lax.dynamic_slice_in_dim(q, q_start, cq, axis=1)
        if banded:
            k_start = jnp.clip(q_start + q_offset - window + 1, 0, Skv - Wk)
        else:
            k_start = 0
        kc = jax.lax.dynamic_slice_in_dim(k, k_start, Wk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, k_start, Wk, axis=1)
        qpos = q_start + q_offset + jnp.arange(cq)[:, None]
        kpos = k_start + jnp.arange(Wk)[None, :]
        m = (kpos <= qpos) if causal else \
            jnp.ones((cq, Wk), bool) & (kpos >= 0)
        if window > 0:
            m = m & (kpos > qpos - window)
        return _sdpa(cfg, qc, kc, vc, m[None, None])

    out = jax.lax.map(jax.checkpoint(chunk), jnp.arange(nq))  # [nq,B,cq,H,Dh]
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, Dh)


def attention(params, cfg, x, positions, *, window: int = 0,
              impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """Full-sequence causal attention. Returns (out [B,S,D], kv dict)."""
    from repro.parallel import activation as act

    q, k, v = _project_qkv(params, cfg, x)
    if cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    q, k, v = act.heads(q), act.heads(k), act.heads(v)
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window,
                                   softcap=cfg.logit_softcap)
    elif q.shape[1] >= _CHUNK_MIN_SEQ:
        out = _sdpa_chunked(cfg, q, k, v, window=window)
    else:
        mask = _causal_mask(q.shape[1], k.shape[1], window)
        out = _sdpa(cfg, q, k, v, mask)
    y = jnp.einsum("bsq,qm->bsm", out.reshape(*out.shape[:2], -1),
                   params["wo"].astype(x.dtype))
    return y, {"k": k, "v": v}


def cross_attention(params, cfg, x, kv: dict) -> jnp.ndarray:
    """Decoder cross-attention against precomputed encoder K/V (no mask)."""
    q, _, _ = _project_qkv(params, cfg, x)  # k,v projections unused on this path
    k, v = kv["k"], kv["v"]
    B, Sq = q.shape[:2]
    mask = jnp.ones((1, 1, Sq, k.shape[1]), dtype=bool)
    out = _sdpa(cfg, q, k, v, mask)
    return jnp.einsum("bsq,qm->bsm", out.reshape(B, Sq, -1),
                      params["wo"].astype(x.dtype))


def kv_quant(x):
    """Per-(token, head) symmetric int8 quantization. x: [..., Dh]."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def page_qmax(dtype) -> float:
    """Symmetric quantization ceiling of a paged storage dtype: 127 for
    int8, 448 for float8_e4m3fn (its largest finite value)."""
    return 127.0 if jnp.dtype(dtype) == jnp.int8 else 448.0


def page_quant(xf, dtype, scale_floor=None):
    """Quantize whole head-major pages ``[..., K, page_tokens, Dh]`` (f32)
    into ``dtype`` with ONE symmetric scale per (page, kv-head): returns
    ``(q, scales[..., K])``.

    ``scale_floor`` (same shape as the scales) makes the scale monotone
    within a page's lifetime: when an append does not raise the page's
    amax, the scale is unchanged and requantizing the page's existing
    tokens reproduces their stored codes exactly (``round(s·q/s) == q``),
    so repeated appends drift only when the scale actually grows."""
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))            # [..., K]
    qmax = page_qmax(dtype)
    scale = amax / qmax
    if scale_floor is not None:
        scale = jnp.maximum(scale, scale_floor)
    # epsilon as a FLOOR, not an addend: adding it after the max would
    # grow a stable page's scale every requantization
    scale = jnp.maximum(scale, 1e-8)
    y = xf / scale[..., None, None]
    if jnp.dtype(dtype) == jnp.int8:
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(dtype)
    return q, scale.astype(jnp.float32)


def page_dequant(q, scales):
    """Dequantize head-major pages ``[..., K, page_tokens, Dh]`` with
    per-(page, head) scales ``[..., K]`` to f32 — the reference the fused
    kernel is pinned bitwise against (``q.astype(f32) * scale`` per
    element, nothing else)."""
    return q.astype(jnp.float32) * scales[..., None, None]


def gather_pages(kv: dict, name: str, page_table):
    """Contiguous per-row view ``[B, max_pages * page_tokens, K, Dh]`` of
    one pool leaf (``"k"`` or ``"v"``) through ``page_table`` [B,
    max_pages] — the XLA gather fallback's read of head-major pages.
    Quantized pools come back dequantized to f32 (``page_dequant``)."""
    pages = kv[name][page_table]                 # [B, maxp, K, pt, Dh]
    scales = kv.get(name + "s")
    if scales is not None:
        pages = page_dequant(pages, scales[page_table])
    B, maxp, K, pt, Dh = pages.shape
    return jnp.swapaxes(pages, 2, 3).reshape(B, maxp * pt, K, Dh)


def init_kv_cache(cfg, batch: int, max_len: int, n_layers: int, dtype=None):
    """Cache entry dict. bf16/f32 mode: {k, v}. int8 mode adds per-(token,
    head) scales {ks, vs} — the production KV-quantization that halves the
    decode-cache HBM footprint (e.g. qwen1.5-32b × decode_32k does not fit
    a 256-chip pod at bf16)."""
    dt = dtype or cfg.jnp_dtype()
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if jnp.dtype(dt) == jnp.int8:
        sshape = shape[:-1] + (1,)
        cache["ks"] = jnp.zeros(sshape, jnp.float32)
        cache["vs"] = jnp.zeros(sshape, jnp.float32)
    return cache


def store_kv(entry: dict, k, v) -> dict:
    """Encode (k, v) [..., K, Dh] into the entry's storage dtype. Returns the
    leaf dict matching ``init_kv_cache`` structure (no layer axis)."""
    if "ks" in entry:
        kq, ks = kv_quant(k)
        vq, vs = kv_quant(v)
        return {"k": kq, "v": vq, "ks": ks, "vs": vs}
    return {"k": k.astype(entry["k"].dtype), "v": v.astype(entry["v"].dtype)}


def load_kv(entry: dict, dtype):
    if "ks" in entry:
        k = (entry["k"].astype(jnp.float32) * entry["ks"]).astype(dtype)
        v = (entry["v"].astype(jnp.float32) * entry["vs"]).astype(dtype)
        return k, v
    return entry["k"].astype(dtype), entry["v"].astype(dtype)


def paged_decode_attention(params, cfg, x, kv: dict, page_table, pos, *,
                           impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """One-token decode against a *paged* KV pool (one layer's slice).

    x: [B,1,D]; kv: {"k","v"} head-major page pools [n_pages, K,
    page_tokens, Dh] shared by every in-flight request; page_table: int32 [B, max_pages]
    mapping row b's token t to page ``page_table[b, t // page_tokens]``;
    pos: int32 [B] per-row write positions. Returns (out [B,1,D], kv').

    The new token's K/V is scattered into its owning page (rows own
    disjoint pages, so the scatter is conflict-free), then attention runs
    either through the Pallas paged flash-decode kernel (``impl='pallas'``,
    the TPU path — each row's pages are copied by table lookup up to its
    length, no gather)
    or an XLA gather fallback that materializes ``[B, max_pages ×
    page_tokens]`` and reuses the dense softmax (the CPU serving path).

    Quantized pools carry per-(page, kv-head) scales ``{"ks","vs"}``
    ``[n_pages, K]``: the append is a code-space rewrite of the row's
    page — the monotone scale grows to ``max(old, token_amax/qmax)``,
    existing codes rescale by ``old/new`` (exactly 1.0 while the scale
    is stable, so they round-trip bitwise), the token quantizes into its
    slot, and stale slots past the write frontier stay zero. The read
    path dequantizes — fused into the Pallas kernel via per-row gathered
    scales, or mirrored exactly in the XLA gather (``q.astype(f32) *
    scale``) so both paths see identical f32 values.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    page_tokens = kv["k"].shape[2]
    quantized = "ks" in kv
    q, k, v = _project_qkv(params, cfg, x)
    positions = jnp.broadcast_to(pos.reshape(-1, 1), (B, 1))
    if cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    # scatter the new token's KV into its page slot
    rows = jnp.arange(B)
    page_ids = page_table[rows, pos // page_tokens]
    offs = pos % page_tokens
    slot = jnp.arange(page_tokens)[None, None, :, None]       # [1, 1, pt, 1]
    off_b = offs[:, None, None, None]                         # [B, 1, 1, 1]
    kv = dict(kv)
    if quantized:
        # code-space append (rows own disjoint pages; only padded rows
        # collide on the scratch page, which is never read). The monotone
        # page scale means existing codes never exceed old_scale*qmax, so
        # the new scale is just max(token amax / qmax, old scale) — no
        # page-wide amax reduction — and existing codes rescale by
        # old/new, which is exactly 1.0 while the scale is stable: the
        # common-case append rewrites the page bitwise-unchanged plus the
        # one inserted slot, at a fraction of a dequant→requant pass.
        fresh = (offs == 0)[:, None]                          # [B, 1]
        for pk, sk, new in (("k", "ks", k), ("v", "vs", v)):
            qmax = page_qmax(kv[pk].dtype)
            int_codes = jnp.dtype(kv[pk].dtype) == jnp.int8
            tok = new[:, 0].astype(jnp.float32)               # [B, K, Dh]
            old_s = kv[sk][page_ids]                          # [B, K]
            # a freshly started page must not inherit the previous
            # occupant's content or scale
            floor = jnp.where(fresh, 0.0, old_s)
            new_s = jnp.maximum(jnp.maximum(
                jnp.max(jnp.abs(tok), axis=-1) / qmax, floor), 1e-8)
            r = jnp.where(fresh, 0.0, old_s / new_s)          # [B, K] <= 1
            pg = kv[pk][page_ids].astype(jnp.float32) * r[:, :, None, None]
            tok_q = tok / new_s[..., None]
            if int_codes:
                pg, tok_q = jnp.round(pg), jnp.round(tok_q)
            pg = jnp.where(slot == off_b, tok_q[:, :, None], pg)
            pg = jnp.where(slot <= off_b, pg, 0.0)  # stale slots → 0
            kv[pk] = kv[pk].at[page_ids].set(
                jnp.clip(pg, -qmax, qmax).astype(kv[pk].dtype))
            kv[sk] = kv[sk].at[page_ids].set(new_s)
    else:
        # whole-page rewrite, as in the quantized append: a scatter of
        # single token slots (indices on the page and token dims) makes
        # the TPU compiler re-lay-out, i.e. copy, the whole pool
        for pk, new in (("k", k), ("v", v)):
            pg = kv[pk][page_ids]                             # [B, K, pt, Dh]
            pg = jnp.where(slot == off_b,
                           new[:, 0, :, None].astype(pg.dtype), pg)
            kv[pk] = kv[pk].at[page_ids].set(pg)
    lengths = pos + 1
    if impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q, kv["k"], kv["v"], page_table, lengths,
            k_scales=kv.get("ks"), v_scales=kv.get("vs"),
            softcap=cfg.logit_softcap)
    else:
        # gather fallback: page_table indexes the pool back into a
        # contiguous per-row view [B, max_pages*page_tokens, K, Dh]
        ck = gather_pages(kv, "k", page_table)
        cv = gather_pages(kv, "v", page_table)
        S = ck.shape[1]
        valid = jnp.arange(S)[None, :] < lengths[:, None]      # [B, S]
        out = _sdpa(cfg, q, ck.astype(q.dtype), cv.astype(q.dtype),
                    valid[:, None, None, :])
    y = jnp.einsum("bsq,qm->bsm", out.reshape(B, 1, -1),
                   params["wo"].astype(x.dtype))
    return y, kv


def chunk_attention(params, cfg, x, kv: dict, start, *,
                    impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """Prefill one prompt *chunk* against a partially filled KV cache.

    x: [B, C, D] — C consecutive prompt tokens starting at absolute
    position ``start`` (an int32 scalar, traced: executables key on the
    chunk width C, never on the offset); kv: one layer's cache entry with
    leaves [B, S_max, K, Dh]. The chunk's K/V is RoPE'd at its absolute
    positions and written contiguously at ``[start, start+C)``, then the C
    queries attend the full cache width under the causal mask
    ``kpos <= start + qi`` — positions beyond the write frontier are
    masked to exactly-zero probability, so chunk-by-chunk prefill is
    bitwise-identical to the monolithic pass (DESIGN.md §6). Returns
    (out [B, C, D], kv').
    """
    B, C = x.shape[:2]
    start = jnp.asarray(start, jnp.int32)
    q, k, v = _project_qkv(params, cfg, x)
    positions = start + jnp.arange(C)[None, :]
    if cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    new = store_kv(kv, k, v)
    kv = dict(kv)
    for key, val in new.items():
        kv[key] = jax.lax.dynamic_update_slice(
            kv[key], val, (0, start) + (0,) * (kv[key].ndim - 2))
    S = kv["k"].shape[1]
    ck, cv = load_kv(kv, q.dtype)
    mask = _causal_mask(C, S, 0, q_offset=start)
    out = _sdpa(cfg, q, ck, cv, mask)
    y = jnp.einsum("bsq,qm->bsm", out.reshape(B, C, -1),
                   params["wo"].astype(x.dtype))
    return y, kv


def paged_chunk_attention(params, cfg, x, kv: dict, page_table, start, *,
                          scratch_page: int,
                          impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """Paged sibling of :func:`chunk_attention`: prefill C prompt tokens
    straight into granted pages.

    x: [B, C, D]; kv: {"k","v"} head-major page pools [n_pages, K,
    page_tokens, Dh];
    page_table: int32 [B, max_pages]; start: int32 scalar — the chunk's
    first absolute position (every row of a chunked-prefill request sits
    at the same offset). Tokens whose position falls past the table width
    are routed to the scratch page (a write sink) instead of letting the
    gather clamp onto a live page. Attention runs through the same
    gather fallback as ``paged_decode_attention``'s XLA path. Quantized
    pools requantize every page the chunk touches (monotone scales;
    straddled leading pages keep their scale floor, pages starting at or
    after ``start`` reset it) and never rewrite settled earlier pages —
    their untouched write-back is routed to the scratch sink. Returns
    (out [B, C, D], kv').
    """
    B, C = x.shape[:2]
    start = jnp.asarray(start, jnp.int32)
    page_table = jnp.asarray(page_table, jnp.int32)
    page_tokens = kv["k"].shape[2]
    max_pages = page_table.shape[1]
    quantized = "ks" in kv
    q, k, v = _project_qkv(params, cfg, x)
    tok_pos = start + jnp.arange(C)                        # [C]
    positions = jnp.broadcast_to(tok_pos[None, :], (B, C))
    if cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    cols = tok_pos // page_tokens                          # [C]
    in_range = cols < max_pages
    rows = jnp.arange(B)[:, None]                          # [B, 1]
    page_ids = page_table[rows, jnp.minimum(cols, max_pages - 1)[None, :]]
    page_ids = jnp.where(in_range[None, :], page_ids, scratch_page)  # [B, C]
    offs = jnp.broadcast_to((tok_pos % page_tokens)[None, :], (B, C))
    kv = dict(kv)
    if quantized:
        S = max_pages * page_tokens
        col_ids = jnp.arange(max_pages)                     # [maxp]
        # which table columns this chunk writes into (same for all rows:
        # chunked rows share one offset); everything else is settled or
        # empty and must NOT be requantized — route its write-back to the
        # scratch sink instead
        touched = (((col_ids + 1) * page_tokens > start)
                   & (col_ids * page_tokens < start + C))
        write_ids = jnp.where(touched[None, :], page_table, scratch_page)
        frontier = (start + C)
        kpos = jnp.arange(S)
        live = (kpos < frontier)[None, :, None, None]       # [1, S, 1, 1]
        fresh_col = (col_ids * page_tokens >= start)[None, :, None]
        for pk, sk, new in (("k", "ks", k), ("v", "vs", v)):
            view = gather_pages(kv, pk, page_table)         # [B, S, K, Dh]
            # pad by C so an over-the-table chunk spills off the end
            # instead of letting dynamic_update_slice clamp onto live data
            view = jnp.concatenate(
                [view, jnp.zeros((B, C) + view.shape[2:], view.dtype)], 1)
            view = jax.lax.dynamic_update_slice(
                view, new.astype(jnp.float32), (0, start, 0, 0))[:, :S]
            view = jnp.where(live, view, 0.0)               # stale slots → 0
            pages = jnp.swapaxes(
                view.reshape(B, max_pages, page_tokens, *view.shape[2:]),
                2, 3)                                       # head-major
            floor = jnp.where(fresh_col, 0.0, kv[sk][page_table])
            qp, sp = page_quant(pages, kv[pk].dtype, scale_floor=floor)
            kv[pk] = kv[pk].at[write_ids].set(qp)
            kv[sk] = kv[sk].at[write_ids].set(sp)
    else:
        kv["k"] = kv["k"].at[page_ids, :, offs].set(k.astype(kv["k"].dtype))
        kv["v"] = kv["v"].at[page_ids, :, offs].set(v.astype(kv["v"].dtype))
    # gather fallback view [B, max_pages*page_tokens, K, Dh] + causal mask
    ck = gather_pages(kv, "k", page_table)
    cv = gather_pages(kv, "v", page_table)
    S = ck.shape[1]
    mask = _causal_mask(C, S, 0, q_offset=start)
    out = _sdpa(cfg, q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
    y = jnp.einsum("bsq,qm->bsm", out.reshape(B, C, -1),
                   params["wo"].astype(x.dtype))
    return y, kv


def decode_attention(params, cfg, x, kv: dict, pos, *, window: int = 0,
                     impl: str = "xla") -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: [B,1,D]; kv: cache entry (no layer axis), leaves
    [B, S_max, K, Dh] (+ scales). Returns (out [B,1,D], kv').

    ``pos`` is a scalar (whole batch at one position — the one-shot server
    path) or an int32 [B] vector (continuous batching: each cache slot holds
    a different request at its own decode offset). The vector path scatters
    each row's KV at its own slot and builds a per-row validity mask.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    batched_pos = pos.ndim > 0
    q, k, v = _project_qkv(params, cfg, x)
    positions = jnp.broadcast_to(pos.reshape(-1, 1), (B, 1))
    if cfg.use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    S = kv["k"].shape[1]
    if window > 0:
        # ring-buffer write for banded caches
        slot = jnp.mod(pos, S)
    else:
        slot = pos
    new = store_kv(kv, k, v)
    kv = dict(kv)
    for key, val in new.items():
        if batched_pos:
            # per-row scatter: row b writes at its own slot[b]
            kv[key] = kv[key].at[jnp.arange(B), slot].set(val[:, 0])
        else:
            kv[key] = jax.lax.dynamic_update_slice(
                kv[key], val, (0, slot) + (0,) * (kv[key].ndim - 2))
    kpos = jnp.arange(S)[None, :]
    posc = pos.reshape(-1, 1)
    if window > 0:
        # valid = within the last `window` tokens (ring semantics)
        age = jnp.mod(posc - kpos, S)
        valid = (age < jnp.minimum(posc + 1, window))      # [B or 1, S]
    else:
        valid = kpos <= posc                               # [B or 1, S]
    ck, cv = load_kv(kv, q.dtype)
    if impl == "pallas" and not batched_pos:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, ck, cv, valid[0],
                                    softcap=cfg.logit_softcap)
    else:
        mask = valid[:, None, None, :]
        out = _sdpa(cfg, q, ck, cv, mask)
    y = jnp.einsum("bsq,qm->bsm", out.reshape(B, 1, -1),
                   params["wo"].astype(x.dtype))
    return y, kv
