"""Paged flash-decode Pallas TPU kernel: one query token vs a paged KV pool.

The dense decode kernel (``decode_attention.py``) streams a *contiguous*
``[B, S, K, D]`` cache — which forces the serving engine to materialize
``max_len × max_active`` slot caches and eat their internal fragmentation.
This kernel's KV operands are instead a **global page pool**
``[n_pages, K, page_tokens, D]`` shared by every in-flight request, plus an
int32 per-request **page table** ``[B, max_pages]``: request ``b``'s tokens
``[ip·page_tokens, (ip+1)·page_tokens)`` live in physical page
``page_table[b, ip]`` (vLLM-block style, one level of indirection).

Pages are **head-major**: one kv head's tokens of one page are a
contiguous ``[page_tokens, D]`` tile, so the K/V block ``(1, 1,
page_tokens, D)`` spans the array's two minor dims in full — the TPU
tiling rule any kv-head count satisfies (a token-major ``[.., pt, K, D]``
page would need a one-head block on the second-minor dim, which Mosaic
refuses for K > 1).

Grid ``(B, K_kv, max_pages)`` with the page dimension innermost
(sequential). The page table and per-request lengths ride
``PrefetchScalarGridSpec`` scalar prefetch, so the K/V BlockSpec *index
maps* chase the table — ``(page_table[b, ip], g, 0, 0)`` — and the pages
DMA straight from wherever they physically sit; no gather materializes a
contiguous cache. Quantized pools' per-page scales are NOT scalar
prefetched (SMEM would then grow with the pool): each request's scale
rows are gathered through its table outside the kernel and arrive as a
small VMEM block. The (m, l, acc) online-softmax scratch carry is
identical to the dense kernel's split-KV reduction, so with
``page_tokens == block_k`` and an in-order page table the two kernels
execute the *same* f32 op sequence and agree **bitwise** (pinned in
``tests/test_kernels.py``).

Rows needing fewer than ``max_pages`` pages pad their table row with any
valid page id (0 by convention); the ``kpos < length[b]`` mask turns those
blocks into exact no-ops (``acc·1 + 0``) without branching.

On CPU/tests the kernel runs in ``interpret=True`` mode (the
``pallas-interpret`` CI job); the XLA fallback for production CPU serving
lives in ``repro.models.attention.paged_decode_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
_LANES = 128


def _flash_step(b, ip, n_ip, q, k, v, len_ref, o_ref, m_sc, l_sc, acc_sc,
                *, scale: float, softcap: float, page_tokens: int):
    """One page's online-softmax update — shared verbatim by the plain and
    quantized kernels so dequantization cannot perturb the (m, l, acc)
    op sequence the bitwise conformance pins."""
    @pl.when(ip == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    kpos = ip * page_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < len_ref[b]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = jnp.broadcast_to(
        alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_sc.shape)
    acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)

    @pl.when(ip == n_ip - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, :1], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
            *, scale: float, softcap: float, page_tokens: int):
    b = pl.program_id(0)
    ip = pl.program_id(2)
    n_ip = pl.num_programs(2)
    q = q_ref[0, 0].astype(jnp.float32)              # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)              # [page_tokens, D]
    v = v_ref[0, 0].astype(jnp.float32)
    _flash_step(b, ip, n_ip, q, k, v, len_ref, o_ref, m_sc, l_sc, acc_sc,
                scale=scale, softcap=softcap, page_tokens=page_tokens)


def _kernel_quant(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, m_sc, l_sc, acc_sc, *, scale: float, softcap: float,
                  page_tokens: int):
    """Fused-dequant variant: pages arrive int8/fp8 and each tile is
    widened and multiplied by its (page, kv-head) scale —
    ``q.astype(f32) * scale``, exactly mirroring
    ``models.attention.page_dequant``. The scales arrive as one VMEM row
    per (request, kv-head) holding the row's per-page scales in table
    order; the one-hot lane sum that picks page ``ip``'s scale adds only
    exact zeros, so the multiply sees the stored f32 value. The (m, l,
    acc) scratch stays fp32 via the shared ``_flash_step``."""
    b = pl.program_id(0)
    ip = pl.program_id(2)
    n_ip = pl.num_programs(2)

    def page_scale(ref):
        row = ref[0, 0]                                # [1, max_pages]
        hit = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == ip
        return jnp.sum(jnp.where(hit, row, 0.0), axis=1, keepdims=True)

    q = q_ref[0, 0].astype(jnp.float32)              # [G, D]
    k = k_ref[0, 0].astype(jnp.float32) * page_scale(ks_ref)
    v = v_ref[0, 0].astype(jnp.float32) * page_scale(vs_ref)
    _flash_step(b, ip, n_ip, q, k, v, len_ref, o_ref, m_sc, l_sc, acc_sc,
                scale=scale, softcap=softcap, page_tokens=page_tokens)


def grid(batch: int, kv_heads: int, max_pages: int) -> Tuple[int, int, int]:
    """The kernel's grid: each (row, kv head) walks all ``max_pages``
    entries of its page-table row, whatever the row's length."""
    return (batch, kv_heads, max_pages)


def pages_walked(batch: int, max_pages: int) -> int:
    """Pages one call visits per kv head, as :func:`grid` walks them."""
    rows, _, pages = grid(batch, 1, max_pages)
    return rows * pages


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scales=None, v_scales=None,
                           softcap: float = 0.0, interpret: bool = False):
    """q: [B,1,H,D]; k_pages/v_pages: [n_pages, K, page_tokens, D];
    page_table: int32 [B, max_pages]; lengths: int32 [B]. → [B,1,H,D].

    Row ``b`` attends its first ``lengths[b]`` tokens, token ``t`` of kv
    head ``g`` living at ``(page_table[b, t // page_tokens], g,
    t % page_tokens)``. Unused table entries must still be valid page ids
    (they are fetched, then masked).

    ``k_scales``/``v_scales`` (f32 ``[n_pages, K]``, both or neither)
    switch on the fused-dequant path for int8/fp8 page pools: each
    request's scale rows are gathered through its page table (``[B, K,
    max_pages]``, independent of the pool size) and each K/V tile is
    multiplied by its page's per-head scale before the fp32 online
    softmax.
    """
    B, _, H, D = q.shape
    K, page_tokens = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    assert H % K == 0
    G = H // K
    quantized = k_scales is not None
    assert quantized == (v_scales is not None), \
        "k_scales and v_scales must be given together"

    qg = q[:, 0].reshape(B, K, G, D)                 # grouped query heads
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    # scalar prefetch (page table + lengths) leads the positional args;
    # BlockSpec index maps receive those refs after the grid ids
    q_map = lambda b, g, ip, tab, ln: (b, g, 0, 0)
    kv_map = lambda b, g, ip, tab, ln: (tab[b, ip], g, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, G, D), q_map),
        pl.BlockSpec((1, 1, page_tokens, D), kv_map),
        pl.BlockSpec((1, 1, page_tokens, D), kv_map),
    ]
    operands = [qg, k_pages, v_pages]
    body = _kernel
    if quantized:
        body = _kernel_quant
        # [B, K, 1, max_pages]: the trailing (1, max_pages) block spans
        # the full dims, and the block index is constant along the page
        # walk, so each (row, head) fetches its scales once
        for s in (k_scales, v_scales):
            rows = jnp.asarray(s, jnp.float32)[page_table]   # [B, maxp, K]
            operands.append(jnp.transpose(rows, (0, 2, 1))[:, :, None, :])
            in_specs.append(pl.BlockSpec((1, 1, 1, max_pages), q_map))
    kernel = functools.partial(body, scale=1.0 / math.sqrt(D),
                               softcap=softcap, page_tokens=page_tokens)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid(B, K, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=("rap_paged_decode_attention_quant" if quantized
              else "rap_paged_decode_attention"),
    )(page_table, lengths, *operands)
    return out.reshape(B, 1, H, D)
