"""Paged flash-decode Pallas TPU kernel: one query token vs a paged KV pool.

The dense decode kernel (``decode_attention.py``) streams a *contiguous*
``[B, S, K, D]`` cache — which forces the serving engine to materialize
``max_len × max_active`` slot caches and eat their internal fragmentation.
This kernel's KV operands are instead a **global page pool**
``[n_pages, K, page_tokens, D]`` shared by every in-flight request, plus an
int32 per-request **page table** ``[B, max_pages]``: request ``b``'s tokens
``[ip·page_tokens, (ip+1)·page_tokens)`` live in physical page
``page_table[b, ip]`` (vLLM-block style, one level of indirection).

Pages are **head-major**: one page's tokens of every kv head form one
contiguous ``[K, page_tokens, D]`` tile, so a page travels HBM → VMEM as
a single DMA whatever the kv-head count.

Grid ``(B,)``, one step per row, sequential (``"arbitrary"``: a v5e has
one TensorCore, and the order lets a row prefetch the next one's pages).
Inside the step a ``fori_loop`` walks the row's length only: block ``i``
holds pages ``[i·ppb, (i+1)·ppb)`` of the row, ``ppb`` =
:func:`pages_per_block`, so a row of ``n`` tokens runs ``⌈n / (ppb ·
page_tokens)⌉`` blocks and a row of length 0 none. The pools stay in
HBM (``memory_space=pl.ANY``); each block's pages are copied, one DMA per
page per pool, into a double-buffered VMEM scratch, and block ``i+1`` —
or at a row's last block, the next row's first — is in flight while
block ``i`` computes. The page table (flattened) and the lengths ride
``PrefetchScalarGridSpec`` scalar prefetch and drive the copies' source
addresses; no gather materializes a contiguous cache.

Table entries past a row's length are never read: the last block's
slots past the row's last page — those past the table's end too, when
``ppb`` does not divide ``max_pages`` — re-copy that page (clamped
column), and the ``kpos < length`` mask turns their tokens, like the
last page's own tail, into exact no-ops. :func:`pages_walked` counts
the copies: whole blocks.

Quantized pools' per-page scales are NOT scalar prefetched (SMEM would
then grow with the pool): each request's scale rows are gathered through
its table outside the kernel and arrive as a small VMEM block. The (m,
l, acc) online-softmax update per block is the dense kernel's split-KV
reduction, so with ``block_k == ppb · page_tokens`` and an in-order page
table the two kernels execute the *same* f32 op sequence and agree
**bitwise** (pinned in ``tests/test_kernels.py``).

On CPU/tests the kernel runs in ``interpret=True`` mode (the
``pallas-interpret`` CI job); the XLA fallback for production CPU serving
lives in ``repro.models.attention.paged_decode_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
_LANES = 128
# a block holds at least this many bytes of each pool, so its DMAs are
# large next to the cost of starting them (and at least _LANES tokens,
# so a score row fills the vector lanes) ...
_BLOCK_BYTES = 64 * 1024
# ... and the double-buffered K + V scratch stays within this much VMEM
_SCRATCH_BYTES = 4 * 1024 * 1024


def pages_per_block(kv_heads: int, page_tokens: int, head_dim: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages one walk step copies per pool, from the page's shape alone:
    enough for ``_BLOCK_BYTES`` and for a lane-wide score row (``_LANES``
    tokens), within ``_SCRATCH_BYTES`` for two K and two V buffers, and
    no more than the table holds."""
    page = kv_heads * page_tokens * head_dim * itemsize
    n = max(-(-_BLOCK_BYTES // page), -(-_LANES // page_tokens))
    n = min(n, _SCRATCH_BYTES // (4 * page))
    return max(1, min(n, max_pages))


def pages_walked(lengths: Iterable[int], page_tokens: int, max_pages: int,
                 ppb: int) -> int:
    """Pages one call copies per pool for rows of these ``lengths``: each
    row's pages with tokens (at most the table's ``max_pages``) rounded
    up to whole blocks of ``ppb``."""
    total = 0
    for n in lengths:
        pages = min(-(-int(n) // page_tokens), max_pages)
        total += -(-pages // ppb) * ppb
    return total


def _online_update(q, k, v, kpos0, length, m_ref, l_ref, acc_ref, *,
                   scale: float, softcap: float):
    """One block's online-softmax update, op for op the dense kernel's
    (``decode_attention._kernel``) so the bitwise conformance holds."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < length
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)


def _kernel(tab_ref, len_ref, q_ref, k_hbm, v_hbm, *refs, scale: float,
            softcap: float, page_tokens: int, max_pages: int, ppb: int,
            quantized: bool):
    """One row: walk its blocks, double-buffered across rows.

    ``slot_ref`` (SMEM) carries the buffer the next block lands in from
    one grid step to the next: a row's last block starts the next row's
    first (an empty row's step starts the one after), so at a step's
    entry its first block is already in flight."""
    if quantized:
        ks_ref, vs_ref, o_ref, *refs, deq = refs
    else:
        o_ref, *refs = refs
    k_buf, v_buf, sems, slot_ref, m_sc, l_sc, acc_sc = refs
    b = pl.program_id(0)
    B = pl.num_programs(0)
    K = k_buf.shape[1]
    bk = ppb * page_tokens

    def row_pages(r):                         # pages holding row r's tokens
        return jnp.minimum(
            jax.lax.div(len_ref[r] + page_tokens - 1, page_tokens),
            max_pages)

    def copies(r, blk, slot):
        """The DMAs of row ``r``'s block ``blk`` into buffer ``slot``, one
        per page per pool; slots past the row's last page re-copy it."""
        last = row_pages(r) - 1
        out = []
        for j in range(ppb):
            page = tab_ref[r * max_pages + jnp.minimum(blk * ppb + j, last)]
            dst = pl.ds(j * page_tokens, page_tokens)
            for i, (src, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                out.append(pltpu.make_async_copy(
                    src.at[page], buf.at[slot, :, dst], sems.at[i, slot]))
        return out

    def start(r, blk, slot):
        for d in copies(r, blk, slot):
            d.start()

    def start_next_row(slot):
        nxt = jnp.minimum(b + 1, B - 1)

        @pl.when((b + 1 < B) & (len_ref[nxt] > 0))
        def _():
            start(nxt, 0, slot)

    n_row = jax.lax.div(row_pages(b) + ppb - 1, ppb)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0

        @pl.when(n_row > 0)
        def _():
            start(b, 0, 0)

    @pl.when(n_row == 0)
    def _():
        start_next_row(slot_ref[0])

    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    length = len_ref[b]

    def page_scale(ref, g, col):
        """Page ``col``'s scale of kv head ``g``: a one-hot lane sum over
        the row's scales, adding only exact zeros."""
        row = ref[0, g:g + 1, :]                       # [1, max_pages]
        hit = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == col
        return jnp.sum(jnp.where(hit, row, 0.0), axis=1, keepdims=True)

    def load(buf, scales, slot, g, blk):
        """Kv head ``g``'s tokens of the block as f32 ``[bk, D]``;
        quantized pages are widened and multiplied by their page's scale,
        exactly as ``models.attention.page_dequant``."""
        x = buf[slot, g].astype(jnp.float32)
        if scales is None:
            return x
        pages = row_pages(b)
        col = jnp.concatenate([
            jnp.broadcast_to(page_scale(
                scales, g, jnp.minimum(blk * ppb + j, pages - 1)),
                (page_tokens, 1))
            for j in range(ppb)], axis=0)                   # [bk, 1]
        # through VMEM at a traced index, so that in interpret mode the
        # dot reads a plain array as on the fp32 path: XLA:CPU fuses a
        # multiply into a matrix-vector dot and sums in another order
        deq[slot] = x * col
        return deq[slot]

    def body(blk, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(blk + 1 < n_row)
        def _():
            start(b, blk + 1, nxt)

        @pl.when(blk + 1 == n_row)
        def _():
            start_next_row(nxt)

        for d in copies(b, blk, slot):
            d.wait()
        for g in range(K):
            q = q_ref[0, g].astype(jnp.float32)            # [G, D]
            k = load(k_buf, ks_ref if quantized else None, slot, g, blk)
            v = load(v_buf, vs_ref if quantized else None, slot, g, blk)
            _online_update(q, k, v, blk * bk, length, m_sc.at[g],
                           l_sc.at[g], acc_sc.at[g], scale=scale,
                           softcap=softcap)
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_row, body, 0)
    for g in range(K):
        l = jnp.maximum(l_sc[g, :, :1], 1e-30)
        o_ref[0, g] = (acc_sc[g] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scales=None, v_scales=None,
                           softcap: float = 0.0, interpret: bool = False):
    """q: [B,1,H,D]; k_pages/v_pages: [n_pages, K, page_tokens, D];
    page_table: int32 [B, max_pages]; lengths: int32 [B]. → [B,1,H,D].

    Row ``b`` attends its first ``lengths[b]`` tokens, token ``t`` of kv
    head ``g`` living at ``(page_table[b, t // page_tokens], g,
    t % page_tokens)``. Table entries past a row's length are never read
    and may hold anything.

    ``k_scales``/``v_scales`` (f32 ``[n_pages, K]``, both or neither)
    switch on the fused-dequant path for int8/fp8 page pools: each
    request's scale rows are gathered through its page table (``[B, K,
    max_pages]``, independent of the pool size) and each page's K/V tile
    is multiplied by its per-head scale before the fp32 online softmax.
    """
    B, _, H, D = q.shape
    K, page_tokens = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    assert H % K == 0
    G = H // K
    quantized = k_scales is not None
    assert quantized == (v_scales is not None), \
        "k_scales and v_scales must be given together"
    ppb = pages_per_block(K, page_tokens, D, k_pages.dtype.itemsize,
                          max_pages)

    qg = q[:, 0].reshape(B, K, G, D)                 # grouped query heads
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)

    # scalar prefetch (page table + lengths) leads the positional args;
    # BlockSpec index maps receive those refs after the grid id
    row_map = lambda b, tab, ln: (b, 0, 0, 0)
    in_specs = [pl.BlockSpec((1, K, G, D), row_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # [B, K, max_pages]: one row's block spans the two minor dims
        for s in (k_scales, v_scales):
            rows = jnp.asarray(s, jnp.float32)[page_table]   # [B, maxp, K]
            operands.append(jnp.transpose(rows, (0, 2, 1)))
            in_specs.append(pl.BlockSpec((1, K, max_pages),
                                         lambda b, tab, ln: (b, 0, 0)))
    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(D),
                               softcap=softcap, page_tokens=page_tokens,
                               max_pages=max_pages, ppb=ppb,
                               quantized=quantized)
    buf = (2, K, ppb * page_tokens, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K, G, D), row_map),
        scratch_shapes=[
            pltpu.VMEM(buf, k_pages.dtype),
            pltpu.VMEM(buf, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),         # (pool, buffer)
            pltpu.SMEM((1,), jnp.int32),             # buffer of next block
            pltpu.VMEM((K, G, _LANES), jnp.float32),
            pltpu.VMEM((K, G, _LANES), jnp.float32),
            pltpu.VMEM((K, G, D), jnp.float32),
        ] + ([pltpu.VMEM((2, ppb * page_tokens, D), jnp.float32)]
             if quantized else []),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=("rap_paged_decode_attention_quant" if quantized
              else "rap_paged_decode_attention"),
    )(page_table.reshape(-1), lengths, *operands)
    return out.reshape(B, 1, H, D)
