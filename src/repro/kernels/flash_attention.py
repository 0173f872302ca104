"""Flash attention (prefill/train) Pallas TPU kernel.

Streaming-softmax tiling: grid ``(B, H, num_q_blocks, num_kv_blocks)`` with
the KV dimension innermost — TPU grids execute the last dimension
sequentially, so the (m, l, acc) accumulators live in VMEM scratch and carry
across KV steps. Block sizes default to 128×128 (MXU-aligned); the working
set per grid cell is

    q (bq·D) + k,v (2·bk·D) + acc (bq·D f32) + s/p (bq·bk f32)  ≈ 0.4 MB

well inside a v5e core's VMEM. GQA is handled in the k/v ``index_map``
(query head h reads kv head ``h // G``) so no KV replication is ever
materialized. Causal masking is iota-based inside the block; fully-masked
blocks above the diagonal skip their matmuls via ``pl.when``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, window: int, softcap: float,
            sq: int, skv: int, block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * block_q
    k_start = ik * block_k

    # Skip blocks strictly above the causal diagonal (or left of the band).
    run = jnp.asarray(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window > 0:
        run = run & (k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = kpos < skv                            # KV padding
        mask = mask & (qpos < sq)                    # Q padding
        if causal:
            mask = mask & (kpos <= qpos)
        if window > 0:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_sc[:, :1]                         # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)    # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)              # [bq, 1]
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_sc[:, :1]
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B,Sq,H,D]; k,v: [B,Skv,K,D]. Returns [B,Sq,H,D] (q.dtype)."""
    B, Sq, H, D = q.shape
    Skv, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    block_q = min(block_q, max(8, Sq))
    block_k = min(block_k, max(_LANES, 8))

    qt = jnp.swapaxes(q, 1, 2)                       # [B,H,Sq,D]
    kt = jnp.swapaxes(k, 1, 2)                       # [B,K,Skv,D]
    vt = jnp.swapaxes(v, 1, 2)

    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qt.shape[2] // block_q
    nk = kt.shape[2] // block_k

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(D), causal=causal, window=window,
        softcap=softcap, sq=Sq, skv=Skv, block_q=block_q, block_k=block_k)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="rap_flash_attention",
    )(qt, kt, vt)
    out = out[:, :, :Sq, :] if pad_q else out
    return jnp.swapaxes(out, 1, 2)
