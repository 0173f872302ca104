"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

The decode hot spot (``decode_32k`` / ``long_500k`` shapes) is memory-bound:
arithmetic intensity ≈ 1 FLOP/byte, so the kernel's job is to stream KV from
HBM exactly once at full bandwidth. Layout choice: queries are grouped
``[B, K_kv, G, D]`` (G = H/K query heads per kv head) so one streamed KV
block serves all G query rows — the GQA group rides the MXU's sublane
dimension instead of replicating KV reads G times.

Grid ``(B, K_kv, num_kv_blocks)`` with the KV dimension innermost
(sequential); (m, l, acc) accumulators carry in VMEM scratch — the split-KV
reduction of flash-decode expressed as a sequential grid walk. A ``valid``
f32 vector masks ring-buffer slots / unwritten cache tail.
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


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, softcap: float, skv: int, block_k: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0, 0].astype(jnp.float32)              # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)              # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)              # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (valid_ref[0] > 0.5)[None, :] & (kpos < skv)
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

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_sc[:, :1], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)


def decode_attention(q, k, v, valid, *, softcap: float = 0.0,
                     block_k: int = 512, interpret: bool = False):
    """q: [B,1,H,D]; k,v: [B,S,K,D]; valid: [S] (bool/num). → [B,1,H,D]."""
    B, _, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    assert H % K == 0
    G = H // K
    block_k = min(block_k, max(_LANES, 8))

    qg = q[:, 0].reshape(B, K, G, D)                 # grouped query heads
    kt = jnp.swapaxes(k, 1, 2)                       # [B,K,S,D]
    vt = jnp.swapaxes(v, 1, 2)
    vf = valid.astype(jnp.float32)[None, :]          # [1,S]

    pad = (-S) % block_k
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad)))
    nk = kt.shape[2] // block_k

    kernel = functools.partial(_kernel, scale=1.0 / math.sqrt(D),
                               softcap=softcap, skv=S, block_k=block_k)

    out = pl.pallas_call(
        kernel,
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, g, ik: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, g, ik: (b, g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, g, ik: (b, g, ik, 0)),
            pl.BlockSpec((1, block_k), lambda b, g, ik: (0, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, g, ik: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, _LANES), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rap_decode_attention",
    )(qg, kt, vt, vf)
    return out.reshape(B, 1, H, D)
