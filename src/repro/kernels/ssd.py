"""Mamba-2 SSD chunk-scan Pallas TPU kernel.

State-space duality splits the sequence into chunks of Q tokens: inside a
chunk the recurrence is the quadratic masked form (three MXU matmuls —
C·Bᵀ, the decay-weighted combine, and the input→state projection); across
chunks a rank-preserving [P,N] state carries. Grid ``(B, H, num_chunks)``
with chunks innermost: the state lives in VMEM scratch across the
sequential chunk walk, so HBM sees each token exactly once (the GPU
implementation's shared-memory chunk buffer maps onto the VMEM-resident
block; the warp-level parallel scan maps onto the sequential-grid carry,
which is the TPU-native form of the same dataflow).

VMEM per cell at (Q=256, N=128, P=64): xh 64K + B/C 2·128K + L 256K +
state 32K  ≈ 0.6 MB f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xh_ref, la_ref, b_ref, c_ref, y_ref, fin_ref, st_ref, *,
            block_q: int):
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    xh = xh_ref[0, 0].astype(jnp.float32)            # [Q, P]
    la = la_ref[0, 0].astype(jnp.float32)            # [1, Q]
    Bm = b_ref[0].astype(jnp.float32)                # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                # [Q, N]

    a_cum = jnp.cumsum(la[0])                        # [Q]
    # intra-chunk decay L[q,s] = exp(a_cum[q]-a_cum[s]) for s<=q
    seg = a_cum[:, None] - a_cum[None, :]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1))
    L = jnp.where(causal, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q,Q]
    y_diag = jax.lax.dot_general(scores * L, xh, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q,P]

    # off-diagonal: contribution of the carried state
    state = st_ref[...]                              # [P, N]
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # [Q,P]
    y_off = y_off * jnp.exp(a_cum)[:, None]
    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: decay full chunk + input→state projection
    total = a_cum[block_q - 1]
    decay_in = jnp.exp(total - a_cum)                # [Q]
    bx = jax.lax.dot_general(xh * decay_in[:, None], Bm,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)      # [P,N]
    st_ref[...] = state * jnp.exp(total) + bx

    @pl.when(c_idx == nc - 1)
    def _emit_final():
        fin_ref[0, 0] = st_ref[...]


def ssd(xh, log_a, Bm, Cm, chunk: int = 256, *, interpret: bool = False):
    """Chunked SSD. xh: [B,T,H,P]; log_a: [B,T,H]; Bm/Cm: [B,T,N].

    Returns (y [B,T,H,P] f32, final_state [B,H,P,N] f32) — matches
    ``repro.models.ssm._ssd_scan`` and the ``ssd_ref`` oracle.
    """
    B, T, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, T)
    pad = (-T) % Q
    if pad:  # decay-1 / zero-input padding is state-neutral
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    nc = Tp // Q

    xh_t = jnp.transpose(xh, (0, 2, 1, 3))           # [B,H,T,P]
    la_t = jnp.transpose(log_a, (0, 2, 1))[:, :, None, :]  # [B,H,1,T]

    y, fin = pl.pallas_call(
        functools.partial(_kernel, block_q=Q),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rap_ssd",
    )(xh_t, la_t, Bm, Cm)
    y = jnp.transpose(y, (0, 2, 1, 3))[:, :T]
    return y, fin
