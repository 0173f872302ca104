"""RG-LRU blocked linear-recurrence Pallas TPU kernel (Griffin).

h_t = a_t ⊙ h_{t-1} + b_t over T, diagonal per channel. The recurrence is
bandwidth-bound; the kernel tiles the channel axis (width blocks ride the
VPU lanes) and walks the time axis in blocks of ``block_t``: inside a block
an associative scan does log₂(block_t) vectorized passes in VMEM, and the
carried hidden state h stitches consecutive blocks:

    h_t = Bscan_t + Ascan_t · h_carry      (Ascan = running ∏a, Bscan = scan of b)

Grid ``(B, W/bw, T/bt)`` with time innermost (sequential) so the [1, bw]
carry lives in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_ref, carry_ref):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0].astype(jnp.float32)                 # [bt, bw]
    b = b_ref[0].astype(jnp.float32)

    def combine(p, q):
        a1, b1 = p
        a2, b2 = q
        return a1 * a2, a2 * b1 + b2

    A, Bs = jax.lax.associative_scan(combine, (a, b), axis=0)
    h = Bs + A * carry_ref[...]                      # [bt, bw]
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = h[-1:, :]


def rglru(a, b, *, block_t: int = 256, block_w: int = 512,
          interpret: bool = False):
    """a, b: [B,T,W] f32 → h [B,T,W] f32 (matches ``rglru_ref``)."""
    B, T, W = a.shape
    block_t = min(block_t, T)
    block_w = min(block_w, W)
    while W % block_w != 0:
        block_w //= 2
    block_w = max(block_w, 1)
    pad_t = (-T) % block_t
    if pad_t:  # a=1,b=0 padding is state-neutral; padded rows sliced off
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, 0)))
    nt = a.shape[1] // block_t
    nw = W // block_w

    h = pl.pallas_call(
        _kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda bb, iw, it: (bb, it, iw)),
            pl.BlockSpec((1, block_t, block_w), lambda bb, iw, it: (bb, it, iw)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_w),
                               lambda bb, iw, it: (bb, it, iw)),
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rap_rglru",
    )(a, b)
    return h[:, :T] if pad_t else h
