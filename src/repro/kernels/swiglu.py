"""Fused GLU-gate Pallas TPU kernel (SwiGLU / GeGLU).

The FFN hot spot: ``wi`` produces a fused ``[T, 2F]`` (gate|up) activation.
Materializing silu(gate) and the product separately costs three HBM
round-trips of a ``[B,S,d_ff]`` tensor; this kernel reads each element once
and writes the ``[T, F]`` product once — both halves of the fused tensor are
addressed by ``index_map`` offsets into the *same* input array, so the gate
half (block column j) and the up half (block column j + F/bf) stream
together through VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(gate_ref, up_ref, o_ref, *, activation: str):
    g = gate_ref[...].astype(jnp.float32)
    u = up_ref[...].astype(jnp.float32)
    if activation == "swiglu":
        a = g * jax.nn.sigmoid(g)
    else:  # geglu
        a = jax.nn.gelu(g, approximate=True)
    o_ref[...] = (a * u).astype(o_ref.dtype)


def fused_glu(h, activation: str = "swiglu", *, block_t: int = 256,
              block_f: int = 512, interpret: bool = False):
    """h: [..., 2F] fused (gate, up) → [..., F] (h.dtype)."""
    orig_shape = h.shape
    F = orig_shape[-1] // 2
    x = h.reshape(-1, 2 * F)
    T = x.shape[0]
    block_t = min(block_t, max(T, 8))
    block_f = min(block_f, F)
    while F % block_f != 0:          # F is 128-aligned for every real config
        block_f //= 2
    block_f = max(block_f, 1)
    pad_t = (-T) % block_t
    if pad_t:
        x = jnp.pad(x, ((0, pad_t), (0, 0)))
    nt, nf = x.shape[0] // block_t, F // block_f
    off = F // block_f               # up half starts nf block-columns later

    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid=(nt, nf),
        in_specs=[
            pl.BlockSpec((block_t, block_f), lambda i, j: (i, j)),
            pl.BlockSpec((block_t, block_f),
                         lambda i, j, off=off: (i, j + off)),
        ],
        out_specs=pl.BlockSpec((block_t, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], F), h.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="rap_fused_glu",
    )(x, x)
    if pad_t:
        out = out[:T]
    return out.reshape(*orig_shape[:-1], F)
