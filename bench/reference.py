"""What every family's plain reference shares, in float32.

It imports nothing of the program. A family module
(``bench/families/<family>.py``) writes its own weight draw and layer
equations (its ``hidden`` function: final hidden states of one sequence
under the request's gates) and hands them to what is here: the weight
draw's helpers (the program's ``init`` recipe for one matrix, the
benchmark's perturbation of norms, biases and vocabulary padding), RMSNorm,
RoPE, the fp8 rounding of the control, and the teacher-forced gap loop
that applies the head in vocabulary chunks at ``precision="highest"``.

Weights stay in bfloat16, as served, and each layer is widened to float32
inside the family's layer loop, so the reference fits beside nothing else
on the chip: run it after the program's state is freed.

The control (``control_gaps``) is the reference put in the program's
place one precision down: every matmul weight rounded to float8_e4m3fn
with a scale per output column (fp8 below the configuration's bfloat16),
the rest as in the reference. At each position of the same prompts and
served tokens it picks its own best token; the float32 reference's gap of
that token is the control's reading.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PERTURB_STD = 0.1        # norm scales, q/k/v biases, qk-norm scales
SEQ_BUCKET = 1024        # sequences pad to a multiple (one compile each)
Q_CHUNK = 512            # query rows per attention block


def _dense(key, n_in: int, n_out: int, scale: float = 1.0):
    std = scale / math.sqrt(n_in)
    w = jax.random.truncated_normal(key, -2.0, 2.0, (n_in, n_out),
                                    jnp.float32) * std
    return w.astype(jnp.bfloat16)


def perturb_key(key, name: str):
    """The key of one perturbed leaf, from its canonical name."""
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def perturbed(key, name: str, shape) -> jnp.ndarray:
    return (jax.random.normal(perturb_key(key, name), shape, jnp.float32)
            * PERTURB_STD).astype(jnp.bfloat16)


def split_seed_key(key):
    """(key of the program's init draw, key of the perturbation)."""
    k_init, k_pert = jax.random.split(key)
    return k_init, k_pert


def _rms(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s.astype(jnp.float32))


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * freqs     # [T, 1, dh/2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


FP8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)


def _fp8(w):
    """``w`` [n_in, n_out] rounded to float8_e4m3fn with a scale per output
    column, back in float32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _widen(lowp: bool):
    """How a stored matmul weight enters the computation."""
    if lowp:
        return _fp8
    return lambda a: a.astype(jnp.float32)


def _vocab_chunk(vp: int, cap: int = 16384) -> int:
    for c in range(min(cap, vp), 0, -1):
        if vp % c == 0:
            return c
    return vp


def _gaps(m: Dict, w, hrows, targets, lowp: bool = False):
    """Per row: (best logit − logit of ``targets``, argmax) over the real
    vocabulary, the head applied in vocabulary chunks."""
    V, Vp = m["vocab_size"], m["vocab_padded"]
    C = _vocab_chunk(Vp)
    n = hrows.shape[0]
    mat = _widen(lowp)

    def body(i, carry):
        best, arg, tgt = carry
        cols = jax.lax.dynamic_slice_in_dim(w["head"], i * C, C, axis=1)
        lg = hrows @ mat(cols)                                  # [n, C]
        ids = i * C + jnp.arange(C)
        lg = jnp.where(ids[None, :] < V, lg, -jnp.inf)
        cmax = jnp.max(lg, axis=1)
        carg = i * C + jnp.argmax(lg, axis=1)
        arg = jnp.where(cmax > best, carg, arg)
        best = jnp.maximum(best, cmax)
        hit = (targets >= i * C) & (targets < (i + 1) * C)
        tl = jnp.take_along_axis(
            lg, jnp.clip(targets - i * C, 0, C - 1)[:, None], axis=1)[:, 0]
        tgt = jnp.where(hit, tl, tgt)
        return best, arg, tgt

    init = (jnp.full((n,), -jnp.inf), jnp.zeros((n,), jnp.int32),
            jnp.full((n,), -jnp.inf))
    best, arg, tgt = jax.lax.fori_loop(0, Vp // C, body, init)
    return best - tgt, arg


# a family's ``hidden(m, w, tokens, g_mixer, g_ffn, lowp)``: the final
# hidden states [T, d] (float32) of one sequence, every matmul weight
# entering through ``_widen(lowp)``
Hidden = Callable[..., jnp.ndarray]


def _make_fns(m: Dict, hidden: Hidden, lowp: bool = False):
    @jax.jit
    def gap_fn(w, tokens, gm, gf, rows, targets):
        with jax.default_matmul_precision("highest"):
            h = hidden(m, w, tokens, gm, gf, lowp)
            return _gaps(m, w, h[rows], targets, lowp)
    return gap_fn


Item = Tuple[np.ndarray, np.ndarray, np.ndarray]


def served_gaps(m: Dict, w, items: List[Item],
                hidden: Hidden) -> List[np.ndarray]:
    """For each (prompt [S], served tokens [n], mask [2L]): the gap, per
    served token, between the reference's best logit and the served
    token's logit at the position that produced it (teacher forced over
    prompt + served tokens)."""
    gap_fn = _make_fns(m, hidden)
    return [g for g, _ in _run(m, w, gap_fn, items)]


def control_gaps(m: Dict, w, items: List[Item],
                 hidden: Hidden) -> List[np.ndarray]:
    """The control's readings on the same items: at each position, the
    float32 reference's gap of the token the fp8 reference puts first."""
    picks = [arg for _, arg in _run(m, w, _make_fns(m, hidden, lowp=True),
                                    items)]
    return [g for g, _ in _run(m, w, _make_fns(m, hidden), items, picks)]


def _run(m: Dict, w, gap_fn, items: List[Item], targets=None):
    """(gap of the target, best token) per served position of each item,
    teacher forced over prompt + served tokens; the targets are the served
    tokens unless ``targets`` gives others."""
    L = m["n_layers"]
    out = []
    for i, (prompt, served, mask) in enumerate(items):
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        T = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        toks = np.zeros((T,), np.int32)
        toks[:len(seq)] = seq
        S, n = len(prompt), len(served)
        rows = np.arange(S - 1, S - 1 + n, dtype=np.int32)
        # rows pad to a bucket too, so the gather's shape repeats
        R = -(-n // SEQ_BUCKET) * SEQ_BUCKET
        rows_p = np.full((R,), S - 1, np.int32)
        rows_p[:n] = rows
        tg = np.zeros((R,), np.int32)
        tg[:n] = served if targets is None else targets[i]
        mask = np.asarray(mask, np.float32)
        g, arg = gap_fn(w, jnp.asarray(toks), jnp.asarray(mask[:L]),
                        jnp.asarray(mask[L:]), jnp.asarray(rows_p),
                        jnp.asarray(tg))
        out.append((np.asarray(g)[:n], np.asarray(arg)[:n]))
    return out
