"""Plain reference of a uniform dense GQA decoder, in float32.

It imports nothing of the program. It rebuilds the weights from the seed
with its own copy of the draw the benchmark gives the program (the
program's ``init`` recipe: the same key splits and distributions, then the
benchmark's own perturbation of norms, biases and vocabulary padding), and
computes the published layer equations at ``precision="highest"``:

    h  = embed[tokens]
    per layer i (gates g_attn[i], g_ffn[i] in {0, 1}, the request's mask):
      x  = rmsnorm(h) * (1 + s_attn[i])
      q, k, v = x Wq + bq, x Wk + bk, x Wv + bv        (GQA: K kv heads)
      q, k = rmsnorm_head(q) * (1 + s_q), ... (qk-norm models only)
      q, k = rope(q), rope(k)                           (whole head, theta)
      h  = h + g_attn[i] * softmax_causal(q k^T / sqrt(Dh)) v Wo
      x  = rmsnorm(h) * (1 + s_ffn[i])
      h  = h + g_ffn[i] * (silu(x Wg) * (x Wu)) Wd
    logits = (rmsnorm(h) * (1 + s_final)) Whead       (real vocabulary only)

Weights stay in bfloat16, as served, and each layer is widened to float32
inside the layer loop, so the reference fits beside nothing else on the
chip: run it after the program's state is freed.

The control (``control_gaps``) is this reference put in the program's
place one precision down: every matmul weight rounded to float8_e4m3fn
with a scale per output column (fp8 below the configuration's bfloat16),
the rest as above. At each position of the same prompts and served tokens
it picks its own best token; the float32 reference's gap of that token is
the control's reading.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Tuple

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


def init_weights(m: Dict, key) -> Dict[str, jnp.ndarray]:
    """The weights the benchmark serves, as flat named stacks."""
    L, d, H, K, Dh, F = (m["n_layers"], m["d_model"], m["n_heads"],
                         m["n_kv_heads"], m["head_dim"], m["d_ff"])
    V, Vp = m["vocab_size"], m["vocab_padded"]
    k_init, k_pert = split_seed_key(key)
    k_embed, k_head, k_rest = jax.random.split(k_init, 3)
    w: Dict[str, jnp.ndarray] = {}
    w["embed"] = (jax.random.normal(k_embed, (Vp, d), jnp.float32)
                  * 0.02).astype(jnp.bfloat16)
    w["head"] = _dense(k_head, d, Vp)
    k_attn, k_ffn = jax.random.split(k_rest, 2)   # sorted kinds: attn, dense
    out_scale = 1.0 / math.sqrt(2 * max(L, 1))

    def attn_layer(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {"wq": _dense(k1, d, H * Dh), "wk": _dense(k2, d, K * Dh),
                "wv": _dense(k3, d, K * Dh),
                "wo": _dense(k4, H * Dh, d, scale=out_scale)}

    def ffn_layer(k):
        k1, k2 = jax.random.split(k)
        return {"wi": _dense(k1, d, 2 * F),
                "wd": _dense(k2, F, d, scale=out_scale)}

    w.update(jax.vmap(attn_layer)(jax.random.split(k_attn, L)))
    w.update(jax.vmap(ffn_layer)(jax.random.split(k_ffn, L)))
    # the benchmark's perturbation: norms, biases, qk-norm scales drawn
    # instead of zero; padded vocabulary rows and columns zero
    w["s_attn"] = perturbed(k_pert, "stacks/attn/norm/scale", (L, d))
    w["s_ffn"] = perturbed(k_pert, "stacks/dense/norm/scale", (L, d))
    w["s_final"] = perturbed(k_pert, "final_norm/scale", (d,))
    if m["qkv_bias"]:
        w["bq"] = perturbed(k_pert, "stacks/attn/bq", (L, H * Dh))
        w["bk"] = perturbed(k_pert, "stacks/attn/bk", (L, K * Dh))
        w["bv"] = perturbed(k_pert, "stacks/attn/bv", (L, K * Dh))
    if m["qk_norm"]:
        w["s_q"] = perturbed(k_pert, "stacks/attn/q_norm", (L, Dh))
        w["s_k"] = perturbed(k_pert, "stacks/attn/k_norm", (L, Dh))
    if Vp > V:
        w["embed"] = w["embed"].at[V:].set(0)
        w["head"] = w["head"].at[:, V:].set(0)
    return w


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


def _hidden(m: Dict, w, tokens, gm, gf, lowp: bool = False):
    """Final hidden states [T, d] (float32) of one sequence."""
    L, H, K, Dh = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G, eps, theta = H // K, m["norm_eps"], m["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    f32 = lambda a: a.astype(jnp.float32)
    mat = _widen(lowp)
    h = f32(w["embed"][tokens])
    names = ["wq", "wk", "wv", "wo", "wi", "wd", "s_attn", "s_ffn"]
    names += [n for n in ("bq", "bk", "bv", "s_q", "s_k") if n in w]
    xs = {n: w[n] for n in names}

    def layer(h, x):
        lw, g_a, g_f = x
        a = _rms(h, lw["s_attn"], eps)
        q, k, v = a @ mat(lw["wq"]), a @ mat(lw["wk"]), a @ mat(lw["wv"])
        if "bq" in lw:
            q, k, v = q + f32(lw["bq"]), k + f32(lw["bk"]), v + f32(lw["bv"])
        q, k, v = (q.reshape(T, H, Dh), k.reshape(T, K, Dh),
                   v.reshape(T, K, Dh))
        if "s_q" in lw:
            q, k = _rms(q, lw["s_q"], eps), _rms(k, lw["s_k"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(T, K, G, Dh)
        outs = []
        for c0 in range(0, T, Q_CHUNK):
            qc = q[c0:c0 + Q_CHUNK]
            s = jnp.einsum("qkgd,skd->kgqs", qc, k) / math.sqrt(Dh)
            qpos = pos[c0:c0 + Q_CHUNK]
            s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :,
                                                           None],
                          s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("kgqs,skd->qkgd", p, v))
        o = jnp.concatenate(outs, 0).reshape(T, H * Dh)
        h = h + g_a * (o @ mat(lw["wo"]))
        x2 = _rms(h, lw["s_ffn"], eps)
        gate, up = jnp.split(x2 @ mat(lw["wi"]), 2, axis=-1)
        h = h + g_f * ((jax.nn.silu(gate) * up) @ mat(lw["wd"]))
        return h, None

    h, _ = jax.lax.scan(layer, h, (xs, gm, gf))
    return _rms(h, w["s_final"], eps)


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


def _make_fns(m: Dict, lowp: bool = False):
    @jax.jit
    def gap_fn(w, tokens, gm, gf, rows, targets):
        with jax.default_matmul_precision("highest"):
            h = _hidden(m, w, tokens, gm, gf, lowp)
            return _gaps(m, w, h[rows], targets, lowp)
    return gap_fn


Item = Tuple[np.ndarray, np.ndarray, np.ndarray]


def served_gaps(m: Dict, w, items: List[Item]) -> List[np.ndarray]:
    """For each (prompt [S], served tokens [n], mask [2L]): the gap, per
    served token, between the reference's best logit and the served
    token's logit at the position that produced it (teacher forced over
    prompt + served tokens)."""
    gap_fn = _make_fns(m)
    return [g for g, _ in _run(m, w, gap_fn, items)]


def control_gaps(m: Dict, w, items: List[Item]) -> List[np.ndarray]:
    """The control's readings on the same items: at each position, the
    float32 reference's gap of the token the fp8 reference puts first."""
    picks = [arg for _, arg in _run(m, w, _make_fns(m, lowp=True), items)]
    return [g for g, _ in _run(m, w, _make_fns(m), items, picks)]


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
