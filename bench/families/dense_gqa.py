"""The dense GQA family: a uniform decoder of grouped-query attention and a
SwiGLU FFN in every layer (glm4-9b, qwen3, llama2).

Everything of the benchmark that depends on this architecture lives here:
the check of the registry's configuration against the file's ``model``
block, the benchmark's perturbation of the program's weights, the plain
reference's weight draw and layer equations, and the work counts of the
device readers. The reference imports nothing of the program and
rebuilds the weights from the seed with its own copy of the draw the
benchmark gives the program (the program's ``init`` recipe: the same key
splits and distributions, then the benchmark's own perturbation), and
computes the published layer equations in float32 at
``precision="highest"``:

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

The counts are the work the mathematics requires, not what an
implementation happens to do: a kernel that walks pages it does not need,
or steps padded rows, spends time the count does not credit.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference import (Q_CHUNK, _dense, _rms, _rope, _widen, perturbed,
                             split_seed_key)

# the program's leaves that init leaves at zero and the benchmark draws
PERTURBED = {("stacks", "attn", "norm", "scale"),
             ("stacks", "dense", "norm", "scale"), ("final_norm", "scale"),
             ("stacks", "attn", "bq"), ("stacks", "attn", "bk"),
             ("stacks", "attn", "bv"), ("stacks", "attn", "q_norm"),
             ("stacks", "attn", "k_norm")}


def check(cfg, m: Dict) -> None:
    """Raise unless the registry's configuration ``cfg`` is a decoder of
    full attention and a dense SwiGLU FFN in every layer, with the widths
    the file's model block ``m`` states."""
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
           "head_dim": cfg.dh, "d_ff": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "vocab_padded": cfg.vocab_padded,
           "qkv_bias": cfg.qkv_bias, "qk_norm": cfg.qk_norm,
           "rope_theta": float(cfg.rope_theta), "norm_eps": cfg.norm_eps,
           "tie_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype}
    bad = {k: (got[k], m[k]) for k in got if got[k] != m[k]}
    kinds = set(cfg.layer_specs())
    if bad or cfg.activation != "swiglu" or kinds != {("attn", "dense")}:
        raise ValueError(f"the registry's config differs from the file's "
                         f"model block: {bad}, activation "
                         f"{cfg.activation}, layers {sorted(kinds)}")


def perturb(params, key, vocab_size: int):
    """The benchmark's perturbation, on the program's parameter tree: the
    norm scales, q/k/v biases and qk-norm scales that init leaves at zero
    are drawn, and padded vocabulary rows/columns are zeroed (a padded id
    is then never the best logit, as in a trained checkpoint)."""
    def leaf(path, x):
        keys = tuple(getattr(p, "key", None) for p in path)
        if keys in PERTURBED:
            return perturbed(key, "/".join(keys), x.shape)
        return x

    params = jax.tree_util.tree_map_with_path(leaf, params)
    vp = params["embed"].shape[0]
    if vp > vocab_size:
        params["embed"] = params["embed"].at[vocab_size:].set(0)
        params["lm_head"] = params["lm_head"].at[:, vocab_size:].set(0)
    return params


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


def hidden(m: Dict, w, tokens, gm, gf, lowp: bool = False):
    """Final hidden states [T, d] (float32) of one sequence; the shared gap
    loop (``reference.served_gaps`` / ``control_gaps``) runs the head."""
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


def layer_params(cfg: Dict) -> int:
    """Matmul parameters of one decoder layer (q/k/v/o and the GLU FFN)."""
    m = cfg["model"]
    d, h, k, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return d * (h * dh + 2 * k * dh) + h * dh * d + 3 * d * f


def head_params(cfg: Dict) -> int:
    m = cfg["model"]
    return m["d_model"] * m["vocab_padded"]


def matmul_flops_per_token(cfg: Dict) -> float:
    """2 × (layer + head parameters): every weight multiplies once."""
    return 2.0 * (cfg["model"]["n_layers"] * layer_params(cfg)
                  + head_params(cfg))


def attn_flops(cfg: Dict, ctx: float) -> float:
    """QK and PV of one query token against ``ctx`` keys, every layer."""
    m = cfg["model"]
    return 4.0 * ctx * m["n_heads"] * m["head_dim"] * m["n_layers"]


def decode_attn_bytes(cfg: Dict, ctx: float, kv_bytes: int = 2,
                      act_bytes: int = 2) -> float:
    """Bytes the paged decode kernel needs for one row at one step, all
    layers: the K and V of the ``ctx`` tokens it attends, q in, out."""
    m = cfg["model"]
    kv = 2.0 * ctx * m["n_kv_heads"] * m["head_dim"] * kv_bytes
    qo = 2.0 * m["n_heads"] * m["head_dim"] * act_bytes
    return (kv + qo) * m["n_layers"]


def kv_bytes_per_ctx_token(cfg: Dict) -> int:
    """Bytes of bf16 K and V one context token holds, all layers."""
    m = cfg["model"]
    return 2 * m["n_kv_heads"] * m["head_dim"] * 2 * m["n_layers"]
