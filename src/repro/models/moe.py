"""Mixture-of-Experts FFN with top-k routing.

Two dispatch implementations:

* ``dense``   — every expert runs on every token, one-hot combine. Exact
                (dropless), O(E/k) FLOP waste. Correctness oracle + smoke tests.
* ``scatter`` — MegaBlocks-style sort-free capacity dispatch: tokens are
                scattered into a per-expert ``[E, C, D]`` buffer, all experts
                run as one grouped einsum (MXU-friendly), results gathered
                back with routing weights. Tokens beyond capacity drop (GShard
                semantics). This is the production / dry-run path; the expert
                axis shards over the "model" mesh axis (EP).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.ffn import glu_activate
from repro.parallel import activation as act


def init_moe_params(rng, cfg) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    pd = cfg.jnp_param_dtype()
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    std_i = 1.0 / math.sqrt(D)
    std_o = 1.0 / math.sqrt(F) / math.sqrt(2 * max(cfg.n_layers, 1))
    wi = jax.random.truncated_normal(k1, -2, 2, (E, D, 2 * F), jnp.float32) * std_i
    wo = jax.random.truncated_normal(k2, -2, 2, (E, F, D), jnp.float32) * std_o
    router = layers.dense_init(k3, D, E, jnp.float32)  # router kept in f32
    return {"wi": wi.astype(pd), "wo": wo.astype(pd), "router": router}


def _route(params, cfg, x):
    """x: [T, D] → (weights [T, k], expert_idx [T, k]) with renormalized top-k."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights.astype(x.dtype), idx


def moe_ffn_dense(params, cfg, x):
    """Oracle path. x: [B, S, D]."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    weights, idx = _route(params, cfg, xt)                     # [T,k]
    onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=x.dtype)  # [T,k,E]
    combine = jnp.einsum("tk,tke->te", weights, onehot)         # [T,E]
    h = jnp.einsum("td,edf->tef", xt, params["wi"].astype(x.dtype))
    h = glu_activate(h, cfg.activation)
    y = jnp.einsum("tef,efd->ted", h, params["wo"].astype(x.dtype))
    out = jnp.einsum("ted,te->td", y, combine)
    return out.reshape(B, S, D)


def _capacity(cfg, T: int) -> int:
    c = int(math.ceil(cfg.moe_capacity_factor * T * cfg.moe_top_k / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU lanes


def moe_ffn_scatter(params, cfg, x):
    """Production path. x: [B, S, D]."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    C = _capacity(cfg, T)

    weights, idx = _route(params, cfg, xt)                 # [T,k]
    flat_e = idx.reshape(-1)                               # [T*k] expert ids
    # position of each assignment within its expert, via stable sort:
    # rank among same-expert assignments == cumulative count.
    order = jnp.argsort(flat_e, stable=True)               # [T*k]
    ranks = jnp.zeros((T * k,), jnp.int32)
    # within sorted order, rank = index - start_of_expert_segment
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))  # [E]
    pos_in_sorted = jnp.arange(T * k, dtype=jnp.int32)
    sorted_rank = pos_in_sorted - seg_start[sorted_e]
    ranks = ranks.at[order].set(sorted_rank)               # [T*k]

    keep = ranks < C                                       # capacity drop mask
    slot = jnp.where(keep, ranks, C)                       # overflow → trash slot
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    # scatter tokens → [E, C+1, D] buffer (last slot is trash)
    buf = jnp.zeros((E, C + 1, D), x.dtype)
    buf = buf.at[flat_e, slot].set(xt[tok], mode="drop")
    buf = act.expert_buffer(buf)          # EP: experts over "model"

    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(x.dtype))
    h = glu_activate(h, cfg.activation)
    y = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(x.dtype))
    y = act.expert_buffer(y)

    # gather back + weighted combine over the k assignments
    gathered = y[flat_e, slot]                             # [T*k, D]
    gathered = gathered * (keep[:, None].astype(x.dtype))
    wflat = weights.reshape(-1, 1).astype(x.dtype)
    out = jax.ops.segment_sum(gathered * wflat, tok, num_segments=T)
    return out.reshape(B, S, D)


def _local_dispatch(cfg, xt, weights, idx, wi, wo, e_lo, E_loc):
    """Capacity dispatch restricted to experts [e_lo, e_lo+E_loc).

    xt [T, D]; weights/idx [T, k]; wi [E_loc, D, 2F]; wo [E_loc, F, D].
    Returns the partial combine ([T, D]) of the local experts only.
    """
    T, D = xt.shape
    k = idx.shape[1]
    C = _capacity(cfg, T)
    flat_e = idx.reshape(-1) - e_lo                        # local ids
    inside = (flat_e >= 0) & (flat_e < E_loc)
    flat_e = jnp.where(inside, flat_e, E_loc)              # sentinel bin
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E_loc + 1))
    pos = jnp.arange(T * k, dtype=jnp.int32)
    sorted_rank = pos - seg_start[sorted_e]
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(sorted_rank)
    keep = inside & (ranks < C)
    slot = jnp.where(keep, ranks, C)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    buf = jnp.zeros((E_loc, C + 1, D), xt.dtype)
    buf = buf.at[jnp.minimum(flat_e, E_loc - 1), slot].set(
        jnp.where(keep[:, None], xt[tok], 0), mode="drop")
    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(xt.dtype))
    h = glu_activate(h, cfg.activation)
    y = jnp.einsum("ecf,efd->ecd", h, wo.astype(xt.dtype))
    gathered = y[jnp.minimum(flat_e, E_loc - 1), slot]
    gathered = gathered * keep[:, None].astype(xt.dtype)
    wflat = weights.reshape(-1, 1).astype(xt.dtype)
    return jax.ops.segment_sum(gathered * wflat, tok, num_segments=T)


def moe_ffn_ep(params, cfg, x, pol):
    """Expert-parallel dispatch under ``shard_map``.

    Exploits the Megatron-style activation layout — x is batch-sharded over
    (pod, data) and *replicated* across "model" — so no token all-to-all is
    needed at all: each model shard routes the full local token set, runs
    only its E/n_model experts, and the partial combines are summed with
    one psum over "model" (the same wire cost as a dense-FFN wo
    all-reduce). GSPMD's scatter partitioner would instead replicate the
    [E, C, D] dispatch buffers and gathered updates (observed: 190 GB/dev
    on olmoe × train_4k); this path keeps them shard-local.

    FSDP composition: when weights carry an extra "data" shard, the body
    all-gathers them before use (explicit ZeRO-3 gather, visible in HLO).
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import _add_fsdp, _param_rule

    mesh = pol.mesh
    E = cfg.n_experts
    E_loc = E // pol.nmdl
    L = cfg.n_layers

    def spec_for(name, arr):
        full = (L,) + arr.shape
        sp = _param_rule(f"stacks/moe/{name}", full, mesh)
        if pol.fsdp:
            sp = _add_fsdp(sp, f"stacks/moe/{name}", full, mesh)
        return P(*tuple(sp)[1:])   # drop the layer axis

    wi_spec = spec_for("wi", params["wi"])
    wo_spec = spec_for("wo", params["wo"])
    x_spec = P(pol.dp, None, None)

    def gather_fsdp(w, spec):
        for axis, ax_name in enumerate(tuple(spec)):
            if ax_name == "data":
                w = jax.lax.all_gather(w, "data", axis=axis, tiled=True)
        return w

    def body(x_loc, wi, wo, router):
        wi = gather_fsdp(wi, wi_spec)
        wo = gather_fsdp(wo, wo_spec)
        xt = x_loc.reshape(-1, x_loc.shape[-1])
        weights, idx = _route({"router": router}, cfg, xt)
        e_lo = jax.lax.axis_index("model") * E_loc
        T, D_ = xt.shape
        # token-group chunking (GShard group capacity): bounds the [T·k, D]
        # gather/scatter transients that otherwise dominate backward temps
        cs = 16384
        while cs > 1 and T % cs:
            cs //= 2
        if T > cs >= 1024:
            k = idx.shape[1]

            def disp(args):
                xt_c, w_c, i_c = args
                return _local_dispatch(cfg, xt_c, w_c, i_c, wi, wo, e_lo,
                                       E_loc)

            out = jax.lax.map(jax.checkpoint(disp),
                              (xt.reshape(-1, cs, D_),
                               weights.reshape(-1, cs, k),
                               idx.reshape(-1, cs, k))).reshape(T, D_)
        else:
            out = _local_dispatch(cfg, xt, weights, idx, wi, wo, e_lo, E_loc)
        out = jax.lax.psum(out, "model")
        return out.reshape(x_loc.shape)

    ep_call = jax.shard_map(body, mesh=mesh,
                            in_specs=(x_spec, wi_spec, wo_spec, P()),
                            out_specs=x_spec, check_vma=False)

    # Outer sequence chunking: the shard_map boundary materializes x (and
    # its f32 cotangent) at full sequence length per data shard; mapping
    # seq chunks through it bounds those transients (observed 25 GB of
    # temps on dbrx × train_4k without this).
    B, S, D = x.shape
    cs = 1024
    while cs > 1 and S % cs:
        cs //= 2
    if S > cs >= 256:
        xc = jnp.swapaxes(x.reshape(B, S // cs, cs, D), 0, 1)

        def one(xb):
            return ep_call(xb, params["wi"], params["wo"], params["router"])

        out = jax.lax.map(jax.checkpoint(one), xc)
        return jnp.swapaxes(out, 0, 1).reshape(B, S, D)
    return ep_call(x, params["wi"], params["wo"], params["router"])


def moe_ffn(params, cfg, x, *, impl: str = "scatter"):
    if impl == "dense":
        return moe_ffn_dense(params, cfg, x)
    pol = act.policy()
    if (pol is not None and pol.nmdl > 1
            and cfg.n_experts % pol.nmdl == 0):
        return moe_ffn_ep(params, cfg, x, pol)
    return moe_ffn_scatter(params, cfg, x)
