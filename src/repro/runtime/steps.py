"""Step functions shared by the trainer, the server, and the AOT dry-run.

Everything here is a pure function of (params, state, batch) so the same
code path is jitted for real execution and ``.lower().compile()``d against
ShapeDtypeStructs for the 512-device dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.optim import adamw
from repro.parallel import compression


def make_train_step(model, opt_cfg: adamw.AdamWConfig, *,
                    remat: bool = True, impl: str = "xla",
                    microbatches: int = 1) -> Callable:
    """(params, opt_state, batch) → (params', opt_state', metrics).

    ``microbatches > 1`` scans gradient accumulation over batch slices —
    activation memory scales 1/m at the cost of an f32 gradient
    accumulator. The standard fit lever for the 100B+ configs whose
    backward working set exceeds HBM even with remat + sequence-parallel
    activations (dbrx-132b × train_4k)."""

    def loss_on(p, b):
        loss, aux = model.loss(p, b, impl=impl, remat=remat)
        return loss, aux

    if microbatches <= 1:
        def train_step(params, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(
                loss_on, has_aux=True)(params, batch)
            params, opt_state, om = adamw.apply(opt_cfg, params, grads,
                                                opt_state)
            return params, opt_state, {**aux, **om}
        return train_step

    m = microbatches

    def train_step(params, opt_state, batch):
        mb = jax.tree.map(
            lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch)

        def accum(gsum, one_batch):
            (loss, aux), g = jax.value_and_grad(
                loss_on, has_aux=True)(params, one_batch)
            gsum = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return gsum, aux

        gsum0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        gsum, auxes = jax.lax.scan(accum, gsum0, mb)
        grads = jax.tree.map(lambda g: g / m, gsum)
        aux = jax.tree.map(lambda a: jnp.mean(a), auxes)
        params, opt_state, om = adamw.apply(opt_cfg, params, grads,
                                            opt_state)
        return params, opt_state, {**aux, **om}

    return train_step


def make_compressed_train_step(model, opt_cfg: adamw.AdamWConfig, mesh, *,
                               pspecs, batch_pspecs_tree,
                               remat: bool = True) -> Callable:
    """Train step with explicit int8 error-feedback DP all-reduce.

    The model runs replicated per DP shard inside ``shard_map`` (TP is not
    composed here — this variant is for parameter-light models where the DP
    gradient all-reduce dominates); gradients cross ICI as int8.

    (params, opt_state, residuals, batch) → (params', opt', residuals', m).
    """
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def step(params, opt_state, residuals, batch):
        def loss_fn(p):
            loss, aux = model.loss(p, batch, remat=remat)
            return loss, aux

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, residuals = compression.compress_allreduce(
            grads, residuals, dp_axes)
        params, opt_state, om = adamw.apply(opt_cfg, params, grads, opt_state)
        metrics = {k: jax.lax.pmean(v, dp_axes)
                   for k, v in {**aux, **om}.items()}
        return params, opt_state, residuals, metrics

    param_spec = P()          # replicated params (DP-only variant)
    return jax.shard_map(
        step, mesh=mesh,
        in_specs=(param_spec, param_spec, param_spec, batch_pspecs_tree),
        out_specs=(param_spec, param_spec, param_spec, P()),
        check_vma=False)


def make_prefill_step(model, max_len: int, *, impl: str = "xla",
                      kv_dtype=None, gates: bool = False) -> Callable:
    """(params, batch[, gates]) → (last_logits, cache)."""
    if gates:
        def prefill_step(params, batch, gate_vals):
            return model.prefill(params, batch, max_len, gates=gate_vals,
                                 impl=impl, kv_dtype=kv_dtype)
    else:
        def prefill_step(params, batch):
            return model.prefill(params, batch, max_len, impl=impl,
                                 kv_dtype=kv_dtype)
    return prefill_step


def make_decode_step(model, *, impl: str = "xla",
                     gates: bool = False) -> Callable:
    """(params, cache, tokens[, gates]) → (logits, cache)."""
    if gates:
        def decode_step(params, cache, tokens, gate_vals):
            return model.decode(params, cache, tokens, gates=gate_vals,
                                impl=impl)
    else:
        def decode_step(params, cache, tokens):
            return model.decode(params, cache, tokens, impl=impl)
    return decode_step


def make_eval_step(model, *, impl: str = "xla") -> Callable:
    def eval_step(params, batch, gate_vals=None):
        loss, aux = model.loss(params, batch, gates=gate_vals, impl=impl)
        return aux
    return eval_step
