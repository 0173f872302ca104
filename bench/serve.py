"""Build the system under test for a cell: the model from the registry, its
weights from the seed, a random-Q ``RLPolicy``, a ``PagedExecutor`` and a
``RAPEngine`` configured as the traffic file says.

Only what an operator sets comes from the mix file (slots, ``max_len``,
the chunked-prefill cap, the KV precision, the budget); every other engine
setting keeps its ``EngineConfig`` default, so a PR that improves a
default is measured.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict

import numpy as np

from bench import reference


def seed32(seed: int) -> int:
    """A 32-bit draw of any whole-number seed (JAX keys take 32 bits)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def model_config(cell):
    """The registry's configuration with the file's overrides, checked by
    the cell's family against the widths the file states."""
    from repro.configs import get_config
    c = cell.config
    cfg = get_config(c["arch"]).replace(**c["overrides"])
    cell.family.check(cfg, c["model"])
    return cfg


def make_params(family, model, seed: int):
    """The served weights, drawn on the device in one jitted call and
    perturbed as ``family`` says."""
    import jax
    vocab = model.cfg.vocab_size

    def draw(key):
        k_init, k_pert = reference.split_seed_key(key)
        return family.perturb(model.init(k_init), k_pert, vocab)

    params = jax.jit(draw)(jax.random.key(seed32(seed)))
    jax.block_until_ready(params)
    return params


def device_budget(engine_cfg: Dict[str, Any], bytes_limit: int) -> float:
    b = engine_cfg["budget"]
    return float(bytes_limit - int(b["headroom_bytes"]))


def build(cell, seed: int, budget: float, kv_dtype=None):
    """(engine, executor, model, policy) for ``cell``; ``kv_dtype``
    overrides the mix's KV precision (the control runs the program's
    int8 pages)."""
    import jax
    from repro.core import dqn, memory
    from repro.core.controller import RAPController
    from repro.core.policy import RLPolicy
    from repro.models import registry
    from repro.runtime import EngineConfig, PagedExecutor, RAPEngine
    cfg = model_config(cell)
    model = registry.build(cfg)
    params = make_params(cell.family, model, seed)
    e = cell.mix["engine"]
    kv = kv_dtype or e["kv_dtype"]
    mm = memory.build_memory_model(cfg)
    L = cfg.n_layers
    k_q = jax.random.key(seed32(seed) ^ 0x5EED)
    qnet = dqn.init_qnet(k_q, 2 * L + 4, 2 * L + 1, 32)
    rng = np.random.default_rng([seed, zlib.crc32(b"calib")])
    toks = rng.integers(0, cfg.vocab_size, (2, 64), dtype=np.int32)
    calib = {"tokens": jax.numpy.asarray(toks),
             "labels": jax.numpy.asarray(toks)}
    controller = RAPController(model, params, calib, mm, qnet,
                               recompute_scores=False)
    policy = RLPolicy(controller)
    ecfg = EngineConfig(mode=e["mode"], max_active=int(e["slots"]),
                        max_len=int(e["max_len"]), budget_bytes=budget,
                        kv_dtype=kv,
                        max_prefill_tokens=int(e["max_prefill_tokens"]),
                        compile_cache=True)
    executor = PagedExecutor(model, params, mode=e["mode"],
                             max_active=int(e["slots"]), kv_dtype=kv,
                             decode_buckets=ecfg.decode_buckets)
    engine = RAPEngine(model, params, policy, ecfg, executor=executor)
    return engine, executor, model, controller


def warm_policy(controller) -> None:
    """One decision that has to prune: the policy's pruning loop then has
    every shape it uses before the window."""
    controller.decide(1, 64, 0.0, memo=False)


def warm_updates(executor, slots: int) -> None:
    """Run the paged group's small update programs at every size a tick
    can give them (page grants and evictions of 1..slots rows), so none
    compiles inside the window."""
    group = executor.groups()[0]
    scratch = executor.pool.scratch_page
    for n in range(1, slots + 1):
        group.grant_pages([(s, 0, scratch) for s in range(n)])
        group.evict(list(range(n)))


def release_pool(engine, executor) -> None:
    """Drop the warm-up run's page arrays before the measured run binds
    its own: two pools do not fit beside the weights."""
    import gc
    engine.pool = None
    executor.pool = None
    gc.collect()
