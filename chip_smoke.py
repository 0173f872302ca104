"""Smoke run of the RAP serving stack on a TPU at llama2-7b's published widths.

Builds llama2-7b (32 layers, d_model 4096, 32 heads, d_ff 11008, vocab
32000) with bf16 weights drawn from ``--seed`` and serves a burst of
requests through ``RAPEngine`` — scheduler, pruning policy, executor and
``KVPool`` — the way a user of the library would.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: sharded serving only

One chip runs four serving phases, then a logits check:

  (a) masked mode, ``LocalExecutor`` (slot caches);
  (b) masked mode, ``PagedExecutor`` with bf16 pages (Pallas paged decode);
  (c) the same with int8 pages (fused-dequant kernel);
  (d) structural mode, ``LocalExecutor``, under a budget that keeps the
      full mask (the compacted stack is the dense one, not a copy);

the policy is a random-Q ``RLPolicy`` (no DQN training). ``--chips 4``
runs only the sharded masked path — on a 1x4 (tensor-parallel) mesh and
on the mesh ``make_serve_mesh`` picks — against the one-chip
``LocalExecutor`` it is compared with, served first and freed before the
meshes are built.

Every phase prints its own line; the last line of standard output is one
JSON object naming the device. Without a TPU the script exits non-zero
before printing it, and it never falls back to the CPU. JAX's persistent
compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` in this checkout, so a second run loads its executables
from disk.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ARCH = "llama2-7b"
SLOTS = 8              # decode slots = concurrent requests
N_REQUESTS = 8
PROMPT_LENS = (64, 128, 192, 240)   # cycled over the requests
NEW_TOKENS = 16        # generated per request (prefill yields the first)
MAX_LEN = 256          # prompt + generated, the slot-cache length
PAGE_TOKENS = 16
# bf16 logits tolerance: both sides run the same bf16 weights, but round
# attention differently (the reference forward casts softmax
# probabilities to bf16 before the value matmul, the paged kernel and the
# sharded partial sums keep other intermediates), and those bf16 roundings
# (unit roundoff 2^-9) compound through 32 residual layers. A 32-layer
# bf16 model at reduced width differs by ~1.5% of the largest logit
# between the two paths on the CPU; 5% leaves headroom for full width
# while still failing a wrong page layout, scale or mask, which moves
# logits by their own magnitude.
LOGITS_RTOL = 0.05


def _device_line(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {"peak_bytes_in_use": int(stats.get("peak_bytes_in_use", -1)),
            "bytes_in_use": int(stats.get("bytes_in_use", -1)),
            "bytes_limit": int(stats.get("bytes_limit", -1))}


def _check_memory(dev, phase: str) -> dict:
    mem = _device_line(dev)
    if not 0 < mem["peak_bytes_in_use"] < mem["bytes_limit"]:
        raise RuntimeError(f"{phase}: device peak {mem['peak_bytes_in_use']}"
                           f" not below bytes_limit {mem['bytes_limit']}")
    return mem


def _logits_gap(got, want, what: str) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise RuntimeError(f"{what}: shape {got.shape} vs {want.shape} or "
                           f"non-finite logits")
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    if gap > LOGITS_RTOL:
        raise RuntimeError(f"{what}: max |Δlogit| is {gap:.4f} of the "
                           f"largest reference logit (> {LOGITS_RTOL})")
    return gap


def _requests(cfg, seed: int):
    from repro.data import SyntheticCorpus
    from repro.runtime import EngineRequest
    corpus = SyntheticCorpus(cfg.vocab_size, seed=seed)
    rng = np.random.default_rng(seed)
    return [EngineRequest(
        rid=f"r{i}", prompt=corpus.sample_tokens(
            rng, 1, PROMPT_LENS[i % len(PROMPT_LENS)]),
        arrival_t=0.0, max_new=NEW_TOKENS) for i in range(N_REQUESTS)]


def _budget(mm) -> float:
    """Resident weights plus every slot's dense KV at MAX_LEN: all the
    requests are admitted at once with the full mask."""
    from repro.core import masks
    full = masks.full_mask(mm.n_layers)
    return mm.param_bytes(full) + SLOTS * mm.state_bytes(full, 1, MAX_LEN)


def _engine_config(mode: str, budget: float, kv_dtype=None):
    from repro.runtime import EngineConfig
    # budget_quantum_frac=0: the policy sees the exact remaining budget,
    # so a budget sized for every dense request keeps every mask full
    return EngineConfig(mode=mode, max_new_tokens=NEW_TOKENS,
                        max_active=SLOTS, max_len=MAX_LEN,
                        budget_bytes=budget, tokens_per_page=PAGE_TOKENS,
                        kv_dtype=kv_dtype, budget_quantum_frac=0.0,
                        compile_cache=True)


def _serve(name, model, params, policy, requests, cfg, executor, dev):
    """Serve ``requests`` and check every one completed; returns
    (tokens by rid, masks by rid, the phase's printed record)."""
    from repro.runtime import RAPEngine
    t0 = time.perf_counter()
    engine = RAPEngine(model, params, policy, cfg, executor=executor)
    rep = engine.run(requests)
    done = {r.rid: r for r in rep.results if r.status == "done"}
    if len(done) != len(requests):
        bad = [(r.rid, r.status, r.reason) for r in rep.results
               if r.status != "done"]
        raise RuntimeError(f"{name}: {len(done)}/{len(requests)} requests "
                           f"done; others {bad}")
    for r in done.values():
        t = np.asarray(r.tokens)
        if t.shape != (1, NEW_TOKENS) or t.min() < 0 \
                or t.max() >= model.cfg.vocab_padded:
            raise RuntimeError(f"{name}: {r.rid} tokens {t.shape} out of "
                               f"range")
    record = {"phase": name, "requests_done": len(done),
              "tokens_generated": int(rep.generated_tokens),
              "compile_events": int(rep.compile_events),
              "cache_hits": int(rep.compile_cache_hits),
              "cache_misses": int(rep.compile_cache_misses),
              "seconds": round(time.perf_counter() - t0, 1),
              **_check_memory(dev, name)}
    return ({rid: np.asarray(r.tokens) for rid, r in done.items()},
            {rid: np.asarray(r.mask) for rid, r in done.items()}, record)


def _lowers_kernel(fn, *args) -> bool:
    """Whether jitted ``fn`` lowered on ``args`` holds a Pallas TPU kernel
    (a ``tpu_custom_call``)."""
    return "tpu_custom_call" in fn.lower(*args).as_text()


def _paged_decode_has_kernel(executor) -> bool:
    """Whether the paged executor's full-width decode horizon, lowered with
    its live state, holds the Pallas paged-decode kernel."""
    group = executor.groups()[0]
    return _lowers_kernel(executor._horizon_fn(group, 8, bucketed=False),
                          executor._group_params(group),
                          executor._pool_leaves(), group.table_dev,
                          group.pos_dev, group.tokens_dev, group.gates_dev)


def _token_share(a: dict, b: dict) -> float:
    same = sum(int((a[k] == b[k]).sum()) for k in a)
    return same / sum(v.size for v in a.values())


def _first_token_logits(model, params, prompt):
    """Prefill logits of the first generated token (what every executor's
    prefill argmaxes), computed under ``params``' own placement."""
    import jax
    import jax.numpy as jnp
    from repro.models import decoder
    fn = jax.jit(lambda p, t: decoder.prefill(p, model.cfg, t, MAX_LEN)[0])
    return np.asarray(fn(params, jnp.asarray(prompt)))[:, :model.cfg.vocab_size]


def _logits_check(model, params, prompt):
    """The paged Pallas path against ``model.logits`` on one prompt: the
    prefill's first-token logits, then one paged decode step through the
    Pallas kernel (bf16 head-major pages holding the prefilled KV) against
    the reference forward over prompt + first token."""
    import jax
    import jax.numpy as jnp
    from repro.models import decoder
    cfg = model.cfg
    L, K, D, V = cfg.n_layers, cfg.n_kv_heads, cfg.dh, cfg.vocab_size
    S = prompt.shape[1]
    npg = -(-(S + 1) // PAGE_TOKENS)
    logits0, cache = jax.jit(lambda p, t: decoder.prefill(
        p, cfg, t, npg * PAGE_TOKENS))(params, jnp.asarray(prompt))
    first = jnp.argmax(logits0, axis=-1).astype(jnp.int32)

    def pages(x):            # [L, 1, npg*pt, K, D] → [L, npg, K, pt, D]
        return jnp.swapaxes(x[:, 0].reshape(L, npg, PAGE_TOKENS, K, D), 2, 3)

    pools = {n: pages(cache["attn"][n]) for n in ("k", "v")}
    table = jnp.arange(npg, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, pl_, tok: decoder.paged_decode_step(
        p, cfg, pl_, table, jnp.asarray([S], jnp.int32), tok,
        impl="pallas")[0])
    if not _lowers_kernel(step, params, pools, first[:, None]):
        raise RuntimeError("paged decode step lowered without the kernel")
    logits1 = step(params, pools, first[:, None])
    ref = jax.jit(lambda p, t: model.logits(p, {"tokens": t}))(
        params, jnp.concatenate([jnp.asarray(prompt), first[:, None]], 1))
    ref = np.asarray(ref)
    return {"phase": "logits_check", "prompt_tokens": int(S),
            "rtol": LOGITS_RTOL,
            "prefill_gap": _logits_gap(np.asarray(logits0)[:, :V],
                                       ref[:, S - 1, :V], "prefill logits"),
            "paged_pallas_gap": _logits_gap(np.asarray(logits1)[:, 0, :V],
                                            ref[:, S, :V],
                                            "paged Pallas decode logits"),
            "paged_argmax_matches": bool(
                np.argmax(np.asarray(logits1)[0, 0, :V])
                == np.argmax(ref[0, S, :V]))}


def run_one_chip(model, mm, seed: int, dev) -> None:
    import jax
    from repro.core import dqn, masks
    from repro.core.controller import RAPController
    from repro.core.policy import RLPolicy
    from repro.data import SyntheticCorpus
    from repro.runtime import LocalExecutor, PagedExecutor

    cfg = model.cfg
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.key(seed))
    jax.block_until_ready(params)
    print(json.dumps({"phase": "init", "arch": cfg.name,
                      "layers": cfg.n_layers, "d_model": cfg.d_model,
                      "param_bytes": int(mm.param_bytes(
                          masks.full_mask(cfg.n_layers))),
                      "seconds": round(time.perf_counter() - t0, 1),
                      **_check_memory(dev, "init")}), flush=True)
    calib = {k: jax.numpy.asarray(v) for k, v in SyntheticCorpus(
        cfg.vocab_size, seed=seed).batch(2, 64, split="calib").items()}
    L = cfg.n_layers
    qnet = dqn.init_qnet(jax.random.key(seed), 2 * L + 4, 2 * L + 1, 32)
    # importance scores are computed once on the dense model and reused:
    # every decision here starts from the full mask
    controller = RAPController(model, params, calib, mm, qnet,
                               recompute_scores=False)
    requests = _requests(cfg, seed)
    budget = _budget(mm)

    phases = [
        ("a_masked_local", "masked", None,
         lambda: LocalExecutor(model, params, mode="masked",
                               max_active=SLOTS)),
        ("b_masked_paged_bf16", "masked", "bf16",
         lambda: PagedExecutor(model, params, mode="masked",
                               max_active=SLOTS, kv_dtype="bf16")),
        ("c_masked_paged_int8", "masked", "int8",
         lambda: PagedExecutor(model, params, mode="masked",
                               max_active=SLOTS, kv_dtype="int8")),
        ("d_structural_local", "structural", None,
         lambda: LocalExecutor(model, params, mode="structural",
                               max_active=SLOTS)),
    ]
    reference = None
    for name, mode, kv_dtype, make in phases:
        executor = make()
        toks, kept, record = _serve(
            name, model, params, RLPolicy(controller), requests,
            _engine_config(mode, budget, kv_dtype), executor, dev)
        if executor.paged:
            record["decode_has_tpu_custom_call"] = \
                _paged_decode_has_kernel(executor)
            if not record["decode_has_tpu_custom_call"]:
                raise RuntimeError(f"{name}: paged decode runs without the "
                                   f"Pallas kernel")
        if mode == "structural":
            if not all(m.all() for m in kept.values()):
                raise RuntimeError(f"{name}: the budget pruned a request")
            stacks = [g.params["stacks"] for g in executor.groups()]
            record["stack_is_dense"] = all(
                s[k] is params["stacks"][k] for s in stacks for k in s)
            if not record["stack_is_dense"]:
                raise RuntimeError(f"{name}: full-mask bucket copied the "
                                   f"weights")
        record["full_masks"] = sum(int(m.all()) for m in kept.values())
        if reference is None:
            reference = toks
        else:
            record["token_match_vs_a"] = round(
                _token_share(reference, toks), 4)
        print(json.dumps(record), flush=True)
        del executor, toks, kept
        gc.collect()
    record = _logits_check(model, params, requests[0].prompt)
    record.update(_check_memory(dev, "logits_check"))
    print(json.dumps(record), flush=True)


def run_four_chips(model, mm, seed: int, dev) -> None:
    import jax
    from repro.core.policy import DensePolicy
    from repro.launch.mesh import make_host_mesh, make_serve_mesh
    from repro.runtime import LocalExecutor, ShardedExecutor

    cfg = model.cfg
    requests = _requests(cfg, seed)
    budget = _budget(mm)
    prompt = requests[0].prompt
    # the one-chip reference first; its arrays are freed before any mesh
    # is built (device 0 cannot hold a full replica beside a shard)
    params = jax.jit(model.init)(jax.random.key(seed))
    ref_toks, _, record = _serve(
        "ref_local_one_chip", model, params, DensePolicy(mm), requests,
        _engine_config("masked", budget),
        LocalExecutor(model, params, mode="masked", max_active=SLOTS), dev)
    ref_logits = _first_token_logits(model, params, prompt)
    print(json.dumps(record), flush=True)
    del params
    gc.collect()

    for name, mesh in (("sharded_tp_1x4",
                        make_host_mesh((1, 4), ("data", "model"))),
                       ("sharded_auto", make_serve_mesh(SLOTS))):
        # weights are drawn straight into their shards: init is jitted
        # with the executor's output shardings, never replicated first
        shardings = ShardedExecutor(model, mesh,
                                    max_active=SLOTS).param_shardings()
        params = jax.jit(model.init, out_shardings=shardings)(
            jax.random.key(seed))
        executor = ShardedExecutor(model, mesh, params=params,
                                   max_active=SLOTS)
        toks, _, record = _serve(name, model, params, DensePolicy(mm),
                                 requests, _engine_config("masked", budget),
                                 executor, dev)
        logits = _first_token_logits(model, params, prompt)
        record.update({
            "mesh": {str(k): int(v) for k, v in mesh.shape.items()},
            "first_token_logits_gap": _logits_gap(logits, ref_logits,
                                                  f"{name} logits"),
            "first_token_logits_rtol": LOGITS_RTOL,
            "first_token_logits_bitwise": bool(
                np.array_equal(logits, ref_logits)),
            "token_match_vs_one_chip": round(_token_share(ref_toks, toks),
                                             4)})
        print(json.dumps(record), flush=True)
        del executor, params, toks
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving phases (a)-(d) and the logits check; "
                         "4: sharded serving against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs import get_config
    from repro.core import memory
    from repro.models import registry
    from repro.runtime.engine import enable_compile_cache

    # before the first compile: JAX latches the cache decision then
    cache_dir = enable_compile_cache()
    cfg = get_config(ARCH)
    model = registry.build(cfg)
    mm = memory.build_memory_model(cfg)
    dev = devices[0]
    print(json.dumps({"phase": "start", "arch": ARCH, "chips": args.chips,
                      "device_kind": dev.device_kind,
                      "compile_cache_dir": cache_dir}), flush=True)
    if args.chips == 4:
        run_four_chips(model, mm, args.seed, dev)
    else:
        run_one_chip(model, mm, args.seed, dev)
    from repro.runtime.engine import _CACHE_EVENTS
    print(json.dumps({"phase": "compile_cache", **_CACHE_EVENTS}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
