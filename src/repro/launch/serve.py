"""Serving launcher: RAP-managed inference over a synthetic workload trace.

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
      --requests 10 --mode structural --policy rl --scheduler fifo

Boots the reduced model, builds the requested pruning policy — for
``--policy rl`` that means briefly training the RAP controller (paper
Algorithm 2); static baselines (shortgpt, llmpruner, random, …) score
their removal order once and need no RL training — then serves an
Azure-like workload trace of (batch, seq_len, memory-budget) requests:
the full online loop of paper Algorithm 3, now policy-agnostic.

Two serving paths (DESIGN.md §10):
  * default — continuous batching through ``RAPEngine``: one shared KV pool
    with admission control; all in-flight requests decode together under
    the chosen scheduler (fifo | sjf | priority);
  * ``--serial`` — the historical one-shot ``RAPServer`` replay, each
    request against its own instantaneous budget.
"""
from __future__ import annotations

import argparse

# Device memory the launcher leaves outside the engine's budget when it
# caps the default budget at the device's capacity: XLA's workspace for
# the serving executables (prefill activations and logits, decode-loop
# temporaries, the int8/fp8 page-write staging) lives outside both the
# weights and the KV pool the budget accounts for.
DEVICE_HEADROOM_BYTES = 1 << 30


def device_budget_cap():
    """``bytes_limit`` of the first device less
    :data:`DEVICE_HEADROOM_BYTES`, or ``None`` where the backend reports
    no memory limit (CPU)."""
    import jax
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return None if not limit else float(limit - DEVICE_HEADROOM_BYTES)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--mode", choices=("structural", "masked"),
                    default="structural")
    ap.add_argument("--policy", default="rl",
                    help="pruning policy (rl | shortgpt | llmpruner | "
                         "random | mha_drop | ffn_skip | oneshot | dense)")
    ap.add_argument("--scheduler", choices=("fifo", "sjf", "priority"),
                    default="fifo", help="engine admission ordering")
    ap.add_argument("--executor", choices=("local", "paged", "sharded"),
                    default="local",
                    help="execution backend: 'local' = slot-batched caches "
                         "(reference, any mode/arch); 'paged' = physically "
                         "paged KV pool with per-request page tables "
                         "(masked or structural mode, uniform-attention "
                         "archs); 'sharded' = mesh-resident slot groups, TP/DP "
                         "horizon decode (masked mode; see --mesh — works "
                         "on CPU via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--mesh", default="auto",
                    help="sharded executor mesh as DATAxMODEL (e.g. 4x2); "
                         "'auto' picks a DP-majority mesh over the host's "
                         "devices whose data axis divides --slots")
    ap.add_argument("--serial", action="store_true",
                    help="one-shot RAPServer replay instead of the engine")
    ap.add_argument("--episodes", type=int, default=20)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="engine decode slots (concurrent requests)")
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="decode tokens fused per engine macro-tick: one "
                         "compiled on-device loop emits H tokens per "
                         "running request with ONE device→host sync "
                         "(results are bitwise-identical to H=1; see "
                         "DESIGN.md §5)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="prefill prompts in pow2-bucketed chunks "
                         "interleaved with decode macro-ticks (async "
                         "engine, DESIGN.md §6) so a long prompt cannot "
                         "stall running decodes; chunk cap defaults to 64 "
                         "tokens unless --max-prefill-tokens is given")
    ap.add_argument("--max-prefill-tokens", type=int, default=0,
                    help="cap on prompt tokens prefilled per engine tick "
                         "(implies --chunked-prefill; 0 = monolithic "
                         "prefill unless --chunked-prefill is set)")
    ap.add_argument("--kv-dtype", default="model",
                    choices=("model", "fp32", "bf16", "int8", "fp8", "auto"),
                    help="KV cache storage precision: 'model' (default) "
                         "stores at the model dtype; int8/fp8 quantize "
                         "pages (paged executor: per-(page, head) scales "
                         "with dequant fused into the decode kernel; slot "
                         "executors: per-(token, head) scales) — admission "
                         "charges quantized bytes, so int8 admits ~2× the "
                         "sequence under the same budget; 'auto' lets the "
                         "policy choose once at startup: quantize when the "
                         "pool cannot host the full decode batch densely")
    ap.add_argument("--pool-requests", type=float, default=2.5,
                    help="KV pool sized for this many concurrent dense "
                         "requests")
    ap.add_argument("--budget-trace", choices=("none", "workload",
                                               "staircase"),
                    default="none",
                    help="time-varying device budget (DESIGN.md §11): "
                         "'workload' replays the trace's OU memory-"
                         "availability walk (each request's budget_frac "
                         "becomes a breakpoint); 'staircase' cuts half "
                         "the KV headroom for the middle half of the "
                         "trace and restores it; 'none' serves the "
                         "static budget. Under a trace the engine "
                         "preempts victims (KV spilled to host, resumed "
                         "bitwise when the budget recovers) unless "
                         "--no-enable-preemption")
    ap.add_argument("--enable-preemption", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="preempt running requests when the budget trace "
                         "drops (--no-enable-preemption: shrink by "
                         "admission-gating new work only; in-flight "
                         "requests keep their pages)")
    ap.add_argument("--bucket-quant", choices=("none", "layer", "pow2"),
                    default="none",
                    help="structural bucket-shape quantization (DESIGN.md "
                         "§9): snap decision masks onto a ladder of whole-"
                         "layer keep-sets before minting a bucket — the "
                         "exact mask runs as 0/1 gates inside it (bitwise-"
                         "identical tokens) — so adaptive policies compile "
                         "a bounded executable set; 'pow2' bounds it at "
                         "ceil(log2 L)+1 families. The paged executor "
                         "floors 'none' at 'layer'")
    ap.add_argument("--compile-cache", action="store_true",
                    help="enable JAX's persistent compilation cache "
                         "(JAX_COMPILATION_CACHE_DIR when set, else "
                         ".jax_cache/ in the checkout): a second serve of "
                         "the same config re-traces but loads XLA binaries "
                         "from disk instead of recompiling (near-zero "
                         "warm-start compiles; DESIGN.md §9)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.chunked_prefill and args.max_prefill_tokens <= 0:
        args.max_prefill_tokens = 64

    import jax
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.core import dqn, env as env_lib, masks, memory, workload
    from repro.core.controller import RAPController
    from repro.core.policy import available_policies, make_policy
    from repro.data import SyntheticCorpus
    from repro.models import registry
    from repro.runtime import (EngineConfig, EngineRequest, PagedExecutor,
                               RAPEngine, RAPServer)

    if args.executor != "local" and args.serial:
        ap.error(f"--executor {args.executor} drives the batching engine; "
                 f"drop --serial")
    if args.executor == "sharded" and args.mode != "masked":
        ap.error("--executor sharded serves masked mode (structural sharded "
                 "buckets are a ROADMAP item); add --mode masked")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = registry.build(cfg)
    # under jit the layer stacks are written straight into their outputs:
    # one copy of the weights at peak, not per-layer trees plus the stack
    params = jax.jit(model.init)(jax.random.key(args.seed))
    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    calib = {k: jax.numpy.asarray(v)
             for k, v in corpus.batch(2, 64, split="calib").items()}
    mm = memory.build_memory_model(cfg)

    wl = workload.WorkloadConfig(seed=args.seed, max_batch=8,
                                 short_len=(32, 128), long_len=(128, 512),
                                 long_frac=0.3)
    sampler = workload.request_sampler(wl, mm)

    if args.policy == "rl":
        print(f"training RAP controller ({args.episodes} episodes)...")
        e = env_lib.PruneEnv(model, params, calib, mm)
        tr = dqn.train(lambda: e, episodes=args.episodes,
                       request_sampler=sampler, seed=args.seed)
        print(f"  reward: first={tr.episode_rewards[0]:.3f} "
              f"last={tr.episode_rewards[-1]:.3f} "
              f"fit-rate={np.mean(tr.episode_fits):.2f}")
        controller = RAPController(model, params, calib, mm, tr.q_params)
        policy = make_policy("rl", controller=controller)
    else:
        print(f"building static policy {args.policy!r} "
              f"(available: {', '.join(available_policies())})")
        policy = make_policy(args.policy, model=model, params=params,
                             calib=calib, mm=mm, seed=args.seed)

    reqs = workload.generate(wl)[: args.requests]
    rng = np.random.default_rng(args.seed)

    if args.serial:
        server = RAPServer(model, params, policy, mode=args.mode,
                           max_new_tokens=args.max_new)
        for i, r in enumerate(reqs):
            sql = min(r.seq_len, 256)
            prompt = corpus.sample_tokens(rng, r.batch, sql)
            budget = r.budget_frac * mm.dense_peak(r.batch, sql + args.max_new)
            res = server.serve(prompt, budget)
            kept = int(res.mask.sum())
            print(f"req {i}: bs={r.batch} sql={sql} "
                  f"budget={r.budget_frac:.2f} "
                  f"→ kept {kept}/{len(res.mask)} blocks, "
                  f"peak {res.peak_bytes/1e6:.1f}MB fits={res.fits} "
                  f"decide {res.decide_s*1e3:.0f}ms infer {res.infer_s:.2f}s "
                  f"{'(new compile)' if res.compiled_new else '(cached)'}")
        print("bucket stats:", server.stats())
        return

    # ------------------------------------------------- continuous batching
    max_total = 256 + args.max_new
    full = masks.full_mask(cfg.n_layers)
    # same workload the serial path serves: requests keep their trace batch
    # size (each occupies that many cache slots)
    slots = max(args.slots, *(r.batch for r in reqs))
    max_b = max(r.batch for r in reqs)
    budget = (mm.param_bytes(full)
              + args.pool_requests * mm.state_bytes(full, max_b, max_total))
    cap = device_budget_cap()
    if cap is not None and budget > cap:
        print(f"budget {budget / 1e9:.2f}GB capped at the device's "
              f"{cap / 1e9:.2f}GB (bytes_limit less "
              f"{DEVICE_HEADROOM_BYTES / 2**30:.0f}GiB headroom)")
        budget = cap
    kv_dtype = None if args.kv_dtype == "model" else args.kv_dtype
    if kv_dtype == "auto":
        # precision as a policy action, resolved ONCE at startup (one pool
        # holds one precision): quantize when the pool cannot host the
        # full decode batch densely at model width, else keep model width
        kv_cap = budget - mm.param_bytes(full)
        dense_req = mm.state_bytes(full, 1, max_total)
        kv_dtype = "int8" if kv_cap < slots * dense_req else None
        print(f"--kv-dtype auto → {kv_dtype or 'model precision'} "
              f"(pool {kv_cap / 1e6:.1f}MB vs {slots} dense requests "
              f"{slots * dense_req / 1e6:.1f}MB)")
    executor = None
    if args.executor == "paged":
        executor = PagedExecutor(model, params, mode=args.mode,
                                 max_active=slots, kv_dtype=kv_dtype,
                                 bucket_quant=args.bucket_quant)
    elif args.executor == "sharded":
        from repro.launch.mesh import make_host_mesh, make_serve_mesh
        from repro.runtime import ShardedExecutor
        if args.mesh == "auto":
            mesh = make_serve_mesh(slots)
        else:
            try:
                d, m = (int(x) for x in args.mesh.lower().split("x"))
            except ValueError:
                ap.error(f"--mesh must be DATAxMODEL (e.g. 4x2), got "
                         f"{args.mesh!r}")
            if d * m > len(jax.devices()):
                ap.error(f"--mesh {args.mesh} needs {d * m} devices, host "
                         f"has {len(jax.devices())} (on CPU set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N)")
            if slots % d != 0:
                # serve_state_pspecs would silently fall back to full
                # replication — N-way dispatch overhead, zero DP sharding
                print(f"WARNING: data axis {d} does not divide {slots} "
                      f"slots — the slot axis will replicate instead of "
                      f"sharding (pick --slots a multiple of {d}, or "
                      f"--mesh auto)")
            mesh = make_host_mesh((d, m), ("data", "model"))
        print(f"sharded mesh: {dict(mesh.shape)} over {mesh.size} of "
              f"{len(jax.devices())} devices")
        executor = ShardedExecutor(model, mesh, params=params,
                                   max_active=slots, kv_dtype=kv_dtype)
    engine = RAPEngine(model, params, policy, EngineConfig(
        mode=args.mode, max_new_tokens=args.max_new, max_active=slots,
        max_len=max_total, budget_bytes=budget, kv_dtype=kv_dtype,
        decode_horizon=args.decode_horizon,
        max_prefill_tokens=args.max_prefill_tokens,
        preemption_enabled=args.enable_preemption,
        bucket_quant=args.bucket_quant,
        compile_cache=args.compile_cache),
        scheduler=args.scheduler, executor=executor)
    ereqs = []
    for i, r in enumerate(reqs):
        sql = min(r.seq_len, 256)
        prompt = corpus.sample_tokens(rng, r.batch, sql)
        # interactive tier: short conversational turns outrank long-form
        # documents (only consulted under --scheduler priority)
        ereqs.append(EngineRequest(rid=f"req{i}", prompt=prompt,
                                   arrival_t=r.t - reqs[0].t,
                                   priority=0 if sql <= 128 else 1))
    # time-varying budget (DESIGN.md §11): breakpoint lists on the
    # engine's virtual clock, derived from the workload or a synthetic
    # mid-serve staircase shock
    trace = None
    if args.budget_trace == "workload":
        from repro.runtime import workload_budget_trace
        t0 = reqs[0].t
        trace = [(t - t0, b) for t, b in
                 workload_budget_trace(reqs, budget)]
    elif args.budget_trace == "staircase":
        from repro.runtime import staircase_trace
        span = max(ereqs[-1].arrival_t, 0.2)
        # cut half the KV headroom (params stay resident — a 50% TOTAL
        # cut would zero the pool at smoke scale) for the middle half
        kv = budget - mm.param_bytes(full)
        shocked = (mm.param_bytes(full) + 0.5 * kv) / budget
        trace = staircase_trace(budget, 0.25 * span, 0.75 * span,
                                frac=shocked)
    if trace is not None:
        print(f"budget trace: {args.budget_trace} "
              f"({len(trace)} breakpoints, "
              f"{min(b for _, b in trace)/1e6:.1f}–"
              f"{max(b for _, b in trace)/1e6:.1f}MB), preemption "
              f"{'on' if args.enable_preemption else 'off'}")
    print(f"engine[{policy.name}/{args.scheduler}/{args.executor}]: "
          f"{len(ereqs)} requests "
          f"(batch {min(r.batch for r in reqs)}–{max(r.batch for r in reqs)}),"
          f" {slots} slots, shared pool {budget/1e6:.1f}MB total budget")
    rep = engine.run(ereqs, budget_trace=trace)
    for r in rep.results:
        if r.status == "done":
            kept = int(r.mask.sum())
            print(f"{r.rid}: kept {kept}/{len(r.mask)} blocks  "
                  f"queue {r.queue_delay_s*1e3:.0f}ms  "
                  f"ttft {r.ttft_s*1e3:.0f}ms  "
                  f"decide {r.decide_s*1e3:.0f}ms"
                  f"{' (memo)' if r.cached_decision else ''}  "
                  f"fits={r.fits}")
        else:
            print(f"{r.rid}: {r.status.upper()} ({r.reason})")
    print(f"engine: {rep.tokens_per_s:.1f} tok/s, "
          f"{rep.decode_iters} decode iters, "
          f"mean queue {rep.mean_queue_delay_s*1e3:.0f}ms, "
          f"fit-rate {rep.budget_fit_rate:.2f}")
    if args.compile_cache:
        from repro.runtime.engine import compile_cache_dir
        print(f"compile cache: {rep.compile_events} traces, "
              f"{rep.compile_cache_hits} disk hits, "
              f"{rep.compile_cache_misses} misses "
              f"({compile_cache_dir()})")
    if rep.preempted_count:
        print(f"preemption: {rep.preempted_count} preempted, "
              f"{rep.spilled_mb:.2f}MB spilled, resume p50/p99 "
              f"{rep.resume_latency.get('p50', 0.0)*1e3:.0f}/"
              f"{rep.resume_latency.get('p99', 0.0)*1e3:.0f}ms, "
              f"preempted-request itl p99 "
              f"{rep.itl_preempted.get('p99', 0.0)*1e3:.2f}ms")
    if rep.ttft.get("count"):
        print(f"latency: ttft p50/p99 {rep.ttft['p50']*1e3:.0f}/"
              f"{rep.ttft['p99']*1e3:.0f}ms, itl p50/p99 "
              f"{rep.itl['p50']*1e3:.2f}/{rep.itl['p99']*1e3:.2f}ms")
    print(f"pool: peak {rep.pool['peak_reserved_bytes']/1e6:.2f}MB "
          f"of {rep.pool['capacity_bytes']/1e6:.2f}MB, "
          f"frag {rep.pool['fragmentation']:.2f}, "
          f"measured frag {rep.measured_frag:.2f}, "
          f"overcommits {int(rep.pool['overcommit_events'])}")
    print("engine stats:", engine.stats())
    # the run's own spans and counters (repro.runtime.tracing)
    tr = rep.trace
    print("spans (count, mean ms): " + ", ".join(
        f"{name} {int(n)}/{sec / n * 1e3:.2f}"
        for name, (n, sec) in sorted(tr.span_totals.items())))
    ct = tr.counter_totals
    print(f"prefill: {int(ct.get('chunk', 0))} chunks, "
          f"{int(ct.get('chunk.tokens', 0))} tokens")
    if ct.get("launch"):
        print(f"paged decode: {int(ct['launch'])} launches, rows "
              f"{int(ct['launch.rows_occupied'])} occupied of "
              f"{int(ct['launch.rows_stepped'])} stepped, pages "
              f"{int(ct['launch.pages_with_tokens'])} with tokens of "
              f"{int(ct['launch.pages_walked'])} walked")


if __name__ == "__main__":
    main()
