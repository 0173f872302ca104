"""Engine vs one-shot serving throughput on a Poisson trace.

Replays the SAME ≥16-request Poisson arrival trace through:

  * **engine/slot** — continuous batching through ``RAPEngine`` +
    ``LocalExecutor``: one shared KV pool (admission-controlled),
    slot-batched decode over all running requests, under the chosen
    pruning policy and scheduler (per mode: masked | structural);
  * **engine/paged** — the same trace through ``PagedExecutor``
    (masked and structural modes): physically paged KV with per-request
    page tables, measuring what paging buys in *physical* internal
    fragmentation (``measured_frag``: 1 − tokens-written /
    cache-bytes-allocated, sampled per decode tick) at equal-or-better
    throughput. Structural rows run under ``--bucket-quant`` (DESIGN.md
    §9) so the compiled-executable set stays bounded, and the warmed
    structural/paged row at the top horizon is hard-gated ≥ its
    structural/slot counterpart;
  * **engine/sharded** — the same trace through ``ShardedExecutor``
    (masked mode): mesh-resident slot groups over a DP-majority host
    mesh (DESIGN.md §7). On a multi-device host the warmed sharded row
    must not be SLOWER than single-device local at equal batch — the
    horizon amortizes the collectives, and a regressive mesh would mean
    sharding costs more than it parallelizes. Gated below like the
    horizon gate, hard-failing on real accelerator meshes; fake
    host-platform CPU devices report the ratio loudly instead (threads
    on one socket measure the partition overhead without the silicon
    that pays for it);
  * **serial** — the historical one-shot path: ``RAPServer.serve()`` per
    request, each against its own instantaneous budget.

Each engine configuration is swept over the decode **horizon** H ∈
{1, 4, 8} (``EngineConfig.decode_horizon``, DESIGN.md §5): H tokens per
fused on-device loop with one device→host sync per horizon. Rows carry a
``host_ms_per_tok`` column — (wall time − time inside compiled launches
and read-backs) / generated tokens — isolating the host-side dispatch
overhead the horizon exists to shrink. After writing its document the
script FAILS (exit 1) if the warmed masked/paged row at the largest
swept horizon (H=8 vs H=1 by default) drops more than 10% of the
smallest's tok/s, or fails to beat its ``host_ms_per_tok``: amortized
dispatch is the point of the feature (tok/s at smoke scale on a small
host is compute-bound parity, and the backlog-aware clamp deliberately
trades a few % of top-horizon tok/s for lower queue delay), and a
silent regression here would invalidate the cross-PR trajectory.

Every engine row also reports request-level latency percentiles
(DESIGN.md §6): **TTFT** (arrival → first token, p50/p90/p99 ms) and
**ITL** (inter-token latency, per generated token). After the sweep an
**interference** section replays a decode-heavy trace three ways —
alone, with a long prompt injected mid-serve prefilled monolithically,
and with the same prompt prefilled in chunks
(``EngineConfig.max_prefill_tokens``) — and gates the async engine's
reason to exist: warmed decode p99 ITL under a concurrent chunked long
prefill must stay ≤ 3× the no-prefill baseline (exit 1 otherwise).

Reports aggregate tokens/sec, mean queue delay, budget-fit rate, and the
pool's reserved/in-use peaks, and writes a machine-readable
``experiments/bench/BENCH_engine.json`` (schema below) so the perf
trajectory is tracked across PRs. The pool-never-exceeds-budget invariant
is asserted in ``tests/test_engine.py``; this script is the measurement
rig.

  PYTHONPATH=src python benchmarks/bench_engine_throughput.py \
      --requests 16 --rate 50 --max-new 8 --policy rl --scheduler fifo
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=1000.0,
                    help="Poisson arrival rate (req/s). Keep the offered "
                         "load (rate × max_new tok/s) well above serving "
                         "capacity: throughput is tokens/makespan on the "
                         "arrival clock, so an undersaturated trace caps "
                         "both servers at the offered rate and the "
                         "comparison measures nothing")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--pool-requests", type=float, default=2.5,
                    help="pool sized for this many concurrent dense requests")
    ap.add_argument("--modes", nargs="+",
                    default=["masked", "structural"])
    ap.add_argument("--horizons", nargs="+", type=int, default=[1, 4, 8],
                    help="decode_horizon sweep: tokens fused per engine "
                         "macro-tick (one compiled launch, one sync)")
    ap.add_argument("--policy", default="rl",
                    help="pruning policy (rl or any registered baseline)")
    ap.add_argument("--scheduler", default="fifo",
                    choices=("fifo", "sjf", "priority"))
    ap.add_argument("--min-tok-s", type=float, default=0.0,
                    help="absolute floor for the warmed masked/paged row "
                         "at the top horizon (0 disables); machine-"
                         "specific, so off by default — the committed "
                         "repo-root BENCH_engine.json is produced with "
                         "--min-tok-s 1500 to pin the PR 4 level")
    ap.add_argument("--kv-dtypes", nargs="*", default=["int8"],
                    help="quantized KV page precisions to sweep (int8/fp8) "
                         "in addition to the model-precision rows: one "
                         "masked slot + paged row each at the top horizon. "
                         "Pass no values to disable. The int8 paged row is "
                         "hard-gated: admitted tokens per MB of pool must "
                         "be ≥ 1.8× the model-precision paged row at equal "
                         "budget, and warmed tok/s ≥ 0.9× of it")
    ap.add_argument("--chunk", type=int, default=16,
                    help="max_prefill_tokens for the interference "
                         "section's chunked run (0 disables the section)")
    ap.add_argument("--no-scenarios", action="store_true",
                    help="skip the elastic-budget scenario section "
                         "(budget-shock staircase + cancellation storm on "
                         "the paged executor, DESIGN.md §11)")
    ap.add_argument("--bucket-quant", default="pow2",
                    choices=("none", "layer", "pow2"),
                    help="structural bucket-shape quantization ladder "
                         "(DESIGN.md §9). The bench defaults to pow2 — an "
                         "adaptive policy's mask stream must not compile "
                         "one executable per distinct mask on the timed "
                         "path")
    ap.add_argument("--compile-cache", action="store_true",
                    help="enable the persistent XLA compilation cache "
                         "(DESIGN.md §9; JAX_COMPILATION_CACHE_DIR when "
                         "set, else .jax_cache/ in the checkout). A second "
                         "bench invocation re-traces but loads executables "
                         "from disk instead of recompiling")
    ap.add_argument("--assert-cache-replay", action="store_true",
                    help="hard gate for warmed-replay CI: with "
                         "--compile-cache pre-populated by an earlier "
                         "identical invocation, this process must hit the "
                         "disk cache (> 0 hits) and compile nearly "
                         "nothing new (≤ 2 misses) — exit 1 otherwise")
    ap.add_argument("--scenario-requests", type=int, default=12,
                    help="requests per scenario run (heavy-tailed "
                         "lognormal prompt mix)")
    ap.add_argument("--shock-frac", type=float, default=0.5,
                    help="fraction of the KV headroom removed mid-serve "
                         "by the budget-shock scenario")
    ap.add_argument("--cancel-frac", type=float, default=0.25,
                    help="fraction of requests cancelled at random "
                         "lifecycle stages by the storm scenario")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed replays per warmed row; the best (highest "
                         "tok/s) is reported, so cross-row gates compare "
                         "configuration capability rather than host noise. "
                         "Ignored under --no-warmup (cold rows are "
                         "single-shot by design)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the compile warm-up replay (reports cold "
                         "numbers dominated by XLA compile latency)")
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args()

    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core import dqn, masks, memory
    from repro.core.controller import RAPController
    from repro.core.policy import make_policy
    from repro.core.workload import PoissonConfig, poisson_requests
    from repro.data import SyntheticCorpus
    from repro.launch.mesh import make_serve_mesh
    from repro.models import registry
    from repro.runtime import (EngineConfig, EngineRequest, PagedExecutor,
                               RAPEngine, RAPServer, ShardedExecutor)

    cache_dir = ""
    if args.compile_cache:
        # enable BEFORE the first compile: JAX latches the cache-used
        # decision process-wide at first use (see enable_compile_cache)
        from repro.runtime.engine import enable_compile_cache
        cache_dir = enable_compile_cache()

    cfg = get_smoke_config(args.arch).replace(n_layers=args.layers)
    model = registry.build(cfg)
    params = jax.jit(model.init)(jax.random.key(args.seed))
    corpus = SyntheticCorpus(cfg.vocab_size, seed=args.seed)
    calib = {k: jax.numpy.asarray(v)
             for k, v in corpus.batch(2, 64, split="calib").items()}
    mm = memory.build_memory_model(cfg)
    def build_policy():
        if args.policy == "rl":
            qp = dqn.init_qnet(jax.random.key(args.seed),
                               2 * cfg.n_layers + 4,
                               2 * cfg.n_layers + 1, 32)
            controller = RAPController(model, params, calib, mm, qp)
            return make_policy("rl", controller=controller)
        return make_policy(args.policy, model=model, params=params,
                           calib=calib, mm=mm, seed=args.seed)

    policy = build_policy()

    # prompt lengths round to 16 — serving engines bucket shapes so compiles
    # amortize; finer granularity just measures XLA compile latency
    wl = PoissonConfig(seed=args.seed, n_requests=args.requests,
                       rate=args.rate, short_len=(16, 48),
                       long_len=(48, 96), round_len_to=16)
    trace = poisson_requests(wl)
    rng = np.random.default_rng(args.seed)
    prompts = [corpus.sample_tokens(rng, 1, r.seq_len) for r in trace]
    max_total = max(r.seq_len for r in trace) + args.max_new

    full = masks.full_mask(cfg.n_layers)
    state1 = mm.state_bytes(full, 1, max_total)
    budget = mm.param_bytes(full) + args.pool_requests * state1
    print(f"[bench] {len(trace)} requests, prompt lens "
          f"{min(r.seq_len for r in trace)}–{max(r.seq_len for r in trace)}, "
          f"budget {budget / 1e6:.2f} MB "
          f"(pool ≈ {args.pool_requests:.1f} dense requests), "
          f"policy={policy.name} scheduler={args.scheduler}")

    reqs = [EngineRequest(rid=f"q{i}", prompt=np.asarray(p, np.int32),
                          arrival_t=trace[i].t)
            for i, p in enumerate(prompts)]

    serve_mesh = make_serve_mesh(args.slots)

    def _ms_pcts(summary):
        # {"p50","p90","p99"} in milliseconds from an EngineReport latency
        # summary (seconds)
        return {k: round(summary.get(k, 0.0) * 1e3, 3)
                for k in ("p50", "p90", "p99")}

    def run_engine(mode, executor_kind, horizon, kv_dtype=None):
        executor = None
        if executor_kind == "paged":
            executor = PagedExecutor(model, params, mode=mode,
                                     max_active=args.slots,
                                     kv_dtype=kv_dtype,
                                     bucket_quant=args.bucket_quant)
        elif executor_kind == "sharded":
            executor = ShardedExecutor(model, serve_mesh, params=params,
                                       max_active=args.slots)
        engine = RAPEngine(model, params, policy, EngineConfig(
            mode=mode, max_new_tokens=args.max_new, max_active=args.slots,
            max_len=max_total, budget_bytes=budget, decode_horizon=horizon,
            kv_dtype=kv_dtype, bucket_quant=args.bucket_quant,
            compile_cache=args.compile_cache),
            scheduler=args.scheduler, executor=executor)
        if not args.no_warmup:      # steady-state: compiles amortize away
            for _ in range(5):
                if engine.run(reqs).compile_events == 0:
                    break
        # best-of-N timed replays: the timed run is ~100 ms on a warmed
        # engine, so repeats are nearly free, and every gate below compares
        # rows measured minutes apart — a single scheduler hiccup or stray
        # compile on a shared host would fail a gate that the configuration
        # actually clears. Cold runs (--no-warmup) stay single-shot: their
        # point is the compile-dominated first replay.
        rep = None
        for _ in range(1 if args.no_warmup else max(1, args.repeats)):
            r = engine.run(reqs)
            assert r.rejected == 0, "trace should fit the pool eventually"
            assert (r.pool["peak_reserved_bytes"]
                    <= r.pool["capacity_bytes"] + 1e-6)
            if rep is None or r.tokens_per_s > rep.tokens_per_s:
                rep = r
        # admitted-tokens-per-MB: KV tokens one MB of pool storage holds at
        # this row's precision — the capacity axis quantized pages buy.
        # Paged rows read the physical page geometry; slot rows derive it
        # from the analytical per-token KV bytes at the row's byte ratio.
        pool_obj = getattr(engine, "pool", None)
        if (pool_obj is not None and pool_obj.page_bytes
                and pool_obj.tokens_per_page):
            tok_per_mb = pool_obj.tokens_per_page * 1e6 / pool_obj.page_bytes
        else:
            from repro.runtime.engine import _kv_byte_ratio
            per_tok = (mm.state_bytes(full, 1, 1)
                       - mm.state_bytes(full, 1, 0))
            per_tok *= _kv_byte_ratio(kv_dtype, cfg)
            tok_per_mb = 1e6 / max(per_tok, 1e-9)
        return rep, tok_per_mb

    rows = []
    # slot executor per requested mode; paged rides along in masked mode
    # (the only mode it serves) so every bench run tracks the paged-vs-slot
    # fragmentation and throughput delta. Heterogeneous-mixer archs
    # (griffin/mamba) stay slot-only — PagedExecutor rejects them.
    from repro.models.decoder import default_layout
    layout = default_layout(cfg)
    paged_ok = (len(layout) > 0
                and all(s.mixer == "attn" and s.ffn == layout[0].ffn
                        for s in layout))
    run_matrix = [(m, "slot") for m in args.modes]
    if paged_ok:
        # paged rides along in every mode it serves (masked + structural)
        # so each bench run tracks the paged-vs-slot delta per mode
        run_matrix.extend((m, "paged") for m in args.modes
                          if m in ("masked", "structural"))
    elif "masked" in args.modes or "structural" in args.modes:
        print(f"[bench] skipping paged runs: {args.arch} is not a uniform "
              f"all-attention layout")
    if "masked" in args.modes:
        # sharded serves ANY layout in masked mode (gated groups); on a
        # single-device host this is the (1, 1) degenerate mesh and the
        # row measures the jit-with-shardings overhead floor
        run_matrix.append(("masked", "sharded"))
        print(f"[bench] sharded mesh: {dict(serve_mesh.shape)} over "
              f"{serve_mesh.size} of {len(jax.devices())} devices")
    serial_cache = {}
    runs = [(m, e, h, None) for m, e in run_matrix for h in args.horizons]
    # quantized rows: one slot + one paged row per requested precision at
    # the top horizon, same trace and budget — the per-MB capacity delta
    # and the fused-dequant throughput cost, measured against the
    # model-precision rows above
    h_top_kv = max(args.horizons)
    for kv in args.kv_dtypes:
        if "masked" in args.modes:
            runs.append(("masked", "slot", h_top_kv, kv))
            if paged_ok:
                runs.append(("masked", "paged", h_top_kv, kv))
    for mode, executor_kind, horizon, kv_dtype in runs:
        rep, tok_per_mb = run_engine(mode, executor_kind, horizon, kv_dtype)

        # ---- serial one-shot replay of the same trace (once per mode)
        def serial_replay(server):
            # one-shot serving is sequential: request i starts at
            # max(previous finish, its arrival) — same arrival process the
            # engine sees, so both report tokens / makespan
            t, tokens, fits = 0.0, 0, []
            for i, p in enumerate(prompts):
                per_req_budget = trace[i].budget_frac * mm.dense_peak(
                    1, trace[i].seq_len + args.max_new)
                t0 = time.perf_counter()
                r = server.serve(np.asarray(p, np.int32), per_req_budget)
                dur = time.perf_counter() - t0
                t = max(t, trace[i].t) + dur
                tokens += r.tokens.size
                fits.append(r.fits)
            return tokens / max(t, 1e-9), fits

        if mode not in serial_cache:
            server = RAPServer(model, params, policy, mode=mode,
                               max_new_tokens=args.max_new)
            if not args.no_warmup:
                serial_replay(server)
            serial_cache[mode] = serial_replay(server)
        serial_tps, serial_fits = serial_cache[mode]

        speedup = rep.tokens_per_s / max(serial_tps, 1e-9)
        # host-side share of serving: wall time not spent inside compiled
        # launches / read-backs, per generated token — the dispatch
        # overhead the horizon decode exists to amortize
        host_ms = ((rep.wall_s - rep.launch_s)
                   / max(rep.generated_tokens, 1) * 1e3)
        row = {
            "mode": mode,
            "executor": executor_kind,
            "decode_horizon": horizon,
            "kv_dtype": kv_dtype or "model",
            "kv_tok_per_mb": round(tok_per_mb, 1),
            "engine_tok_s": round(rep.tokens_per_s, 1),
            "serial_tok_s": round(serial_tps, 1),
            "speedup": round(speedup, 2),
            "queue_delay_ms": round(rep.mean_queue_delay_s * 1e3, 1),
            "fit_rate": round(rep.budget_fit_rate, 3),
            "decode_iters": rep.decode_iters,
            "compiles": rep.compile_events,
            "cache_hits": rep.compile_cache_hits,
            "cache_misses": rep.compile_cache_misses,
            "host_ms_per_tok": round(host_ms, 4),
            "pool_peak_mb": round(rep.pool["peak_reserved_bytes"] / 1e6, 3),
            "pool_frag": round(rep.pool["fragmentation"], 3),
            "measured_frag": round(rep.measured_frag, 3),
            # request-level latency percentiles (DESIGN.md §6): TTFT is
            # arrival → first token; ITL per generated decode token
            "ttft_ms": _ms_pcts(rep.ttft),
            "itl_ms": _ms_pcts(rep.itl),
        }
        rows.append(row)
        print(f"[bench] {mode:10s}/{executor_kind:5s} H={horizon} "
              f"kv={row['kv_dtype']:5s} "
              f"engine {row['engine_tok_s']:8.1f} tok/s  "
              f"serial {row['serial_tok_s']:8.1f} tok/s  "
              f"speedup ×{row['speedup']:.2f}  "
              f"host {row['host_ms_per_tok']:.3f} ms/tok  "
              f"ttft p50/p99 {row['ttft_ms']['p50']:.1f}/"
              f"{row['ttft_ms']['p99']:.1f} ms  "
              f"itl p99 {row['itl_ms']['p99']:.2f} ms  "
              f"measured-frag {row['measured_frag']:.3f}")
        if speedup <= 1.0:
            print(f"[bench] WARNING: engine did not beat serial in {mode}")

    by_exec = {(r["mode"], r["executor"], r["decode_horizon"],
                r["kv_dtype"]): r for r in rows}
    h_top = max(args.horizons)
    slot = by_exec.get(("masked", "slot", h_top, "model"))
    paged = by_exec.get(("masked", "paged", h_top, "model"))

    # ---- horizon sanity warning: H > 1 should never lose to H = 1 ------
    # (the fused loop exists to amortize dispatch; a slower bigger horizon
    # means macro-ticks are stalling something — admission, completions)
    h_min = min(args.horizons)
    for (m, e) in {(r["mode"], r["executor"]) for r in rows}:
        base = by_exec.get((m, e, h_min, "model"))
        if not base or h_min != 1:
            continue
        for h in args.horizons:
            r = by_exec.get((m, e, h, "model"))
            if r and h > 1 and r["engine_tok_s"] < base["engine_tok_s"]:
                print(f"[bench] WARNING: {m}/{e} H={h} "
                      f"({r['engine_tok_s']:.1f} tok/s) underperforms H=1 "
                      f"({base['engine_tok_s']:.1f} tok/s) — the horizon "
                      f"should amortize dispatch, not stall admission")
    if slot and paged:
        print(f"[bench] paged vs slot (masked, H={h_top}): "
              f"frag {paged['measured_frag']:.3f} vs "
              f"{slot['measured_frag']:.3f}, "
              f"tok/s {paged['engine_tok_s']:.1f} vs "
              f"{slot['engine_tok_s']:.1f} "
              f"(×{paged['engine_tok_s'] / max(slot['engine_tok_s'], 1e-9):.2f})")
        if paged["measured_frag"] >= slot["measured_frag"]:
            print("[bench] WARNING: paged fragmentation not below slot")
        if paged["engine_tok_s"] < 0.9 * slot["engine_tok_s"]:
            print("[bench] WARNING: paged throughput >10% below slot")

    # ---- interference: decode ITL under a concurrent long prefill ----
    # A decode-heavy trace (3 short requests generating 64 tokens each at
    # H=2) is replayed three ways: alone (baseline), with a long prompt
    # injected shortly after decode starts and prefilled monolithically,
    # and with the same prompt prefilled in `--chunk`-token slices
    # interleaved between decode launches. The chunked run is what the
    # async engine promises: the long prefill's host/device time is
    # amortized across macro-ticks instead of stalling the running
    # decodes for the whole prompt.
    interference = None
    if args.chunk > 0:
        i_short_new, i_long_len, i_horizon = 64, 96, 2
        i_max_len = 128
        i_budget = (mm.param_bytes(full)
                    + 4.5 * mm.state_bytes(full, 1, i_max_len))
        shorts = [EngineRequest(
            rid=f"d{i}", prompt=np.asarray(
                corpus.sample_tokens(rng, 1, 16), np.int32),
            arrival_t=0.0) for i in range(3)]
        long_req = EngineRequest(
            rid="long", prompt=np.asarray(
                corpus.sample_tokens(rng, 1, i_long_len), np.int32),
            arrival_t=0.01, max_new=2)

        def run_interference(reqs_i, chunk):
            engine = RAPEngine(model, params, policy, EngineConfig(
                mode="masked", max_new_tokens=i_short_new,
                max_active=args.slots, max_len=i_max_len,
                budget_bytes=i_budget, decode_horizon=i_horizon,
                max_prefill_tokens=chunk), scheduler=args.scheduler)
            if not args.no_warmup:
                for _ in range(5):
                    if engine.run(reqs_i).compile_events == 0:
                        break
            rep = engine.run(reqs_i)
            assert rep.rejected == 0
            return rep

        base_rep = run_interference(shorts, 0)
        mono_rep = run_interference(shorts + [long_req], 0)
        chunk_rep = run_interference(shorts + [long_req], args.chunk)
        interference = {
            "config": {"decode_requests": len(shorts),
                       "decode_new_tokens": i_short_new,
                       "long_prompt_len": i_long_len,
                       "decode_horizon": i_horizon,
                       "chunk": args.chunk},
            "baseline_itl_ms": _ms_pcts(base_rep.itl),
            "monolithic_itl_ms": _ms_pcts(mono_rep.itl),
            "chunked_itl_ms": _ms_pcts(chunk_rep.itl),
            "monolithic_ttft_ms": _ms_pcts(mono_rep.ttft),
            "chunked_ttft_ms": _ms_pcts(chunk_rep.ttft),
        }
        print(f"[bench] interference (decode p99 ITL): baseline "
              f"{interference['baseline_itl_ms']['p99']:.2f} ms, "
              f"+long monolithic "
              f"{interference['monolithic_itl_ms']['p99']:.2f} ms, "
              f"+long chunked({args.chunk}) "
              f"{interference['chunked_itl_ms']['p99']:.2f} ms")
    # ---- elastic-budget scenarios (DESIGN.md §11) --------------------
    # Fault-injection on the paged executor (slot fallback for non-
    # uniform layouts): a mid-serve budget-shock staircase (preemption +
    # KV spill/resume must keep completing requests and recover warmed
    # throughput) and a cancellation storm (≥ --cancel-frac of requests
    # cancelled at random lifecycle stages must leave zero live rids and
    # zero leaked pages). Both hard-gate after the doc is written.
    scenarios = None
    if not args.no_scenarios and "masked" in args.modes:
        from repro.runtime import (heavy_tailed_requests, run_budget_shock,
                                   run_cancellation_storm)
        sc_exec = "paged" if paged_ok else "slot"
        sc_max_new, sc_max_prompt = 4, 64
        sc_max_len = sc_max_prompt + sc_max_new
        sc_budget = (mm.param_bytes(full)
                     + args.pool_requests * mm.state_bytes(full, 1,
                                                           sc_max_len))
        tok_src = corpus.sample_tokens(rng, 1, sc_max_prompt)
        # fresh policy: the row sweep's policy memoized decisions stamped
        # with each row's kv_dtype, and a cached int8 decision replayed
        # against the scenarios' model-precision pool is a dtype mismatch
        sc_policy = build_policy()

        def sc_engine():
            executor = (PagedExecutor(model, params, max_active=args.slots)
                        if sc_exec == "paged" else None)
            return RAPEngine(model, params, sc_policy, EngineConfig(
                mode="masked", max_new_tokens=sc_max_new,
                max_active=args.slots, max_len=sc_max_len,
                budget_bytes=sc_budget, decode_horizon=2),
                scheduler=args.scheduler, executor=executor)

        def sc_reqs(seed):
            return heavy_tailed_requests(
                tok_src, args.scenario_requests, seed=seed,
                max_len=sc_max_prompt, max_new=sc_max_new)

        shock_eng = sc_engine()
        if not args.no_warmup:      # warm compiles so phase rates are real
            shock_eng.run(sc_reqs(args.seed))
        shock = run_budget_shock(shock_eng, sc_reqs(args.seed),
                                 budget_bytes=sc_budget,
                                 frac=args.shock_frac)
        shock_rep = shock.pop("report")
        storm = run_cancellation_storm(sc_engine(), sc_reqs(args.seed + 1),
                                       cancel_frac=args.cancel_frac,
                                       seed=args.seed)
        storm_rep = storm.pop("report")
        scenarios = {
            "executor": sc_exec,
            "budget_shock": {
                **{k: v for k, v in shock.items()},
                "itl_ms": _ms_pcts(shock_rep.itl),
                "itl_preempted_ms": _ms_pcts(shock_rep.itl_preempted),
                "itl_preempted_count": shock_rep.itl_preempted["count"],
            },
            "cancellation_storm": storm,
        }
        print(f"[bench] budget shock ({sc_exec}, −{args.shock_frac:.0%} KV "
              f"headroom): pre/shock/post "
              f"{shock['pre']['completed']:.0f}/"
              f"{shock['shock']['completed']:.0f}/"
              f"{shock['post']['completed']:.0f} done, replay "
              f"{shock['replay_tok_per_s']:.0f} vs warmed "
              f"{shock['warmed_tok_per_s']:.0f} tok/s (recovery "
              f"×{shock['recovery_ratio']:.2f}), preempted "
              f"{shock['preempted_count']}, spilled "
              f"{shock['spilled_mb']:.2f} MB, resume p50 "
              f"{shock['resume_p50_s'] * 1e3:.1f} ms")
        print(f"[bench] cancellation storm ({sc_exec}): "
              f"{storm['cancelled']}/{storm['n_requests']} cancelled "
              f"(quota {storm['cancel_quota']}), {storm['done']} done, "
              f"live {storm['live_requests']:.0f}, spilled "
              f"{storm['spilled_requests']:.0f}, leaked pages "
              f"{storm['leaked_pages']:.0f}")
    elif not args.no_scenarios:
        print("[bench] skipping scenarios (masked mode not in --modes)")

    os.makedirs(args.out, exist_ok=True)
    # per-PR perf trajectory: one machine-readable document with the run
    # configuration, so cross-PR comparisons know what was measured
    doc = {
        "schema": 8,        # v8: structural serving at speed (DESIGN.md §9)
                            # — the run matrix gains structural/paged rows
                            # (PagedExecutor now serves structural mode over
                            # per-bucket compacted layer stacks; the warmed
                            # structural/paged row at the top horizon is
                            # hard-gated ≥ its structural/slot counterpart);
                            # structural rows run under --bucket-quant
                            # (default pow2: bounded compiled-executable
                            # set); rows gain cache_hits/cache_misses from
                            # the persistent XLA compilation cache
                            # (--compile-cache) and the document gains
                            # a "compile_cache" section;
                            # --assert-cache-replay hard-gates a warmed
                            # second invocation to near-zero recompiles.
                            # Config gains bucket_quant + compile_cache_dir.
                            # v7: elastic-budget scenarios (DESIGN.md §11) —
                            # the document gains a "scenarios" section:
                            # budget_shock (per-phase completion/tok-s under
                            # a mid-serve KV-headroom staircase cut, with
                            # preempted/spilled/resume-latency and separate
                            # preempted-request ITL percentiles) and
                            # cancellation_storm (pool-ledger invariants
                            # after cancelling ≥ --cancel-frac of requests
                            # at random lifecycle stages). Hard-gated:
                            # shock+post phases must complete > 0 requests,
                            # the full-budget replay after the shocked run
                            # ≥ 0.9× the pre-shock warmed tok/s, storm
                            # ends with zero live rids and zero leaked
                            # pages. Config gains scenario knobs.
                            # v6: quantized KV pages (DESIGN.md §4) — rows
                            # gain kv_dtype ("model"|int8|fp8) and
                            # kv_tok_per_mb (KV tokens one MB of pool
                            # holds at the row's precision); --kv-dtypes
                            # adds masked slot+paged quantized rows at the
                            # top horizon, int8 paged hard-gated ≥ 1.8×
                            # the model-precision row's kv_tok_per_mb and
                            # (warmed) ≥ 0.9× its tok/s; warmed rows are
                            # best-of---repeats timed replays; config gains
                            # kv_dtypes + repeats. v5: async engine latency
                            # (DESIGN.md §6) —
                            # rows gain ttft_ms/itl_ms {p50,p90,p99} and
                            # the document gains the "interference"
                            # section (decode ITL under a concurrent
                            # monolithic vs chunked long prefill). v4
                            # added sharded executor rows (mesh-resident
                            # slot groups, DESIGN.md §7) — executor gains
                            # "sharded" and config gains mesh (axis sizes)
                            # + devices. v3 added the horizon sweep
                            # (decode_horizon, host_ms_per_tok). v2 added
                            # executor (slot|paged) + measured_frag.
        "bench": "engine_throughput",
        "config": {
            "arch": args.arch, "layers": args.layers,
            "requests": args.requests, "rate": args.rate,
            "max_new": args.max_new, "slots": args.slots,
            "pool_requests": args.pool_requests, "policy": policy.name,
            "scheduler": args.scheduler, "seed": args.seed,
            "warmup": not args.no_warmup,
            "repeats": 1 if args.no_warmup else max(1, args.repeats),
            "horizons": list(args.horizons),
            "kv_dtypes": list(args.kv_dtypes),
            "mesh": {str(k): int(v) for k, v in serve_mesh.shape.items()},
            "devices": len(jax.devices()),
            "scenario_requests": args.scenario_requests,
            "shock_frac": args.shock_frac,
            "cancel_frac": args.cancel_frac,
            "bucket_quant": args.bucket_quant,
            "compile_cache_dir": cache_dir,
        },
        "rows": rows,
        "interference": interference,
        "scenarios": scenarios,
    }
    if args.compile_cache:
        from repro.runtime.engine import _CACHE_EVENTS
        doc["compile_cache"] = {"dir": cache_dir,
                                "hits": _CACHE_EVENTS["hits"],
                                "misses": _CACHE_EVENTS["misses"]}
        print(f"[bench] compile cache: {doc['compile_cache']['hits']} disk "
              f"hits, {doc['compile_cache']['misses']} misses "
              f"({cache_dir})")
    bench_out = os.path.join(args.out, "BENCH_engine.json")
    with open(bench_out, "w") as f:
        json.dump(doc, f, indent=1)
    # rows-only file kept for pre-split consumers of the old layout
    legacy_out = os.path.join(args.out, "engine_throughput.json")
    with open(legacy_out, "w") as f:
        json.dump(rows, f, indent=1)
    # CSV summary: scalar columns only (nested percentile dicts live in
    # the JSON document)
    hdr = [k for k in rows[0] if not isinstance(rows[0][k], dict)]
    print(",".join(hdr))
    for r in rows:
        print(",".join(str(r[h]) for h in hdr))
    print(f"[bench] wrote {bench_out}")

    # Horizon perf gate — AFTER the doc is written, so a failing run still
    # leaves its machine-readable rows behind for diagnosis. Compares the
    # sweep's endpoints, so custom --horizons stay gated too.
    h_lo, h_hi = min(args.horizons), max(args.horizons)
    lo = by_exec.get(("masked", "paged", h_lo, "model"))
    hi = by_exec.get(("masked", "paged", h_hi, "model"))
    if not (lo and hi) or h_lo == h_hi:
        print("[bench] skipping horizon gate (no masked/paged rows at two "
              "distinct horizons)")
    elif args.no_warmup:
        # cold runs measure per-run XLA compile latency (a bigger horizon
        # compiles a bigger scan), not serving throughput — gate only warmed
        print(f"[bench] skipping H={h_hi}>H={h_lo} gate (--no-warmup: "
              f"numbers are compile-dominated)")
    elif hi["engine_tok_s"] < 0.9 * lo["engine_tok_s"]:
        raise SystemExit(
            f"[bench] FAIL: masked/paged H={h_hi} "
            f"({hi['engine_tok_s']:.1f} tok/s) is more than 10% below "
            f"H={h_lo} ({lo['engine_tok_s']:.1f} tok/s) — the fused "
            f"horizon loop must not cost throughput; a regression "
            f"here invalidates the perf trajectory")
    elif hi["host_ms_per_tok"] >= lo["host_ms_per_tok"]:
        # tok/s at the two endpoints is compute-bound parity on a small
        # host — the horizon's own promise is amortized dispatch, which
        # host_ms_per_tok measures directly (the backlog-aware clamp also
        # deliberately trades a few % of H=8 tok/s for ~2× lower queue
        # delay, see EngineConfig.decode_horizon)
        raise SystemExit(
            f"[bench] FAIL: masked/paged H={h_hi} host overhead "
            f"({hi['host_ms_per_tok']:.3f} ms/tok) does not beat "
            f"H={h_lo} ({lo['host_ms_per_tok']:.3f} ms/tok) — the fused "
            f"horizon loop exists to amortize per-token dispatch")

    # Quantized-KV gate — the capacity claim int8 pages exist for: at
    # equal budget, the int8 paged pool must hold ≥ 1.8× the KV tokens per
    # MB of the model-precision pool (narrower elements minus the per-page
    # scale overhead), and (warmed) serve ≥ 0.9× its throughput — the
    # fused-dequant kernel must not give the capacity win back in tok/s.
    # The per-MB ratio is page geometry, not timing, so it gates cold
    # runs too.
    q8 = by_exec.get(("masked", "paged", h_top, "int8"))
    base8 = by_exec.get(("masked", "paged", h_top, "model"))
    if not (q8 and base8):
        print("[bench] skipping int8 gate (no masked/paged int8+model "
              "rows at the top horizon)")
    else:
        ratio_mb = q8["kv_tok_per_mb"] / max(base8["kv_tok_per_mb"], 1e-9)
        ratio_ts = (q8["engine_tok_s"]
                    / max(base8["engine_tok_s"], 1e-9))
        print(f"[bench] int8 vs model paged (masked, H={h_top}): "
              f"{q8['kv_tok_per_mb']:.0f} vs {base8['kv_tok_per_mb']:.0f} "
              f"tok/MB (×{ratio_mb:.2f}), tok/s ×{ratio_ts:.2f}")
        if ratio_mb < 1.8:
            raise SystemExit(
                f"[bench] FAIL: int8 paged admitted-tokens-per-MB is only "
                f"×{ratio_mb:.2f} the model-precision row (need ≥ 1.8×) — "
                f"quantized pages must buy real KV capacity at equal "
                f"budget")
        if args.no_warmup:
            print("[bench] skipping int8 throughput gate (--no-warmup: "
                  "numbers are compile-dominated)")
        elif ratio_ts < 0.9:
            raise SystemExit(
                f"[bench] FAIL: int8 paged throughput is ×{ratio_ts:.2f} "
                f"the model-precision row (need ≥ 0.9×) — the fused "
                f"dequant path must not give the capacity win back")

    # Structural-paged gate (DESIGN.md §9) — paged structural decode runs
    # per-bucket compacted stacks over the shared page pool; at the top
    # horizon the warmed paged row must not be slower than structural/slot
    # (same compacted compute, better packing). Hard gate: a regression
    # here means the structural paged path costs more than it serves.
    st_slot = by_exec.get(("structural", "slot", h_top, "model"))
    st_paged = by_exec.get(("structural", "paged", h_top, "model"))
    if not (st_slot and st_paged):
        print("[bench] skipping structural-paged gate (no structural "
              "slot+paged rows at the top horizon)")
    elif args.no_warmup:
        print("[bench] skipping structural-paged gate (--no-warmup: "
              "numbers are compile-dominated)")
    else:
        ratio = (st_paged["engine_tok_s"]
                 / max(st_slot["engine_tok_s"], 1e-9))
        print(f"[bench] structural paged vs slot (H={h_top}): "
              f"{st_paged['engine_tok_s']:.1f} vs "
              f"{st_slot['engine_tok_s']:.1f} tok/s (×{ratio:.2f})")
        # 5% band: the two warmed rows are typically within measurement
        # noise of each other (same compacted compute), and best-of-
        # --repeats can land either side of parity on a shared host
        if ratio < 0.95:
            raise SystemExit(
                f"[bench] FAIL: warmed structural/paged H={h_top} "
                f"({st_paged['engine_tok_s']:.1f} tok/s) is ×{ratio:.2f} "
                f"of structural/slot ({st_slot['engine_tok_s']:.1f} "
                f"tok/s, need ≥ 0.95×) — paged structural decode must "
                f"not cost throughput against the slot path it "
                f"generalizes")

    # Cache-replay gate (DESIGN.md §9, opt-in) — CI runs the bench twice
    # with --compile-cache; the second invocation passes
    # --assert-cache-replay and must load its executables from disk: same
    # config ⇒ same traces ⇒ every compile should be a cache hit. A small
    # miss slack absorbs executables whose keys legitimately vary across
    # processes (e.g. autotuning); near-zero is the contract.
    if args.assert_cache_replay:
        if not args.compile_cache:
            raise SystemExit("[bench] FAIL: --assert-cache-replay needs "
                             "--compile-cache")
        from repro.runtime.engine import _CACHE_EVENTS
        hits, misses = _CACHE_EVENTS["hits"], _CACHE_EVENTS["misses"]
        if hits <= 0 or misses > 2:
            raise SystemExit(
                f"[bench] FAIL: warmed replay did not reuse the persistent "
                f"compile cache ({hits} hits, {misses} misses; need > 0 "
                f"hits and ≤ 2 misses) — a second identical invocation "
                f"must load executables from {cache_dir}, "
                f"not recompile the serving set")
        print(f"[bench] cache replay gate passed: {hits} hits, "
              f"{misses} misses")

    # Absolute-throughput gate (opt-in, machine-specific): the warmed
    # masked/paged row at the top horizon must hold the floor the
    # previous PR's committed run established on the same machine.
    if args.min_tok_s > 0 and not args.no_warmup:
        anchor = by_exec.get(("masked", "paged", h_top, "model")) or \
            by_exec.get(("masked", "slot", h_top, "model"))
        if anchor and anchor["engine_tok_s"] < args.min_tok_s:
            raise SystemExit(
                f"[bench] FAIL: warmed masked/{anchor['executor']} "
                f"H={h_top} ({anchor['engine_tok_s']:.1f} tok/s) is below "
                f"the --min-tok-s floor ({args.min_tok_s:.0f} tok/s) — "
                f"throughput regressed against the committed trajectory")

    # Chunked-prefill interference gate — AFTER the doc write, like the
    # horizon gate. The async engine's latency contract: with a long
    # prompt prefilled in chunks interleaved between decode launches,
    # warmed decode p99 ITL must stay within 3× the no-prefill baseline.
    # Monolithic prefill is reported but not gated — stalling for the
    # whole prompt is exactly the behaviour chunking replaces. A 50 µs
    # floor keeps degenerate sub-tick baselines from making 3× meaningless.
    if interference is None:
        print("[bench] skipping interference gate (--chunk 0)")
    elif args.no_warmup:
        print("[bench] skipping interference gate (--no-warmup: numbers "
              "are compile-dominated)")
    else:
        base_p99 = interference["baseline_itl_ms"]["p99"]
        chunk_p99 = interference["chunked_itl_ms"]["p99"]
        limit = 3.0 * max(base_p99, 0.05)
        if chunk_p99 > limit:
            raise SystemExit(
                f"[bench] FAIL: decode p99 ITL under a concurrent chunked "
                f"long prefill ({chunk_p99:.2f} ms) exceeds 3× the "
                f"no-prefill baseline ({base_p99:.2f} ms) — chunked "
                f"prefill must bound decode latency interference; a "
                f"regression here invalidates the async-engine contract")

    # Sharded gate — on a multi-device host, the warmed sharded row at the
    # top horizon must not be slower than single-device local at equal
    # batch: the horizon pays the mesh's collectives once per H tokens, so
    # sharding must amortize, not regress. Enforced on real accelerator
    # meshes only: fake host-platform CPU "devices"
    # (XLA_FLAGS=--xla_force_host_platform_device_count) are threads on
    # one socket, so the partition/dispatch overhead they measure is real
    # but the parallel speedup that would pay for it is structurally
    # impossible — there, the ratio is reported loudly instead of failing.
    # Also skipped on one device (the (1, 1) mesh row only tracks the
    # jit-with-shardings overhead floor) and on cold runs.
    sh = by_exec.get(("masked", "sharded", h_hi, "model"))
    sl = by_exec.get(("masked", "slot", h_hi, "model"))
    if not (sh and sl):
        print("[bench] skipping sharded gate (no masked sharded+slot rows)")
    elif args.no_warmup:
        print("[bench] skipping sharded gate (--no-warmup: numbers are "
              "compile-dominated)")
    elif serve_mesh.size <= 1:
        print("[bench] skipping sharded gate (single-device mesh)")
    else:
        ratio = sh["engine_tok_s"] / max(sl["engine_tok_s"], 1e-9)
        print(f"[bench] sharded vs local (masked, H={h_hi}, "
              f"{serve_mesh.size}-device mesh): "
              f"{sh['engine_tok_s']:.1f} vs {sl['engine_tok_s']:.1f} tok/s "
              f"(×{ratio:.2f})")
        if sh["engine_tok_s"] >= sl["engine_tok_s"]:
            pass
        elif jax.default_backend() == "cpu":
            print(f"[bench] WARNING: sharded slower than local ×{ratio:.2f} "
                  f"— expected on fake host-platform CPU devices (shared "
                  f"socket); the gate hard-fails on real accelerator "
                  f"meshes")
        else:
            raise SystemExit(
                f"[bench] FAIL: masked/sharded H={h_hi} on a "
                f"{serve_mesh.size}-device mesh ({sh['engine_tok_s']:.1f} "
                f"tok/s) is slower than single-device local "
                f"({sl['engine_tok_s']:.1f} tok/s) at equal batch — "
                f"collectives must be amortized by the horizon, not "
                f"regressive; a regression here invalidates the sharded "
                f"serve path")

    # Scenario gates (DESIGN.md §11) — AFTER the doc write, like every
    # gate above: a failing run still leaves its rows behind. These are
    # the robustness contract the elastic-budget machinery ships under;
    # run_budget_shock / run_cancellation_storm returning at all already
    # proves no deadlock (the engine drained).
    if scenarios is not None:
        sh = scenarios["budget_shock"]
        stm = scenarios["cancellation_storm"]
        if sh["shock"]["completed"] <= 0 or sh["post"]["completed"] <= 0:
            raise SystemExit(
                f"[bench] FAIL: budget shock stalled completions "
                f"(shock {sh['shock']['completed']:.0f} done, post "
                f"{sh['post']['completed']:.0f} done) — the engine must "
                f"keep serving through a −{args.shock_frac:.0%} KV cut "
                f"and after recovery, not deadlock or starve")
        if sh["preempted_count"] > 0 and sh["itl_preempted_count"] <= 0:
            raise SystemExit(
                "[bench] FAIL: requests were preempted but no ITL samples "
                "landed in the preempted pool — resume gaps would pollute "
                "the untouched requests' percentiles")
        if args.no_warmup:
            print("[bench] skipping shock recovery gate (--no-warmup: "
                  "numbers are compile-dominated)")
        elif sh["recovery_ratio"] < 0.9:
            raise SystemExit(
                f"[bench] FAIL: the full-budget replay AFTER the shocked "
                f"run reached only ×{sh['recovery_ratio']:.2f} of the "
                f"pre-shock warmed rate ({sh['replay_tok_per_s']:.0f} vs "
                f"{sh['warmed_tok_per_s']:.0f} tok/s, need ≥ 0.9×) — "
                f"restoring the budget must restore goodput; pages or "
                f"slots are leaking across the shock")
        if (stm["live_requests"] or stm["spilled_requests"]
                or stm["leaked_pages"]):
            raise SystemExit(
                f"[bench] FAIL: cancellation storm leaked state — live "
                f"rids {stm['live_requests']:.0f}, spilled "
                f"{stm['spilled_requests']:.0f}, leaked pages "
                f"{stm['leaked_pages']:.0f} (all must be 0); the cancel "
                f"path must release every page at every lifecycle stage")
        if stm["cancelled"] < stm["cancel_quota"]:
            print(f"[bench] WARNING: storm cancelled {stm['cancelled']} < "
                  f"quota {stm['cancel_quota']} (trace drained before the "
                  f"storm met its quota — raise --scenario-requests)")


if __name__ == "__main__":
    main()
