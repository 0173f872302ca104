"""Cross-executor conformance suite (DESIGN.md §7).

Every ``ModelExecutor`` backend must be observationally identical on the
engine's serve path: the SAME trace yields bitwise-identical per-request
token streams and keep-masks, the engine-report invariants hold, and the
decode horizon is unobservable (H ∈ {1, 4, 8} bitwise-equal, including a
``max_new`` that lands mid-horizon). A new executor only registers a
factory in ``EXECUTORS`` plus a param in ``EXECUTOR_PARAMS`` — every test
here then runs against it.

The sharded factory builds a DP-majority mesh (model axis 1): tensor
parallelism re-associates the matmul reductions (partial sums per shard),
so TP meshes are numerically close but not contractually bitwise — DP
sharding keeps per-slot compute identical, which is the contract this
suite pins. On one device that is the degenerate (1, 1) mesh; the
multi-device CI job re-runs the ``multi_device``-marked tests under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the data
axis really shards (plus the 8-way end-to-end and transfer-guard tests
below).
"""
import jax
import numpy as np
import pytest

from repro.core import masks
from repro.core.policy import Decision, DensePolicy, RLPolicy
from repro.launch.mesh import make_host_mesh, make_serve_mesh
from repro.runtime import (EngineConfig, EngineRequest, LocalExecutor,
                           PagedExecutor, RAPEngine, ShardedExecutor,
                           TickStaircase)

EXECUTORS = {
    "local": lambda model, params, slots, kv_dtype=None: None,  # engine default
    "paged": lambda model, params, slots, kv_dtype=None: PagedExecutor(
        model, params, max_active=slots, kv_dtype=kv_dtype),
    "sharded": lambda model, params, slots, kv_dtype=None: ShardedExecutor(
        model, make_serve_mesh(slots), params=params, max_active=slots,
        kv_dtype=kv_dtype),
}

# sharded runs in the multi-device CI job (8 fake CPU devices); tier-1
# covers its single-device smoke path via tests/test_engine.py
EXECUTOR_PARAMS = ["local", "paged",
                   pytest.param("sharded", marks=pytest.mark.multi_device)]


# `served` (tiny model + memory model + random-Q controller) comes from
# tests/conftest.py — shared with the engine and horizon suites.


def _reqs(prompts, max_new=None, rate=1000.0, seed=0):
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for i, p in enumerate(prompts):
        t += float(rng.exponential(1.0 / rate))
        out.append(EngineRequest(rid=f"r{i}", prompt=np.asarray(p, np.int32),
                                 arrival_t=t, max_new=max_new))
    return out


def _engine(model, params, c, kind, *, budget, max_new, slots=4, max_len=32,
            horizon=8, chunk=0, kv_dtype=None, policy=None):
    return RAPEngine(model, params, policy or RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=max_new, max_active=slots,
        max_len=max_len, budget_bytes=budget, tokens_per_page=8,
        kv_dtype=kv_dtype, decode_horizon=horizon,
        max_prefill_tokens=chunk),
        executor=EXECUTORS[kind](model, params, slots, kv_dtype))


# ------------------------------------------------------- canonical trace
# 8 requests, alternating 16/24-token prompts, a pool of ~2.5 dense
# requests (admission must queue under load) — the PR 3 paged-vs-local
# acceptance trace, now the conformance trace every executor serves.
def _trace(batch, mm, cfg):
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(cfg.n_layers)
    prompts = [toks[:1, : (16 if i % 2 else 24)] for i in range(8)]
    budget = mm.param_bytes(full) + 2.5 * mm.state_bytes(full, 1, 26)
    return prompts, budget


@pytest.fixture(scope="module")
def reference_run(served):
    """The LocalExecutor report on the canonical trace — the oracle every
    backend is compared against bitwise."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    eng = _engine(model, params, c, "local", budget=budget, max_new=2)
    return eng.run(_reqs(prompts))


@pytest.mark.parametrize("kind", EXECUTOR_PARAMS)
def test_trace_tokens_match_local_reference(served, reference_run, kind):
    """Bitwise token/mask equality on the canonical trace. For 'local'
    this degenerates to a run-to-run determinism check (same oracle
    trace, fresh engine)."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    eng = _engine(model, params, c, kind, budget=budget, max_new=2)
    rep = eng.run(_reqs(prompts))
    done_ref = {r.rid: r for r in reference_run.results
                if r.status == "done"}
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert len(done) == len(done_ref) == 8 and rep.rejected == 0
    for rid, r in done_ref.items():
        np.testing.assert_array_equal(
            r.tokens, done[rid].tokens,
            err_msg=f"{kind} diverged from local on {rid}")
        np.testing.assert_array_equal(r.mask, done[rid].mask)


@pytest.mark.parametrize("kind", EXECUTOR_PARAMS)
def test_report_invariants(served, kind):
    """Engine-report invariants every backend must uphold: all served,
    accounting consistent, pool fully drained, budget never exceeded."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    eng = _engine(model, params, c, kind, budget=budget, max_new=2)
    rep = eng.run(_reqs(prompts))
    done = [r for r in rep.results if r.status == "done"]
    assert len(done) == 8 and rep.rejected == 0
    assert rep.generated_tokens == sum(r.tokens.size for r in done)
    assert rep.tokens_per_s > 0.0 and rep.decode_iters > 0
    assert 0.0 <= rep.launch_s <= rep.wall_s + 1e-9
    for r in done:
        assert r.admitted_t >= r.arrival_t - 1e-9
        assert r.queue_delay_s >= 0.0
        assert r.finished_t >= r.admitted_t
        assert r.tokens.shape == (1, 2)       # truncated, never padded
        # TTFT is measured from arrival, so queue delay is a lower bound
        assert r.ttft_s >= r.queue_delay_s - 1e-9
    # latency summaries: one TTFT per served request, ordered percentiles
    assert rep.ttft["count"] == 8.0
    assert rep.ttft["p50"] <= rep.ttft["p90"] + 1e-12 <= rep.ttft["p99"] + 2e-12
    assert rep.itl["count"] >= 8.0            # ≥1 decode token per request
    assert rep.itl["p50"] <= rep.itl["p90"] + 1e-12 <= rep.itl["p99"] + 2e-12
    # the delivery record: its first entry is the first token (TTFT's
    # anchor), its tokens add up to what the request produced
    for r in done:
        assert r.deliveries[0][0] - r.arrival_t == r.ttft_s
        assert sum(n for _, n in r.deliveries) == r.tokens.shape[1]
        ts = [t for t, _ in r.deliveries]
        assert ts == sorted(ts)
    pool = rep.pool
    assert pool["peak_in_use_bytes"] <= pool["peak_reserved_bytes"] + 1e-6
    assert pool["peak_reserved_bytes"] <= pool["capacity_bytes"] + 1e-6
    assert pool["capacity_bytes"] + eng.resident_param_bytes <= budget + 1e-6
    assert pool["overcommit_events"] == 0
    assert pool["reserved_bytes"] == 0 and pool["in_use_bytes"] == 0


@pytest.mark.parametrize("kind", EXECUTOR_PARAMS)
def test_horizon_token_equivalence(served, kind):
    """decode_horizon ∈ {1, 4, 8} must emit bitwise-identical per-request
    token streams — max_new=6 deliberately lands mid-horizon for H=4 and
    H=8, exercising boundary truncation."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    prompts = [toks[:1, :16], toks[:1, :24], toks[:1, :16]]
    outs = {}
    for horizon in (1, 4, 8):
        eng = _engine(model, params, c, kind, budget=budget, max_new=6,
                      horizon=horizon)
        rep = eng.run(_reqs(prompts))
        assert all(r.status == "done" for r in rep.results)
        outs[horizon] = {r.rid: r.tokens for r in rep.results}
        for r in rep.results:
            assert r.tokens.shape == (1, 6)    # truncated, never padded
    for horizon in (4, 8):
        for rid, t in outs[1].items():
            np.testing.assert_array_equal(
                t, outs[horizon][rid],
                err_msg=f"{kind}: H={horizon} diverged from H=1 on {rid}")


@pytest.mark.parametrize("chunk", [1, 8, 64],
                         ids=["slice1", "horizon8", "whole"])
@pytest.mark.parametrize("kind", EXECUTOR_PARAMS)
def test_chunked_prefill_bitwise_conformance(served, reference_run, kind,
                                             chunk):
    """Chunked prefill is unobservable in results: the canonical trace
    served with ``max_prefill_tokens`` ∈ {1 (single-token slices), 8
    (horizon-sized), 64 (≥ whole prompt)} emits token streams and masks
    bitwise-identical to the monolithic reference, on every backend.
    Pow2 chunk decomposition never pads, so no garbage K/V can perturb
    the attention math."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    eng = _engine(model, params, c, kind, budget=budget, max_new=2,
                  chunk=chunk)
    rep = eng.run(_reqs(prompts))
    done_ref = {r.rid: r for r in reference_run.results
                if r.status == "done"}
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert len(done) == len(done_ref) == 8 and rep.rejected == 0
    for rid, r in done_ref.items():
        np.testing.assert_array_equal(
            r.tokens, done[rid].tokens,
            err_msg=f"{kind} chunk={chunk} diverged from monolithic "
                    f"on {rid}")
        np.testing.assert_array_equal(r.mask, done[rid].mask)


@pytest.mark.parametrize("kind", EXECUTOR_PARAMS)
def test_chunked_horizon_equivalence(served, kind):
    """Chunked prefill composed with every decode horizon H ∈ {1, 4, 8}
    matches the monolithic H=1 stream bitwise — chunking and horizon are
    independently and jointly unobservable."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    prompts = [toks[:1, :16], toks[:1, :24], toks[:1, :16]]
    base = _engine(model, params, c, kind, budget=budget, max_new=6,
                   horizon=1).run(_reqs(prompts))
    ref = {r.rid: r.tokens for r in base.results}
    assert all(r.status == "done" for r in base.results)
    for horizon in (1, 4, 8):
        eng = _engine(model, params, c, kind, budget=budget, max_new=6,
                      horizon=horizon, chunk=8)
        rep = eng.run(_reqs(prompts))
        assert all(r.status == "done" for r in rep.results)
        for r in rep.results:
            np.testing.assert_array_equal(
                ref[r.rid], r.tokens,
                err_msg=f"{kind}: chunked H={horizon} diverged from "
                        f"monolithic H=1 on {r.rid}")


def test_paged_fragmentation_below_slot(served, reference_run):
    """Paged-specific conformance extra: measured physical fragmentation
    must be strictly below the slot-cache baseline (pages grow per token;
    slot caches pin max_len per occupant)."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    eng = _engine(model, params, c, "paged", budget=budget, max_new=2)
    rep = eng.run(_reqs(prompts))
    assert 0.0 < rep.measured_frag < reference_run.measured_frag
    assert rep.pool["committed_pages"] == 0


# ------------------------------------------------------ quantized KV rows
# int8 KV is not bitwise vs the fp32 reference (quantization perturbs the
# attention values), so quantized rows get their own contracts: a tolerance
# gate against fp32, an EXACT gate on the greedy-stability trace, and full
# bitwise invariance of horizon/chunking WITHIN the quantized path.
QUANT_PARAMS = ["local", "paged"]


@pytest.mark.parametrize("kind", QUANT_PARAMS)
def test_quantized_trace_matches_fp32_within_tolerance(served, kind):
    """int8 vs model-width KV on the canonical trace under the tolerance
    gate: every request's FIRST token is exact (prefill logits are computed
    at model width before quantize-on-write), and at least 6 of 8 full
    streams are token-exact. The quantized pool must also buy ≥ 1.8× the
    pages of the fp32 pool at the same byte budget — the admission headroom
    the precision action exists for."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    eng_f = _engine(model, params, c, kind, budget=budget, max_new=4)
    ref = {r.rid: r for r in eng_f.run(_reqs(prompts, max_new=4)).results
           if r.status == "done"}
    eng_q = _engine(model, params, c, kind, budget=budget, max_new=4,
                    kv_dtype="int8")
    rep = eng_q.run(_reqs(prompts, max_new=4))
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert len(done) == len(ref) == 8 and rep.rejected == 0
    # int8 reservations are ~4× smaller, so the policy's effective-budget
    # cell can drift for a request or two — compare decodes only where the
    # decision agreed (a mask flip changes the compute, not the precision)
    agree = [rid for rid in ref
             if np.array_equal(ref[rid].mask, done[rid].mask)]
    assert len(agree) >= 6, f"{kind}: masks diverged on {8 - len(agree)}/8"
    exact = 0
    for rid in agree:
        assert done[rid].tokens[0, 0] == ref[rid].tokens[0, 0], \
            f"{kind}: int8 perturbed the model-width prefill logits on {rid}"
        exact += np.array_equal(ref[rid].tokens, done[rid].tokens)
    assert exact >= len(agree) - 1, \
        f"{kind}: only {exact}/{len(agree)} int8 streams token-exact"
    # pool ledger: drained, physical-width accounting engaged
    assert rep.pool["reserved_bytes"] == 0 and rep.pool["in_use_bytes"] == 0
    if kind == "paged":
        assert eng_q.pool.kv_dtype == "int8"
        assert rep.pool["in_use_scale"] < 1.0
        assert eng_q.pool.n_pages >= 1.8 * eng_f.pool.n_pages


@pytest.mark.parametrize("kind", QUANT_PARAMS)
def test_quantized_greedy_stability_exact(served, kind):
    """The dedicated greedy-stability trace: ``max_new=1`` serves every
    request as prefill-only next-token prediction, whose logits never read
    quantized KV back — int8 serving MUST match fp32 exactly here, pinning
    that quantize-on-write cannot corrupt the prefill compute path."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    ref = _engine(model, params, c, kind, budget=budget,
                  max_new=1).run(_reqs(prompts, max_new=1))
    rep = _engine(model, params, c, kind, budget=budget, max_new=1,
                  kv_dtype="int8").run(_reqs(prompts, max_new=1))
    done_ref = {r.rid: r for r in ref.results if r.status == "done"}
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert len(done) == len(done_ref) == 8
    agree = [rid for rid in done_ref
             if np.array_equal(done_ref[rid].mask, done[rid].mask)]
    assert len(agree) >= 6, f"{kind}: masks diverged on {8 - len(agree)}/8"
    for rid in agree:
        np.testing.assert_array_equal(
            done_ref[rid].tokens, done[rid].tokens,
            err_msg=f"{kind}: int8 diverged on the greedy-stability trace "
                    f"({rid})")


@pytest.mark.parametrize("kind", QUANT_PARAMS)
def test_quantized_horizon_unobservable(served, kind):
    """WITHIN the int8 path, horizon decode stays bitwise unobservable:
    H ∈ {1, 4, 8} emit identical streams. Decode reads quantized KV
    identically at every horizon, so this pins the quantized decode write
    seam (per-token masked page requantization, horizon pre-grant extends,
    scratch-page routing) against the H=1 quantized reference."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    ref = None
    for horizon in (1, 4, 8):
        eng = _engine(model, params, c, kind, budget=budget, max_new=4,
                      horizon=horizon, kv_dtype="int8")
        rep = eng.run(_reqs(prompts, max_new=4))
        done = {r.rid: r.tokens for r in rep.results if r.status == "done"}
        assert len(done) == 8 and rep.rejected == 0
        if ref is None:
            ref = done
            continue
        for rid, t in ref.items():
            np.testing.assert_array_equal(
                t, done[rid],
                err_msg=f"{kind}: int8 H={horizon} diverged from H=1 "
                        f"on {rid}")


@pytest.mark.parametrize("kind", QUANT_PARAMS)
def test_quantized_chunked_prefill_tolerance(served, kind):
    """Chunked prefill under int8 is NOT bitwise vs monolithic — a later
    chunk attends to earlier chunks' *dequantized* KV, where monolithic
    prefill attends at model width — so it gets the tolerance gate:
    all 8 requests served, masks identical, ≥ 6/8 streams token-exact
    against the monolithic quantized run."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    ref = _engine(model, params, c, kind, budget=budget, max_new=4,
                  kv_dtype="int8").run(_reqs(prompts, max_new=4))
    done_ref = {r.rid: r for r in ref.results if r.status == "done"}
    for chunk in (8, 64):
        eng = _engine(model, params, c, kind, budget=budget, max_new=4,
                      chunk=chunk, kv_dtype="int8")
        rep = eng.run(_reqs(prompts, max_new=4))
        done = {r.rid: r for r in rep.results if r.status == "done"}
        assert len(done) == len(done_ref) == 8 and rep.rejected == 0
        agree = [rid for rid in done_ref
                 if np.array_equal(done_ref[rid].mask, done[rid].mask)]
        assert len(agree) >= 6, \
            f"{kind}: masks diverged on {8 - len(agree)}/8 (chunk={chunk})"
        exact = sum(np.array_equal(done_ref[rid].tokens, done[rid].tokens)
                    for rid in agree)
        assert exact >= len(agree) - 1, \
            (f"{kind}: only {exact}/{len(agree)} int8 chunked "
             f"(chunk={chunk}) streams token-exact")


# --------------------------------------------------- sharded: multi-device
@pytest.mark.multi_device
def test_sharded_eight_way_mesh_end_to_end(served):
    """Acceptance: a full trace served on an 8-way host-platform mesh
    (one slot per device — the data axis REALLY shards) emits token
    streams bitwise-identical to LocalExecutor."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (multi-device CI job sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    local = _engine(model, params, c, "local", budget=budget, max_new=2,
                    slots=8)
    rep_l = local.run(_reqs(prompts))
    mesh = make_host_mesh((8, 1), ("data", "model"))
    eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=2, max_active=8, max_len=32,
        budget_bytes=budget, tokens_per_page=8),
        executor=ShardedExecutor(model, mesh, params=params, max_active=8))
    rep_s = eng.run(_reqs(prompts))
    group = eng.executor.groups()[0]
    spec = group.cache["attn"]["k"].sharding.spec
    assert "data" in jax.tree.leaves(tuple(spec)), spec   # DP engaged
    done_l = {r.rid: r for r in rep_l.results if r.status == "done"}
    done_s = {r.rid: r for r in rep_s.results if r.status == "done"}
    assert len(done_l) == len(done_s) == 8
    for rid, r in done_l.items():
        np.testing.assert_array_equal(r.tokens, done_s[rid].tokens)
        np.testing.assert_array_equal(r.mask, done_s[rid].mask)
    assert eng.executor.stats()["mesh_devices"] == 8


@pytest.mark.multi_device
def test_sharded_tp_mesh_serves_and_is_deterministic(served):
    """A mesh with a real TP axis serves the trace end-to-end and is
    run-to-run deterministic. TP partial-sum re-association means bitwise
    equality with local is NOT contractual here — the bitwise conformance
    contract is pinned on DP meshes above."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for a (2, 2) mesh")
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    mesh = make_host_mesh((2, 2), ("data", "model"))

    def run():
        eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
            mode="masked", max_new_tokens=2, max_active=4, max_len=32,
            budget_bytes=budget, tokens_per_page=8),
            executor=ShardedExecutor(model, mesh, params=params,
                                     max_active=4))
        return eng.run(_reqs(prompts))

    a, b = run(), run()
    done_a = {r.rid: r for r in a.results if r.status == "done"}
    done_b = {r.rid: r for r in b.results if r.status == "done"}
    assert len(done_a) == len(done_b) == 8
    for rid, r in done_a.items():
        np.testing.assert_array_equal(r.tokens, done_b[rid].tokens)


@pytest.mark.multi_device
def test_sharded_horizon_zero_transfers_when_warm(tiny_model):
    """After one warming call, a sharded horizon launch moves no bytes
    between host and device: the mesh-resident cache, positions, seed
    tokens, and gates are all committed device arrays and the horizon
    executable's shardings are pinned. The only sync is the single
    [n_slots, H] token read-back after the launch (placement columns stay
    exempt, as on the local path)."""
    model, params, batch = tiny_model
    full = masks.full_mask(model.cfg.n_layers)
    prompt = np.asarray(batch["tokens"])[:1, :16]
    mesh = make_serve_mesh(4)
    ex = ShardedExecutor(model, mesh, params=params, max_active=4)
    group = ex.group_for(full, 32)
    ex.prefill_into(group, [0], "r0", prompt, full)
    ex.decode_horizon(group, 4)                     # warm (compiles)
    with jax.transfer_guard("disallow"):
        toks_dev, idx, new = group.launch_horizon(4, ex.decode_buckets)
    assert not new                                  # warmed executable
    assert idx is None                              # full width, always
    toks = np.asarray(toks_dev)                     # the one read-back
    assert toks.shape == (4, 4)


# ------------------------------------------- elastic-budget preemption
# (DESIGN.md §11): a mid-serve budget shock forces KV spill to host and
# later resume; the token streams must be BITWISE identical to the
# unshocked run on every backend — preemption must be unobservable in
# the output, exactly like the decode horizon above.

def _kv_staircase(eng, budget, down, up, frac=0.45):
    """Tick staircase cutting ``frac`` of the KV headroom (budget minus
    resident params) between ticks ``down`` and ``up``; see
    run_budget_shock for why the cut targets the KV share."""
    params_b = float(eng.resident_param_bytes)
    kv = max(budget - params_b, 0.0)
    shocked = (params_b + (1.0 - frac) * kv) / budget
    return TickStaircase(budget, [(down, 1.0), (up - down, shocked),
                                  (0, 1.0)])


@pytest.mark.parametrize("kind", EXECUTOR_PARAMS)
def test_preemption_spill_restore_bitwise(served, kind):
    """Spill→restore round-trip under a mid-serve KV budget shock is
    bitwise: every request completes with the SAME tokens and mask as the
    unshocked oracle, at least one request was actually preempted, and
    the pool drains clean.

    Both runs use DensePolicy so the keep-mask cannot depend on the live
    budget: an adaptive policy legitimately prunes differently for
    requests ADMITTED during the shock window (that is the paper's
    point), which would flip tokens without any spill-path bug. Pinning
    the decision isolates exactly what this test owns — preemption must
    be unobservable in the output."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    ref_eng = _engine(model, params, c, kind, budget=budget, max_new=6,
                      horizon=2, policy=DensePolicy(mm))
    ref = {r.rid: r for r in ref_eng.run(_reqs(prompts, max_new=6)).results
           if r.status == "done"}
    eng = _engine(model, params, c, kind, budget=budget, max_new=6,
                  horizon=2, policy=DensePolicy(mm))
    rep = eng.run(_reqs(prompts, max_new=6),
                  budget_trace=_kv_staircase(eng, budget, down=4, up=14))
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert rep.preempted_count > 0, f"{kind}: shock never preempted"
    assert rep.spilled_mb > 0
    assert set(done) == set(ref) == {f"r{i}" for i in range(8)}
    for rid, r in ref.items():
        np.testing.assert_array_equal(
            r.tokens, done[rid].tokens,
            err_msg=f"{kind}: preemption changed tokens on {rid}")
        np.testing.assert_array_equal(r.mask, done[rid].mask)
    assert rep.pool["reserved_bytes"] == 0
    assert rep.pool["spilled_requests"] == 0
    assert rep.pool["free_pages"] == rep.pool["n_pages"]


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_preemption_bitwise_fp32_and_int8(served, kv_dtype):
    """The paged pool's PHYSICAL spill path (page gather → host → page
    scatter, including int8 quantization scale rows) round-trips bitwise:
    the shocked run reproduces the same-precision unshocked oracle
    token-for-token. fp32 and int8 pools are separate oracles — int8 is
    compared against int8, so any scale-row corruption on the spill path
    shows up as a token flip. DensePolicy pins the keep-mask (see
    test_preemption_spill_restore_bitwise) so only the spill path can
    flip a token."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    ref_eng = _engine(model, params, c, "paged", budget=budget, max_new=6,
                      horizon=2, kv_dtype=kv_dtype, policy=DensePolicy(mm))
    ref = {r.rid: r for r in ref_eng.run(_reqs(prompts, max_new=6)).results
           if r.status == "done"}
    eng = _engine(model, params, c, "paged", budget=budget, max_new=6,
                  horizon=2, kv_dtype=kv_dtype, policy=DensePolicy(mm))
    # int8 pages reserve ~4x less, so the shock must cut deeper to evict
    frac = 0.45 if kv_dtype is None else 0.8
    rep = eng.run(_reqs(prompts, max_new=6),
                  budget_trace=_kv_staircase(eng, budget, down=4, up=14,
                                             frac=frac))
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert rep.preempted_count > 0
    assert set(done) == set(ref)
    for rid, r in ref.items():
        np.testing.assert_array_equal(
            r.tokens, done[rid].tokens,
            err_msg=f"kv_dtype={kv_dtype}: spill path changed tokens "
                    f"on {rid}")
    assert rep.pool["reserved_bytes"] == 0
    assert rep.pool["free_pages"] == rep.pool["n_pages"]


# ------------------------------------------------------ structural serving
# (DESIGN.md §9): structural buckets on the paged backend, the bucket
# aliasing regression, bucket-shape quantization, the bounded group set,
# and the persistent compilation cache.

class FixedMaskPolicy(DensePolicy):
    """Deterministic mask sequence keyed by observe() call index: call i
    returns ``seq[min(i, len(seq)-1)]``. Pins exactly which keep-mask each
    admission sees, independent of budget drift — the structural
    conformance tests need the mask stream itself to be the controlled
    variable."""

    name = "fixed"

    def __init__(self, mm, seq):
        super().__init__(mm)
        self._seq = [np.array(m, copy=True) for m in seq]
        self._i = 0

    def observe(self, state):
        mask = self._seq[min(self._i, len(self._seq) - 1)]
        self._i += 1
        peak = self.mm.peak_bytes(mask, state.batch, state.total_len)
        return self._stamp(Decision(mask=mask.copy(), steps=0,
                                    peak_bytes=peak,
                                    fits=peak <= state.budget_bytes,
                                    latency_s=0.0))


STRUCT_PARAMS = ["local", "paged"]


def _struct_engine(model, params, policy, kind, *, budget, max_new, slots=4,
                   max_len=32, horizon=8, kv_dtype=None, bucket_quant="none",
                   max_groups=0, compile_cache=False):
    ex = None
    if kind == "paged":
        ex = PagedExecutor(model, params, mode="structural", max_active=slots,
                           kv_dtype=kv_dtype, bucket_quant=bucket_quant)
    return RAPEngine(model, params, policy, EngineConfig(
        mode="structural", max_new_tokens=max_new, max_active=slots,
        max_len=max_len, budget_bytes=budget, tokens_per_page=8,
        kv_dtype=kv_dtype, decode_horizon=horizon,
        bucket_quant=bucket_quant, max_structural_groups=max_groups,
        compile_cache=compile_cache), executor=ex)


def _drop_layer(cfg, *layers):
    m = masks.full_mask(cfg.n_layers)
    for i in layers:
        m[i] = m[cfg.n_layers + i] = False
    return m


@pytest.mark.parametrize("kind", STRUCT_PARAMS)
def test_structural_bucket_aliasing_serves_own_weights(served, kind):
    """THE aliasing regression (DESIGN.md §9): masks dropping DIFFERENT
    layers share a bucket signature (``bucket_key`` collapses k whole-layer
    drops by count), but must never share compacted params. Two
    same-signature requests served concurrently — A drops layer 0, B drops
    layer 1, one slot per group so neither can join the other's group —
    must each emit the stream their own single-request serve emits. The
    pre-fix executor cached the first mask's ``compact_params`` under the
    shared signature, so B decoded with A's weights (deferred behind A,
    then seated on A's gather)."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    mA, mB = _drop_layer(model.cfg, 0), _drop_layer(model.cfg, 1)
    assert masks.bucket_key(model.cfg, mA) == masks.bucket_key(model.cfg, mB)
    assert masks.gather_key(model.cfg, mA) != masks.gather_key(model.cfg, mB)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    pA, pB = toks[:1, :16], toks[:1, :24]

    def solo(mask, prompt):
        eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mask]),
                             kind, budget=budget, max_new=4, slots=1)
        rep = eng.run([EngineRequest(rid="x", prompt=prompt, arrival_t=0.0,
                                     max_new=4)])
        return rep.result("x")

    ref_a, ref_b = solo(mA, pA), solo(mB, pB)
    eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mA, mB]),
                         kind, budget=budget, max_new=4, slots=1)
    rep = eng.run([
        EngineRequest(rid="a", prompt=pA, arrival_t=0.0, max_new=4),
        EngineRequest(rid="b", prompt=pB, arrival_t=0.0, max_new=4)])
    ra, rb = rep.result("a"), rep.result("b")
    assert ra.status == rb.status == "done"
    np.testing.assert_array_equal(ra.mask, mA)
    np.testing.assert_array_equal(rb.mask, mB)
    np.testing.assert_array_equal(
        ra.tokens, ref_a.tokens,
        err_msg=f"{kind}: request A diverged from its solo reference")
    np.testing.assert_array_equal(
        rb.tokens, ref_b.tokens,
        err_msg=f"{kind}: same-signature request B was served with the "
                f"wrong compacted weights (bucket aliasing)")
    # one compiled family, two resident parameter gathers
    s = eng.executor.stats()
    assert s["bucket_signatures"] == 1
    assert s["groups"] == 2


def test_structural_paged_matches_local_bitwise(served):
    """Structural paged serves the canonical trace bitwise-identically to
    structural local: compacted per-bucket layer stacks decoding over the
    shared page pool reproduce the slot-cache reference token for token.
    One fixed whole-layer mask for every request, so backend-dependent
    policy call order cannot flip a mask."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    mask = _drop_layer(model.cfg, 1)
    outs = {}
    for kind in STRUCT_PARAMS:
        eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mask]),
                             kind, budget=budget, max_new=4)
        rep = eng.run(_reqs(prompts, max_new=4))
        done = {r.rid: r for r in rep.results if r.status == "done"}
        assert len(done) == 8 and rep.rejected == 0, kind
        for r in done.values():
            np.testing.assert_array_equal(r.mask, mask)
        outs[kind] = done
    for rid, r in outs["local"].items():
        np.testing.assert_array_equal(
            r.tokens, outs["paged"][rid].tokens,
            err_msg=f"structural paged diverged from local on {rid}")


@pytest.mark.parametrize("kind", STRUCT_PARAMS)
def test_structural_horizon_token_equivalence(served, kind):
    """Horizon decode stays unobservable in structural mode: H ∈ {1, 4, 8}
    emit bitwise-identical streams through the compacted layer stacks
    (max_new=6 lands mid-horizon for H=4 and H=8)."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    mask = _drop_layer(model.cfg, 2)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    prompts = [toks[:1, :16], toks[:1, :24], toks[:1, :16]]
    outs = {}
    for horizon in (1, 4, 8):
        eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mask]),
                             kind, budget=budget, max_new=6, horizon=horizon)
        rep = eng.run(_reqs(prompts, max_new=6))
        assert all(r.status == "done" for r in rep.results)
        outs[horizon] = {r.rid: r.tokens for r in rep.results}
    for horizon in (4, 8):
        for rid, t in outs[1].items():
            np.testing.assert_array_equal(
                t, outs[horizon][rid],
                err_msg=f"structural {kind}: H={horizon} diverged from "
                        f"H=1 on {rid}")


@pytest.mark.parametrize("kind,kv_dtype", [("local", None), ("paged", None),
                                           ("paged", "int8")],
                         ids=["local-fp32", "paged-fp32", "paged-int8"])
def test_structural_spill_restore_bitwise(served, kind, kv_dtype):
    """Preemption is unobservable in structural mode too: a mid-serve KV
    budget shock spills compacted-bucket residents (paged: physical page
    gather → host → scatter, including int8 scale rows) and the resumed
    streams match the unshocked same-precision oracle bitwise. The resume
    path re-resolves the group by gather key, so a restored request can
    never land on another bucket's weights."""
    model, params, batch, mm, c = served
    prompts, budget = _trace(batch, mm, model.cfg)
    mask = _drop_layer(model.cfg, 1)
    ref_eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mask]),
                             kind, budget=budget, max_new=6, horizon=2,
                             kv_dtype=kv_dtype)
    ref = {r.rid: r for r in ref_eng.run(_reqs(prompts, max_new=6)).results
           if r.status == "done"}
    eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mask]),
                         kind, budget=budget, max_new=6, horizon=2,
                         kv_dtype=kv_dtype)
    frac = 0.45 if kv_dtype is None else 0.8
    rep = eng.run(_reqs(prompts, max_new=6),
                  budget_trace=_kv_staircase(eng, budget, down=4, up=14,
                                             frac=frac))
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert rep.preempted_count > 0, f"{kind}/{kv_dtype}: shock never " \
                                    f"preempted"
    assert set(done) == set(ref)
    for rid, r in ref.items():
        np.testing.assert_array_equal(
            r.tokens, done[rid].tokens,
            err_msg=f"structural {kind}/{kv_dtype}: spill/resume changed "
                    f"tokens on {rid}")
    assert rep.pool["reserved_bytes"] == 0
    assert rep.pool["spilled_requests"] == 0


def test_bucket_quantization_bitwise_and_bounded(tiny_model):
    """Bucket-shape quantization is invisible in the tokens and bounds the
    compiled set: every trial mask served through a pow2-quantized bucket
    (exact mask realized as 0/1 gates inside it) emits the stream the
    exact structural compaction emits — gating a block off multiplies by
    literal 0.0/1.0, bitwise-identical to dropping it — while the
    signature count collapses onto the pow2 ladder (≤ ceil(log2 L)+1
    families; here {4, 2}-layer buckets for 5 distinct masks)."""
    model, params, batch = tiny_model
    L = model.cfg.n_layers
    prompt = np.asarray(batch["tokens"])[:1, :16]
    trial = [_drop_layer(model.cfg, 0), _drop_layer(model.cfg, 1),
             _drop_layer(model.cfg, 3), _drop_layer(model.cfg, 0, 1)]
    half = masks.full_mask(L)
    half[L + 2] = False                      # ffn-only drop: gated in both
    trial.append(half)
    streams, stats = {}, {}
    for quant in ("none", "pow2"):
        ex = LocalExecutor(model, params, mode="structural", max_active=2,
                           bucket_quant=quant)
        out = []
        for i, m in enumerate(trial):
            g = ex.group_for(m, 32)
            first = ex.prefill_into(g, [0], f"r{i}", prompt, m)
            toks, _ = ex.decode_horizon(g, 4)
            g.evict([0])
            out.append(np.concatenate([first, toks[0]]))
        streams[quant] = out
        stats[quant] = ex.stats()
    for i, m in enumerate(trial):
        np.testing.assert_array_equal(
            streams["none"][i], streams["pow2"][i],
            err_msg=f"pow2 bucket changed tokens for trial mask {i}")
    bound = int(np.ceil(np.log2(L))) + 1
    assert stats["pow2"]["bucket_signatures"] <= bound
    assert stats["pow2"]["bucket_signatures"] == 2      # {4, 2}-layer
    assert stats["pow2"]["groups"] == 2                 # gathers collapsed
    assert stats["none"]["groups"] == len(trial)        # one per exact mask
    assert (stats["pow2"]["prefill_executables"]
            < stats["none"]["prefill_executables"])


def test_structural_group_cap_evicts_idle(tiny_model):
    """The ``max_groups`` cap bounds ``_groups``/``_prefill_fns``/resident
    param growth under an adaptive mask stream: idle structural groups are
    evicted LRU at mint time, releasing their prefill executables and —
    when last of their signature — the resident compacted stack. Occupied
    groups are never evicted (the cap may overshoot while all are busy)."""
    model, params, batch = tiny_model
    L = model.cfg.n_layers
    prompt = np.asarray(batch["tokens"])[:1, :16]
    ex = LocalExecutor(model, params, mode="structural", max_active=2,
                       max_groups=2)
    for k in range(L):                      # 4 distinct single-layer drops
        m = _drop_layer(model.cfg, k)
        g = ex.group_for(m, 32)
        ex.prefill_into(g, [0], f"r{k}", prompt, m)
        ex.decode_horizon(g, 2)
        g.evict([0])
    s = ex.stats()
    assert s["groups"] <= 2
    assert s["resident_param_stacks"] <= 2
    # all four masks share one 3-layer signature: one prefill family
    assert s["prefill_executables"] == 1
    # occupied groups are exempt: with both cap slots busy, a third mask
    # overshoots instead of evicting a resident
    g0 = ex.group_for(_drop_layer(model.cfg, 0), 32)
    ex.prefill_into(g0, [0], "busy0", prompt, _drop_layer(model.cfg, 0))
    g1 = ex.group_for(_drop_layer(model.cfg, 1), 32)
    ex.prefill_into(g1, [0], "busy1", prompt, _drop_layer(model.cfg, 1))
    g2 = ex.group_for(_drop_layer(model.cfg, 2), 32)
    assert g0.occupied() and g1.occupied()
    assert ex.stats()["groups"] == 3
    # …and the overshoot drains at the next mint once they idle
    g0.evict([0])
    g1.evict([0])
    ex.group_for(_drop_layer(model.cfg, 3), 32)
    assert ex.stats()["groups"] <= 2


def test_invalidation_unified(tiny_model):
    """``set_max_active`` and ``drop_groups`` share one invalidation path:
    both clear groups, prefill executables, and resident compacted params
    — stale (signature, slots) keys must not pin dead XLA executables
    after a capacity reshape."""
    model, params, batch = tiny_model
    prompt = np.asarray(batch["tokens"])[:1, :16]
    for invalidate in (lambda e: e.set_max_active(4),
                       lambda e: e.drop_groups()):
        ex = LocalExecutor(model, params, mode="structural", max_active=2)
        m = _drop_layer(model.cfg, 0)
        g = ex.group_for(m, 32)
        ex.prefill_into(g, [0], "r0", prompt, m)
        g.evict([0])
        s = ex.stats()
        assert s["groups"] == 1 and s["prefill_executables"] == 1
        assert s["resident_param_stacks"] == 1
        invalidate(ex)
        s = ex.stats()
        assert s["groups"] == 0
        assert s["prefill_executables"] == 0
        assert s["resident_param_stacks"] == 0


def test_persistent_compile_cache_hits(served, tmp_path, monkeypatch):
    """With ``EngineConfig.compile_cache`` on, a second engine serving
    the same config after ``jax.clear_caches()`` re-traces its executables
    but loads the XLA binaries from disk: the report shows cache hits,
    near-zero misses, and the replayed streams are bitwise-identical."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    mask = _drop_layer(model.cfg, 1)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    prompts = [toks[:1, :16], toks[:1, :16]]
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    prev = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        def serve():
            eng = _struct_engine(model, params, FixedMaskPolicy(mm, [mask]),
                                 "local", budget=budget, max_new=4,
                                 compile_cache=True)
            return eng.run(_reqs(prompts, max_new=4))

        rep1 = serve()
        assert rep1.compile_events > 0
        assert any(tmp_path.iterdir()), "cache not written where the env " \
            "var points"
        jax.clear_caches()                  # drop in-memory executables
        # first replay: executables compiled BEFORE the cache was enabled
        # (session fixtures, earlier tests) are written — not hit — so
        # only the second replay has a history-independent miss count
        rep2 = serve()
        assert rep2.compile_cache_hits > 0, \
            "warmed replay never hit the persistent cache"
        jax.clear_caches()
        rep3 = serve()
        assert rep3.compile_cache_hits > 0
        assert rep3.compile_cache_misses == 0, \
            "fully-warmed replay still recompiled"
        done1 = {r.rid: r.tokens for r in rep1.results}
        for rep in (rep2, rep3):
            for r in rep.results:
                np.testing.assert_array_equal(done1[r.rid], r.tokens)
    finally:
        for n, v in prev.items():
            jax.config.update(n, v)
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()               # re-latch: later tests cache-free
        from repro.runtime.engine import _CACHE_LISTENER
        _CACHE_LISTENER.pop("dir", None)


def test_compile_cache_dir_yields_to_env(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and
    ``enable_compile_cache`` points JAX there; unset, the cache is the one
    fixed ``.jax_cache/`` at the checkout's root."""
    import os

    from jax._src import compilation_cache as _cc
    from repro.runtime import engine

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert engine.compile_cache_dir() == os.path.join(root, ".jax_cache")
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert engine.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        _cc.reset_cache()
        engine._CACHE_LISTENER.pop("dir", None)
