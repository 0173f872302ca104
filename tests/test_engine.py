"""Continuous-batching engine: KV-pool invariants, admission control,
FIFO trace completion, and token equivalence against one-shot serving."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import masks, memory
from repro.core.policy import RLPolicy
from repro.core.workload import PoissonConfig, poisson_requests
from repro.models import decoder
from repro.runtime import (EngineConfig, EngineRequest, KVPool, PagedExecutor,
                           PoolExhausted, RAPEngine, RAPServer)


# ------------------------------------------------------------------ KV pool
def test_pool_alloc_free_occupancy_invariants():
    pool = KVPool(1000, page_bytes=100)           # 10 pages
    a = pool.alloc("r1", 250)                     # 3 pages (ceil)
    assert len(a.pages) == 3 and pool.free_pages == 7
    assert pool.bytes_in_use == 250 and pool.bytes_reserved == 300
    frag = pool.stats()["fragmentation"]
    assert 0.0 < frag < 1.0                       # 50B of internal frag
    pool.alloc("r2", 700)                         # 7 pages → pool full
    assert pool.free_pages == 0 and not pool.can_alloc(1)
    with pytest.raises(PoolExhausted):
        pool.alloc("r3", 1)
    with pytest.raises(ValueError):               # double alloc is a bug
        pool.alloc("r1", 10)
    pool.free("r1")
    assert pool.free_pages == 3 and pool.can_alloc(300)
    pool.free("r2")
    st = pool.stats()
    assert pool.free_pages == 10
    assert st["reserved_bytes"] == 0 and st["in_use_bytes"] == 0
    assert st["peak_reserved_bytes"] == 1000      # never exceeded capacity
    assert st["peak_in_use_bytes"] == 950
    assert st["peak_reserved_bytes"] <= st["capacity_bytes"]


def test_pool_overcommit_is_tracked_not_silent():
    pool = KVPool(200, page_bytes=100)
    pool.alloc("a", 150)
    with pytest.raises(PoolExhausted):
        pool.alloc("b", 150)
    pool.alloc("b", 150, allow_overcommit=True)
    assert pool.stats()["overcommit_events"] == 1
    pool.free("b")
    pool.free("a")
    assert pool.free_pages == 2                   # overflow pages evaporate


def test_pool_partial_tail_page_unusable():
    pool = KVPool(250, page_bytes=100)            # 2 whole pages only
    assert pool.n_pages == 2
    assert not pool.fits_capacity(201)
    assert pool.fits_capacity(200)


def test_pool_free_unknown_rid_and_idempotent():
    """free() of an unknown rid names the rid and the live set (a bare
    KeyError used to escape); missing_ok=True makes the cancel path
    idempotent without corrupting the free list."""
    pool = KVPool(1000, page_bytes=100)
    pool.alloc("alive", 150)
    with pytest.raises(ValueError, match=r"ghost.*alive"):
        pool.free("ghost")
    assert pool.free("ghost", missing_ok=True) == 0.0
    pool.free("alive")
    assert pool.free("alive", missing_ok=True) == 0.0   # double free is safe
    assert pool.free_pages == 10
    st = pool.stats()
    assert st["reserved_bytes"] == 0 and st["in_use_bytes"] == 0


def test_pool_overflow_pages_never_backfilled():
    """Pins the overcommit contract: synthesized overflow pages are
    bookkeeping fictions — a later free() of a DIFFERENT request returns
    its real pages to the free list but cannot backfill the overflowed
    allocation, which stays over-budget until itself freed."""
    pool = KVPool(300, page_bytes=100)            # 3 real pages
    pool.alloc("a", 200)                          # 2 real pages
    over = pool.alloc("b", 300, allow_overcommit=True)  # 1 real + 2 overflow
    assert sum(1 for p in over.pages if p >= pool.n_pages) == 2
    assert pool.stats()["overcommit_events"] == 1
    before = tuple(pool._live["b"].pages)
    pool.free("a")                                # real pages come back...
    assert pool.free_pages == 2
    assert tuple(pool._live["b"].pages) == before  # ...but b keeps overflow
    assert pool.bytes_reserved == 300              # still charged page-full
    pool.free("b")
    assert pool.free_pages == 3                    # overflow ids evaporated
    assert pool.bytes_reserved == 0


# -------------------------------------------------------- token allocations
def test_pool_token_alloc_extend_free():
    """The physically paged contract: admission commits worst-case pages,
    extend() grants a page only on boundary crossings, and within the
    commitment a strict-mode extend can never fail."""
    pool = KVPool(8 * 64, page_bytes=64, tokens_per_page=4)   # 8 pages
    a = pool.alloc_tokens("r1", 1, 6, max_tokens=12,
                          in_use_bytes=60.0, in_use_per_token=10.0)
    assert a.held_pages == 2 and a.committed_pages == 3       # ceil(12/4)
    assert pool.free_pages == 6 and pool.committed_pages == 1
    assert pool.bytes_reserved == 2 * 64 and pool.bytes_in_use == 60.0
    # tokens 7, 8 fill page 2; token 9 crosses into a fresh page
    assert pool.extend("r1") == [[]]
    assert pool.extend("r1") == [[]]
    grants = pool.extend("r1")
    assert len(grants[0]) == 1 and pool.committed_pages == 0
    assert pool.bytes_reserved == 3 * 64
    assert pool.bytes_in_use == pytest.approx(90.0)
    pool.extend("r1", 3)                                      # up to 12
    with pytest.raises(ValueError, match="commitment"):
        pool.extend("r1")                                     # 13 > 12
    assert pool.free("r1") == 3 * 64
    assert pool.free_pages == 8 and pool.committed_pages == 0
    st = pool.stats()
    assert st["reserved_bytes"] == 0 and st["in_use_bytes"] == 0


def test_pool_token_commitments_gate_admission():
    """can_alloc_tokens discounts OUTSTANDING commitments, not just free
    pages — otherwise a mid-decode extend could find the free list empty
    and deadlock the engine."""
    pool = KVPool(6 * 64, page_bytes=64, tokens_per_page=4)   # 6 pages
    pool.alloc_tokens("a", 1, 4, max_tokens=16)   # holds 1, commits 4
    assert pool.free_pages == 5
    assert pool.can_alloc_tokens(1, 8)            # 2 ≤ 5 − 3
    assert not pool.can_alloc_tokens(1, 12)       # 3 > 5 − 3
    with pytest.raises(PoolExhausted, match="commit"):
        pool.alloc_tokens("b", 1, 4, max_tokens=12)
    pool.alloc_tokens("b", 1, 4, max_tokens=8)
    # a's committed extends succeed even while b holds pages
    for _ in range(12):
        pool.extend("a")
    assert pool.free_pages == 1
    # b still has one committed page outstanding → a 2-row request that
    # would need both remaining pages is not admissible
    assert not pool.can_alloc_tokens(2, 2)
    pool.free("a")
    pool.free("b")
    assert pool.free_pages == 6
    multi = pool.alloc_tokens("c", 2, 6, max_tokens=8)
    assert [len(r) for r in multi.rows] == [2, 2]   # per-row page lists
    assert pool.extend("c", 2) == [[], []]          # 6→8 fills page 2 exactly
    pool.free("c")
    assert sorted(pool._free) == list(range(6))     # no leaks


# ----------------------------------------------- memory-model pool plumbing
def test_block_bytes_seq_zero_guard():
    cfg = get_smoke_config("recurrentgemma-9b")   # has fixed (seq-indep) state
    mm = memory.build_memory_model(cfg)
    L = mm.n_layers
    bb = mm.block_bytes(2, 0)
    # per-token term vanishes at seq=0; seq-independent recurrent/window
    # state is still charged per batch element
    np.testing.assert_allclose(
        bb[:L], mm.mixer_param_bytes + mm.mixer_state_fixed * 2)
    np.testing.assert_array_equal(bb, mm.block_bytes(2, -5))  # clamped
    full = masks.full_mask(L)
    assert mm.state_bytes(full, 2, 0) == pytest.approx(
        2 * float(np.sum(mm.mixer_state_fixed)))
    assert mm.state_bytes(full, 2, -3) == mm.state_bytes(full, 2, 0)


def test_pool_accounting_ledger():
    acct = memory.PoolAccounting(capacity_bytes=100.0)
    acct.reserve(60.0, 50.0)
    assert acct.available_bytes == 40.0
    assert acct.fragmentation() == pytest.approx(1 / 6)
    with pytest.raises(memory.PoolExhausted):
        acct.reserve(50.0, 50.0)
    acct.reserve(50.0, 50.0, allow_overcommit=True)
    assert acct.overcommit_events == 1
    acct.release(50.0, 50.0)
    acct.release(60.0, 50.0)
    assert acct.reserved_bytes == 0 and acct.in_use_bytes == 0
    assert acct.peak_reserved_bytes == 110.0


def test_pool_accounting_in_use_scale_reports_physical_bytes():
    """Mixed-precision accounting: with ``in_use_scale=0.25`` (int8 pages
    under an fp32 model) analytical charges land at quarter width through
    reserve/grow/release, so ``pool_peak_mb``/``pool_frag`` report TRUE
    bytes and fragmentation cannot go negative."""
    acct = memory.PoolAccounting(capacity_bytes=1000.0, in_use_scale=0.25)
    acct.reserve(400.0, 400.0)            # analytical 400B → physical 100B
    assert acct.in_use_bytes == pytest.approx(100.0)
    assert acct.peak_in_use_bytes == pytest.approx(100.0)
    acct.grow(0.0, 200.0)                 # append charges scale too
    assert acct.in_use_bytes == pytest.approx(150.0)
    assert acct.fragmentation() == pytest.approx(1.0 - 150.0 / 400.0)
    assert acct.fragmentation() >= 0.0    # unscaled would report -0.5
    acct.release(400.0, 600.0)
    assert acct.in_use_bytes == pytest.approx(0.0)
    assert acct.reserved_bytes == pytest.approx(0.0)
    # default pools are unscaled: analytical bytes pass through unchanged
    plain = memory.PoolAccounting(capacity_bytes=1000.0)
    plain.reserve(400.0, 300.0)
    assert plain.in_use_bytes == pytest.approx(300.0)


def test_pool_rejects_mismatched_kv_dtype():
    """A request whose Decision.kv_dtype disagrees with the pool's
    allocated precision fails loudly at admission, naming both dtypes —
    never silently writing mis-scaled pages."""
    import jax.numpy as jnp
    pool = KVPool(8 * 64, page_bytes=64, tokens_per_page=4)
    pool.allocate_physical(n_layers=1, n_kv_heads=2, head_dim=4,
                           dtype=jnp.float32, kv_dtype="int8")
    with pytest.raises(ValueError, match=r"'fp32'.*'int8'"):
        pool.alloc_tokens("r0", 1, 4, max_tokens=8, kv_dtype="fp32")
    assert "r0" not in pool._tok          # rejected before taking pages
    # a matching ask and a None ask (pool-native precision) both pass
    pool.alloc_tokens("r1", 1, 4, max_tokens=8, kv_dtype="int8")
    pool.alloc_tokens("r2", 1, 4, max_tokens=8)
    pool.free("r1")
    pool.free("r2")
    assert pool.bytes_reserved == 0


# ------------------------------------------------------------------- engine
# `served` (tiny model + memory model + random-Q controller) comes from
# tests/conftest.py — shared with the horizon and executor suites.


def _engine(model, params, c, mm, *, mode="masked", budget, max_new=4,
            slots=4, max_len=32, admission="strict", scheduler=None):
    return RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode=mode, max_new_tokens=max_new, max_active=slots, max_len=max_len,
        budget_bytes=budget, admission=admission), scheduler=scheduler)


def _reqs(prompts, rate=1000.0, seed=0):
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for i, p in enumerate(prompts):
        t += float(rng.exponential(1.0 / rate))
        out.append(EngineRequest(rid=f"r{i}", prompt=np.asarray(p, np.int32),
                                 arrival_t=t))
    return out


def test_engine_single_request_matches_reference_decode(served):
    """Engine greedy tokens == a raw prefill/decode_step greedy rollout."""
    model, params, batch, mm, c = served
    cfg = model.cfg
    prompt = np.asarray(batch["tokens"])[:1, :16]
    total = 16 + 4
    state = mm.state_bytes(masks.full_mask(cfg.n_layers), 1, total)
    budget = mm.param_bytes(masks.full_mask(cfg.n_layers)) + 4 * state
    eng = _engine(model, params, c, mm, budget=budget)
    rep = eng.run(_reqs([prompt]))
    r = rep.results[0]
    assert r.status == "done" and r.fits
    assert bool(r.mask.all())                     # budget was generous

    import jax.numpy as jnp
    tokens = jnp.asarray(prompt, jnp.int32)
    logits, cache = decoder.prefill(params, cfg, tokens, total)
    ref = [np.asarray(jnp.argmax(logits, -1))[:, None]]
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        lg, cache = decoder.decode_step(params, cfg, cache, tok)
        tok = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        ref.append(np.asarray(tok))
    np.testing.assert_array_equal(r.tokens, np.concatenate(ref, axis=1))


def test_engine_matches_oneshot_server(served):
    """Shared-pool engine == force-mode RAPServer wrapper, token for token."""
    model, params, batch, mm, c = served
    cfg = model.cfg
    prompt = np.asarray(batch["tokens"])[:1, :16]
    full = masks.full_mask(cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 20)
    srv = RAPServer(model, params, RLPolicy(c), mode="masked",
                    max_new_tokens=4)
    sres = srv.serve(prompt, budget)
    eng = _engine(model, params, c, mm, budget=budget)
    rep = eng.run(_reqs([prompt]))
    r = rep.results[0]
    np.testing.assert_array_equal(r.tokens, sres.tokens)
    np.testing.assert_array_equal(r.mask, sres.mask)


def test_engine_masked_structural_equivalent_under_pruning(served):
    """A budget that forces pruning: both modes pick the same mask and emit
    identical greedy tokens from the slot-batched decode paths."""
    model, params, batch, mm, c = served
    cfg = model.cfg
    prompt = np.asarray(batch["tokens"])[:1, :16]
    full = masks.full_mask(cfg.n_layers)
    # below dense peak → controller must prune
    budget = 0.8 * mm.dense_peak(1, 20)
    reps = {}
    for mode in ("masked", "structural"):
        eng = _engine(model, params, c, mm, mode=mode, budget=budget,
                      admission="force")
        reps[mode] = eng.run(_reqs([prompt])).results[0]
    m, s = reps["masked"], reps["structural"]
    assert not m.mask.all()                       # pruning actually happened
    np.testing.assert_array_equal(m.mask, s.mask)
    np.testing.assert_array_equal(m.tokens, s.tokens)
    assert s.bucket != () and m.bucket == ()


def test_engine_fifo_trace_and_budget_invariant(served):
    """≥16-request Poisson trace: FIFO completion, every request served,
    pool bytes never exceed the configured shared budget."""
    model, params, batch, mm, c = served
    cfg = model.cfg
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(cfg.n_layers)
    prompts = [toks[:1, : (16 if i % 2 else 24)] for i in range(16)]
    total = 24 + 2
    state1 = mm.state_bytes(full, 1, total)
    # pool fits ~2.5 dense requests → admission must queue under load
    budget = mm.param_bytes(full) + 2.5 * state1
    eng = _engine(model, params, c, mm, budget=budget, max_new=2,
                  slots=4, max_len=32)
    reqs = _reqs(prompts, rate=1000.0)
    rep = eng.run(reqs)

    done = [r for r in rep.results if r.status == "done"]
    assert len(done) == 16 and rep.rejected == 0
    # FIFO: completion order == arrival order (equal decode lengths)
    assert [r.rid for r in done] == [q.rid for q in reqs]
    for r in done:
        assert r.admitted_t >= r.arrival_t - 1e-9
        assert r.queue_delay_s >= 0.0
    assert rep.generated_tokens == 16 * 2
    assert rep.tokens_per_s > 0.0
    # the acceptance invariant: in-use ≤ reserved ≤ pool capacity, and
    # capacity + resident params ≤ the configured global budget
    pool = rep.pool
    assert pool["peak_in_use_bytes"] <= pool["peak_reserved_bytes"] + 1e-6
    assert pool["peak_reserved_bytes"] <= pool["capacity_bytes"] + 1e-6
    assert (pool["capacity_bytes"] + eng.resident_param_bytes
            <= budget + 1e-6)
    assert pool["overcommit_events"] == 0
    # pool fully drained after the run
    assert pool["reserved_bytes"] == 0 and pool["in_use_bytes"] == 0


def test_engine_rejects_oversized_request(served):
    model, params, batch, mm, c = served
    cfg = model.cfg
    full = masks.full_mask(cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 64)
    eng = _engine(model, params, c, mm, budget=budget, slots=2, max_len=24)
    toks = np.asarray(batch["tokens"])
    reqs = _reqs([toks[:1, :30], toks[:1, :16]])  # 30+4 > max_len=24
    rep = eng.run(reqs)
    by = {r.rid: r for r in rep.results}
    assert by["r0"].status == "rejected" and "capacity" in by["r0"].reason
    assert by["r1"].status == "done"
    assert rep.rejected == 1


def test_engine_strict_requires_headroom(served):
    """A global budget below resident parameter bytes cannot host a strict
    pool — admission control refuses to start rather than thrash."""
    model, params, batch, mm, c = served
    eng = _engine(model, params, c, mm, budget=1.0)
    with pytest.raises(ValueError):
        eng.run(_reqs([np.asarray(batch["tokens"])[:1, :8]]))


def test_controller_batch_aware_decide_and_memo(served):
    """reserved_bytes shrinks the effective budget; identical effective
    budgets hit the memo table."""
    model, params, batch, mm, c = served
    L = model.cfg.n_layers
    dense = mm.dense_peak(1, 32)
    a = c.decide(1, 32, dense, reserved_bytes=0.35 * dense)
    b = c.decide(1, 32, 0.65 * dense)
    np.testing.assert_array_equal(a.mask, b.mask)
    assert b.cached                       # same (bucket, shape) memo key
    assert b.latency_s < a.latency_s or a.cached
    full_budget = c.decide(1, 32, 2 * dense)
    assert full_budget.mask.sum() >= a.mask.sum()


def test_poisson_trace_deterministic_and_ordered():
    cfg = PoissonConfig(seed=3, n_requests=20, rate=8.0)
    a, b = poisson_requests(cfg), poisson_requests(cfg)
    assert [r.t for r in a] == [r.t for r in b]
    ts = [r.t for r in a]
    assert all(t2 > t1 for t1, t2 in zip(ts, ts[1:]))
    assert all(r.seq_len % cfg.round_len_to == 0 for r in a)
    assert len(a) == 20


# ------------------------------------------------------- serving-API split
def test_old_constructor_raises_migration_hint(served):
    """Pre-split callers passed a RAPController (positionally or via the
    controller= kwarg); both must fail loudly with the wrapping recipe."""
    model, params, batch, mm, c = served
    with pytest.raises(TypeError, match="RLPolicy"):
        RAPEngine(model, params, c, EngineConfig())
    with pytest.raises(TypeError, match="RLPolicy"):
        RAPEngine(model, params, controller=c)
    with pytest.raises(TypeError, match="RLPolicy"):
        RAPServer(model, params, c)
    with pytest.raises(TypeError, match="RLPolicy"):
        RAPServer(model, params, controller=c)


def test_engine_config_validation():
    """Numeric misconfigurations fail at construction with actionable
    messages, not deep inside a serve loop."""
    with pytest.raises(ValueError, match="budget_quantum_frac"):
        EngineConfig(budget_quantum_frac=1.5)
    with pytest.raises(ValueError, match="budget_quantum_frac"):
        EngineConfig(budget_quantum_frac=-0.1)
    with pytest.raises(ValueError, match="max_active"):
        EngineConfig(max_active=0)
    with pytest.raises(ValueError, match="tokens_per_page"):
        EngineConfig(tokens_per_page=0)
    with pytest.raises(ValueError, match="max_len"):
        EngineConfig(max_len=0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        EngineConfig(max_new_tokens=-1)
    with pytest.raises(ValueError, match="budget_bytes"):
        EngineConfig(budget_bytes=-1.0)
    with pytest.raises(ValueError, match="decode_buckets"):
        EngineConfig(decode_buckets=(0, 2))
    with pytest.raises(ValueError, match="len_buckets"):
        EngineConfig(len_buckets="linear")
    with pytest.raises(ValueError, match="preemption_enabled"):
        EngineConfig(preemption_enabled=1)
    with pytest.raises(ValueError, match="spill_headroom_frac"):
        EngineConfig(spill_headroom_frac=1.0)
    with pytest.raises(ValueError, match="spill_headroom_frac"):
        EngineConfig(spill_headroom_frac=-0.1)
    with pytest.raises(ValueError, match="victim_policy"):
        EngineConfig(victim_policy="coinflip")
    EngineConfig(budget_quantum_frac=0.0, max_active=1, tokens_per_page=1,
                 preemption_enabled=False, spill_headroom_frac=0.0,
                 victim_policy="arrival")


def _two_prompts(batch):
    toks = np.asarray(batch["tokens"])
    return toks[:1, :24], toks[:1, :8]   # long, short


def test_scheduler_fifo_vs_sjf_completion_order(served):
    """One slot, long request first: FIFO serves arrival order, SJF runs
    the short job first."""
    model, params, batch, mm, c = served
    cfg = model.cfg
    long_p, short_p = _two_prompts(batch)
    full = masks.full_mask(cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    orders = {}
    for sched in ("fifo", "sjf"):
        eng = _engine(model, params, c, mm, budget=budget, max_new=2,
                      slots=1, max_len=32, scheduler=sched)
        reqs = [EngineRequest(rid="long", prompt=long_p, arrival_t=0.0),
                EngineRequest(rid="short", prompt=short_p, arrival_t=0.0)]
        rep = eng.run(reqs)
        orders[sched] = [r.rid for r in rep.results if r.status == "done"]
    assert orders["fifo"] == ["long", "short"]
    assert orders["sjf"] == ["short", "long"]


def test_engine_duplicate_rid_rejected_not_crashed(served):
    """Two same-rid requests in one tick: the second is rejected as a
    result, not raised as a ValueError that loses the whole run."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    eng = _engine(model, params, c, mm, budget=budget, max_new=2)
    reqs = [EngineRequest(rid="dup", prompt=toks[:1, :16], arrival_t=0.0),
            EngineRequest(rid="dup", prompt=toks[:1, :16], arrival_t=0.0)]
    rep = eng.run(reqs)
    statuses = sorted(r.status for r in rep.results)
    assert statuses == ["done", "rejected"]
    rej = [r for r in rep.results if r.status == "rejected"][0]
    assert "duplicate" in rej.reason


def test_sjf_cost_scales_with_batch(served):
    """SJF orders by total KV demand (batch × tokens), not per-row prompt
    length: a 2-row short request is a LARGER job than a 1-row longer
    one."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 6 * mm.state_bytes(full, 1, 32)
    eng = _engine(model, params, c, mm, budget=budget, max_new=2,
                  slots=2, max_len=32, scheduler="sjf")
    reqs = [EngineRequest(rid="wide", prompt=toks[:2, :16], arrival_t=0.0),
            EngineRequest(rid="narrow", prompt=toks[:1, :24],
                          arrival_t=0.0)]
    rep = eng.run(reqs)
    # narrow: 1×26 tokens < wide: 2×18 tokens → narrow first
    assert [r.rid for r in rep.results if r.status == "done"] == \
        ["narrow", "wide"]


def test_scheduler_priority_overrides_arrival(served):
    model, params, batch, mm, c = served
    cfg = model.cfg
    long_p, short_p = _two_prompts(batch)
    full = masks.full_mask(cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    eng = _engine(model, params, c, mm, budget=budget, max_new=2,
                  slots=1, max_len=32, scheduler="priority")
    reqs = [EngineRequest(rid="steerage", prompt=short_p, arrival_t=0.0,
                          priority=5),
            EngineRequest(rid="vip", prompt=long_p, arrival_t=0.0,
                          priority=-1)]
    rep = eng.run(reqs)
    assert [r.rid for r in rep.results if r.status == "done"] == \
        ["vip", "steerage"]


def test_priority_scheduler_aging_prevents_starvation():
    """Aging bounds starvation: a low-priority request behind a steady
    high-priority stream sorts ahead once it has waited
    ``aging_s × Δpriority`` seconds — instead of being deferred forever.
    Pure scheduler-level pin (no engine) so the ordering math is exact."""
    from repro.runtime import PriorityScheduler

    sched = PriorityScheduler(aging_s=1.0)
    low = EngineRequest(rid="low", prompt=np.zeros((1, 4), np.int32),
                        arrival_t=0.0, priority=5)
    sched.add(low, cost=8.0)
    # steady stream: one fresh high-priority arrival per second, and the
    # head of each tick's plan is admitted (removed) — the scenario that
    # starves `low` forever without aging
    admitted = []
    for t in range(10):
        sched.add(EngineRequest(rid=f"hi{t}",
                                prompt=np.zeros((1, 4), np.int32),
                                arrival_t=float(t), priority=0), cost=8.0)
        head = sched.schedule(float(t)).admit[0]
        admitted.append(head.rid)
        sched.remove(head.rid)
    # the stream wins while effective(low) = 5 - t exceeds a fresh hi's 0
    assert admitted[:5] == [f"hi{t}" for t in range(5)]
    # ...then low overtakes, exactly at aging_s × Δpriority = 5 s
    assert admitted[5] == "low"
    # aging disabled → starvation returns, no matter how long it waits
    frozen = PriorityScheduler(aging_s=float("inf"))
    frozen.add(low, cost=8.0)
    frozen.add(EngineRequest(rid="hi", prompt=np.zeros((1, 4), np.int32),
                             arrival_t=1e6, priority=0), cost=8.0)
    assert frozen.schedule(1e9).admit[0].rid == "hi"
    # validation: aging_s must be a positive duration
    for bad in (0.0, -1.0):
        with pytest.raises(ValueError, match="aging_s"):
            PriorityScheduler(aging_s=bad)


def test_decode_buckets_token_equivalent(served):
    """Dynamic decode-batch buckets must not change greedy tokens."""
    model, params, batch, mm, c = served
    cfg = model.cfg
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(cfg.n_layers)
    budget = mm.param_bytes(full) + 6 * mm.state_bytes(full, 1, 32)
    prompts = [toks[:1, :16], toks[:1, :24], toks[:1, :16]]
    outs = {}
    for buckets in ((1, 2, 4, 8), ()):
        eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
            mode="masked", max_new_tokens=4, max_active=8, max_len=32,
            budget_bytes=budget, decode_buckets=buckets))
        rep = eng.run(_reqs(prompts))
        outs[buckets] = {r.rid: r.tokens for r in rep.results}
    for rid, t in outs[(1, 2, 4, 8)].items():
        np.testing.assert_array_equal(t, outs[()][rid])


def test_server_pow2_len_buckets_fix_recompile_trap(served):
    """A long serve mints its own long-cache group; re-serving the short
    shape afterwards hits the already-compiled short group (the historical
    shim dropped every group on max_len growth)."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    srv = RAPServer(model, params, RLPolicy(c), mode="masked",
                    max_new_tokens=2)
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 64)
    r1 = srv.serve(toks[:1, :8], budget)      # short → 16-token bucket
    assert r1.compiled_new
    r2 = srv.serve(toks[:1, :30], budget)     # long → 32-token bucket
    assert r2.compiled_new
    r3 = srv.serve(toks[:1, :8], budget)      # short again: no recompile
    assert not r3.compiled_new
    np.testing.assert_array_equal(r1.tokens, r3.tokens)


# ------------------------------------------------------------ paged executor
def _paged_engine(model, params, c, mm, *, budget, max_new=2, slots=4,
                  max_len=32, tokens_per_page=8, scheduler=None):
    ex = PagedExecutor(model, params, max_active=slots)
    return RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=max_new, max_active=slots,
        max_len=max_len, budget_bytes=budget,
        tokens_per_page=tokens_per_page), scheduler=scheduler, executor=ex)


# NOTE: the paged-vs-local token-equivalence acceptance test moved into
# the cross-executor conformance suite (tests/test_executors.py), which
# runs EVERY backend — local, paged, sharded — through the same trace.


def test_engine_paged_mixed_lengths_one_group(served):
    """Heterogeneous cache lengths share ONE paged group (the pow2
    cache-length machinery is gone on this path) and heterogeneous
    per-slot masks decode together."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 3 * mm.state_bytes(full, 1, 30)
    eng = _paged_engine(model, params, c, mm, budget=budget, max_new=4,
                        slots=4, max_len=32, tokens_per_page=4)
    prompts = [toks[:1, :8], toks[:1, :24], toks[:1, :16]]
    rep = eng.run(_reqs(prompts))
    assert all(r.status == "done" for r in rep.results)
    assert eng.executor.stats()["groups"] == 1
    # every request decoded against its own page-table row: cross-check
    # token equality against the local reference path
    ref = _engine(model, params, c, mm, budget=budget, max_new=4,
                  slots=4, max_len=32)
    rep_ref = ref.run(_reqs(prompts))
    for r in rep_ref.results:
        np.testing.assert_array_equal(
            r.tokens, next(p.tokens for p in rep.results if p.rid == r.rid))


def test_engine_paged_queues_under_page_pressure(served):
    """A pool sized below the trace's concurrent demand must queue (defer)
    paged admissions — commitments, not optimism — and still finish."""
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    # room for roughly one dense request's page commitment at a time
    # (a 26-token request commits ceil(26/8)=4 pages; 1.7 × analytical
    # bytes quantizes to 5 physical pages)
    budget = mm.param_bytes(full) + 1.7 * mm.state_bytes(full, 1, 26)
    eng = _paged_engine(model, params, c, mm, budget=budget, max_new=2,
                        slots=4, max_len=32)
    prompts = [toks[:1, :24] for _ in range(4)]
    rep = eng.run(_reqs(prompts))
    assert all(r.status == "done" for r in rep.results)
    assert rep.pool["overcommit_events"] == 0
    assert rep.pool["peak_reserved_bytes"] <= rep.pool["capacity_bytes"] + 1e-6
    # with ~1 request of headroom, later arrivals must have waited
    assert max(r.queue_delay_s for r in rep.results) > 0.0


def test_paged_executor_validation(served):
    """Misconfigurations fail loudly at construction, not mid-serve."""
    model, params, batch, mm, c = served
    # structural paged buckets are now a supported mode (DESIGN.md §9);
    # unknown modes still fail loudly at construction
    with pytest.raises(ValueError, match="mode"):
        PagedExecutor(model, params, mode="gated")
    # int8 paged pools are now a supported precision: the executor
    # resolves the canonical name and allocates quantized pages + scales
    import jax.numpy as jnp
    ex8 = PagedExecutor(model, params, kv_dtype=jnp.int8)
    assert ex8.kv_dtype_name == "int8" and ex8.kv_quantized
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedExecutor(model, params, kv_dtype="int4")
    ex = PagedExecutor(model, params)
    with pytest.raises(ValueError, match="masked"):
        RAPEngine(model, params, RLPolicy(c),
                  EngineConfig(mode="structural"), executor=ex)
    with pytest.raises(ValueError, match="strict"):
        RAPEngine(model, params, RLPolicy(c),
                  EngineConfig(admission="force"), executor=ex)
    with pytest.raises(RuntimeError, match="bind_pool"):
        ex.group_for(masks.full_mask(model.cfg.n_layers), 32)


def test_sharded_executor_places_params_and_serves(served):
    """Single-device smoke of the sharded serve path (the mesh-sharded
    variants run in the multi-device CI job — tests/test_executors.py):
    params placed under the production rules, a degenerate (1, 1) mesh
    serves a trace bitwise-identical to LocalExecutor, and the
    still-unimplemented corners point at the ROADMAP."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import ShardedExecutor

    model, params, batch, mm, c = served
    mesh = make_host_mesh((1, 1), ("data", "model"))
    ex = ShardedExecutor(model, mesh, params=params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ex.params)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ex.groups() == []

    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 32)
    prompts = [toks[:1, :16], toks[:1, :24]]
    rep_l = _engine(model, params, c, mm, budget=budget,
                    max_new=2).run(_reqs(prompts))
    eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=2, max_active=4, max_len=32,
        budget_bytes=budget),
        executor=ShardedExecutor(model, mesh, params=params, max_active=4))
    rep_s = eng.run(_reqs(prompts))
    for r in rep_l.results:
        s = next(x for x in rep_s.results if x.rid == r.rid)
        assert r.status == s.status == "done"
        np.testing.assert_array_equal(r.tokens, s.tokens)
    assert eng.stats()["mesh_devices"] == 1

    # unimplemented corners fail loudly with the ROADMAP pointer
    with pytest.raises(NotImplementedError, match="ROADMAP"):
        ShardedExecutor(model, mesh, params=params, mode="structural")
    with pytest.raises(RuntimeError, match="params"):
        ShardedExecutor(model, mesh).group_for(full, 32)


# ------------------------------------- elastic budgets / spill / cancel
# (DESIGN.md §11). Budget shocks in tests are TICK-counting staircases
# (repro.runtime.scenarios.TickStaircase): the engine evaluates callable
# traces once per tick, so the shock hits after a deterministic number of
# ticks regardless of how long a tick takes on the host running the test.


def _shock_engine(served, *, kind="paged", max_new=6, horizon=2, chunk=0,
                  scheduler=None, victim_policy="scheduler",
                  preemption_enabled=True):
    model, params, batch, mm, c = served
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 2.5 * mm.state_bytes(full, 1, 30)
    ex = (PagedExecutor(model, params, max_active=4) if kind == "paged"
          else None)
    eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=max_new, max_active=4, max_len=32,
        budget_bytes=budget, tokens_per_page=8, decode_horizon=horizon,
        max_prefill_tokens=chunk, victim_policy=victim_policy,
        preemption_enabled=preemption_enabled),
        executor=ex, scheduler=scheduler)
    toks = np.asarray(batch["tokens"])
    prompts = [toks[:1, : (16 if i % 2 else 24)] for i in range(6)]
    return eng, _reqs(prompts), budget


def _kv_staircase(eng, budget, down, up, frac=0.5):
    """Tick staircase cutting FRAC of the KV headroom (params stay
    resident; cutting the total would zero the pool at smoke scale)."""
    from repro.runtime import TickStaircase
    kv = budget - eng.resident_param_bytes
    shocked = (eng.resident_param_bytes + (1.0 - frac) * kv) / budget
    return TickStaircase(budget, [(down, 1.0), (up - down, shocked),
                                  (0, 1.0)])


def test_select_victims_priority_and_aging():
    """SLO-tier victim order: lowest effective priority (largest numeric
    rank, aged by waiting time) first, most-remaining-work tiebreak, then
    newest arrival — and the base scheduler (no priority notion) falls
    through to the tiebreaks."""
    from repro.runtime import FIFOScheduler, PriorityScheduler
    from repro.runtime.scheduler import VictimCandidate

    def cand(rid, prio, arr, rem):
        return VictimCandidate(rid=rid, priority=prio, arrival_t=arr,
                               remaining_tokens=rem, reserved_bytes=100.0)

    pr = PriorityScheduler(aging_s=10.0)
    # low tier (rank 2) yields before high tier (rank 0)
    order = pr.select_victims([cand("hi", 0, 0.0, 4),
                               cand("lo", 2, 0.0, 4)], now=1.0)
    assert [c.rid for c in order] == ["lo", "hi"]
    # aging: a low-tier request that waited 3 levels' worth outranks a
    # fresh mid-tier one (preempted later), same contract admission has
    order = pr.select_victims([cand("old-lo", 2, 0.0, 4),
                               cand("new-mid", 1, 29.0, 4)], now=30.0)
    assert [c.rid for c in order] == ["new-mid", "old-lo"]
    # ties: most remaining work yields first, then newest arrival
    fifo = FIFOScheduler()
    order = fifo.select_victims([cand("short", 0, 0.0, 1),
                                 cand("long", 0, 0.0, 9)], now=0.0)
    assert [c.rid for c in order] == ["long", "short"]
    order = fifo.select_victims([cand("early", 0, 0.0, 4),
                                 cand("late", 0, 5.0, 4)], now=9.0)
    assert [c.rid for c in order] == ["late", "early"]


def test_engine_preempts_and_drains_under_shock(served):
    """A mid-serve KV-budget cut preempts victims (pages spilled to host)
    and the run still completes every request, token-identical to an
    unshocked run; the pool ends fully drained and the report carries the
    preemption accounting."""
    eng, reqs, budget = _shock_engine(served)
    ref = eng.run(reqs)
    assert all(r.status == "done" for r in ref.results)
    eng2, reqs2, _ = _shock_engine(served)
    rep = eng2.run(reqs2, budget_trace=_kv_staircase(eng2, budget, 4, 12,
                                                     frac=0.6))
    assert rep.preempted_count > 0 and rep.spilled_mb > 0.0
    assert rep.resume_latency["count"] >= 1
    assert len(rep.budget_events) >= 3       # full → shocked → recovered
    done = {r.rid: r for r in rep.results if r.status == "done"}
    assert len(done) == len(reqs2)
    for r in ref.results:
        np.testing.assert_array_equal(r.tokens, done[r.rid].tokens)
    st = eng2.pool.stats()
    assert st["live_requests"] == 0 and st["spilled_requests"] == 0
    assert st["free_pages"] == st["n_pages"]
    # preempted requests' ITL pooled separately from untouched ones
    assert rep.itl_preempted["count"] > 0
    assert rep.itl["count"] > 0


def test_engine_preemption_disabled_still_gates_admission(served):
    """preemption_enabled=False: a shock never evicts running requests
    (preempted_count == 0) but the shrunken budget still defers NEW
    admissions; the run drains once the budget recovers."""
    eng, reqs, budget = _shock_engine(served, preemption_enabled=False)
    rep = eng.run(reqs, budget_trace=_kv_staircase(eng, budget, 4, 12,
                                                   frac=0.6))
    assert rep.preempted_count == 0
    assert all(r.status == "done" for r in rep.results)


def test_engine_force_resume_drains_without_recovery(served):
    """A trace that never recovers must not deadlock: the idle-engine
    backstop force-resumes preempted requests (physical capacity checks
    only) and the run drains."""
    from repro.runtime import TickStaircase
    eng, reqs, budget = _shock_engine(served)
    kv = budget - eng.resident_param_bytes
    never_up = TickStaircase(budget, [
        (4, 1.0), (0, (eng.resident_param_bytes + 0.3 * kv) / budget)])
    rep = eng.run(reqs, budget_trace=never_up)
    assert rep.preempted_count > 0
    # every ADMITTED request drains to completion (force-resumed victims
    # included); requests the shocked budget can never admit are rejected
    # loudly rather than spun on forever
    by_status = {}
    for r in rep.results:
        by_status.setdefault(r.status, []).append(r)
    assert by_status.get("done"), "nothing drained"
    assert set(by_status) <= {"done", "rejected"}
    for r in by_status.get("rejected", []):
        assert "budget" in (r.reason or "") or "deferred" in (r.reason or "")
    st = eng.pool.stats()
    assert st["live_requests"] == 0 and st["spilled_requests"] == 0


def test_engine_cancel_every_lifecycle_stage(served):
    """cancel(rid) is safe at every stage: pending (not yet arrived),
    queued, prefilling, decoding mid-horizon, and preempted — plus
    double-cancel and unknown-rid no-ops. Pool drains to zero live rids
    and zero leaked pages."""
    model, params, batch, mm, c = served
    full = masks.full_mask(model.cfg.n_layers)
    toks = np.asarray(batch["tokens"])
    budget = mm.param_bytes(full) + 2.0 * mm.state_bytes(full, 1, 30)
    eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=8, max_active=2, max_len=32,
        budget_bytes=budget, tokens_per_page=8, decode_horizon=2,
        max_prefill_tokens=8),
        executor=PagedExecutor(model, params, max_active=2))
    # r5 arrives far in the future → stays pending; 2 slots force a queue
    reqs = [EngineRequest(rid=f"r{i}", prompt=toks[:1, :24],
                          arrival_t=0.001 * i, max_new=8) for i in range(5)]
    reqs.append(EngineRequest(rid="r5", prompt=toks[:1, :16],
                              arrival_t=120.0, max_new=8))
    staircase = _kv_staircase(eng, budget, 6, 10 ** 9, frac=0.7)
    state = {"tick": 0, "hit": set()}

    def on_tick(e):
        state["tick"] += 1
        assert e.cancel("nonexistent") is False
        if "pending" not in state["hit"] and any(
                r.rid == "r5" for r in e._pending):
            assert e.cancel("r5") is True
            assert e.cancel("r5") is False          # double-cancel no-op
            state["hit"].add("pending")
        if "queued" not in state["hit"] and "r4" in e.scheduler:
            assert e.cancel("r4") is True
            state["hit"].add("queued")
        if "prefilling" not in state["hit"] and e._prefilling:
            rid = next(iter(e._prefilling))
            assert e.cancel(rid) is True
            state["hit"].add("prefilling")
        elif "running" not in state["hit"] and e._running:
            rid = next(iter(e._running))
            assert e.cancel(rid) is True            # mid-horizon: scan in
            assert e.cancel(rid) is False           # flight right now
            state["hit"].add("running")
        if "preempted" not in state["hit"] and e._preempted:
            rid = next(iter(e._preempted))
            assert e.cancel(rid) is True
            state["hit"].add("preempted")

    rep = eng.run(reqs, budget_trace=staircase, on_tick=on_tick)
    assert {"pending", "queued", "prefilling", "running",
            "preempted"} <= state["hit"]
    by = {r.rid: r for r in rep.results}
    assert by["r5"].status == "cancelled" and by["r4"].status == "cancelled"
    assert rep.cancelled == sum(1 for r in rep.results
                                if r.status == "cancelled") >= 5
    st = eng.pool.stats()
    assert st["live_requests"] == 0 and st["spilled_requests"] == 0
    assert st["free_pages"] == st["n_pages"]


def test_engine_cancel_races_completion_safely(served):
    """The missing_ok seam from the engine API: cancelling a rid that
    completed earlier in the same run is a no-op (False), and a cancelled
    request's tokens are truncated to what it had generated — fold-back
    never resurrects it."""
    eng, reqs, budget = _shock_engine(served, max_new=4)
    finished = {}
    did_cancel = []

    def on_tick(e):
        for r in e._results:
            if r.status == "done" and r.rid not in finished:
                finished[r.rid] = True
                assert e.cancel(r.rid) is False     # racing a completion
        if finished and not did_cancel and e._running:
            did_cancel.append(True)
            rid = next(iter(e._running))
            run = e._running[rid]
            n_before = len(run.out)
            assert e.cancel(rid) is True
            res = next(x for x in e._results if x.rid == rid)
            n_tokens = 0 if res.tokens is None else res.tokens.shape[1]
            assert n_tokens == n_before < run.max_new

    rep = eng.run(reqs, on_tick=on_tick)
    assert rep.cancelled == 1
    assert sum(1 for r in rep.results if r.status == "done") == len(reqs) - 1
    st = eng.pool.stats()
    assert st["live_requests"] == 0 and st["free_pages"] == st["n_pages"]


def test_engine_cancellation_storm_no_leaks(served):
    """Deterministic tier-1 cancellation storm (the bench hard-gates the
    same invariants): ≥25% of requests cancelled at random lifecycle
    stages under a concurrent budget shock — zero live rids, zero leaked
    pages, zero spilled leftovers, no deadlock."""
    from repro.runtime import run_cancellation_storm
    eng, reqs, budget = _shock_engine(served, max_new=6)
    res = run_cancellation_storm(
        eng, reqs, cancel_frac=0.34, seed=5,
        budget_trace=_kv_staircase(eng, budget, 4, 14, frac=0.6))
    assert res["cancelled"] >= res["cancel_quota"] >= 2
    assert res["live_requests"] == 0
    assert res["leaked_pages"] == 0
    assert res["spilled_requests"] == 0
    assert res["done"] + res["cancelled"] == len(reqs)
    assert not res["deadlock"]


def test_run_exception_releases_pool(served):
    """A run that raises mid-serve releases pages, commitments, spilled
    copies, and seated slots — the next run() on the same engine starts
    from a clean ledger (the cross-run rid-leak fix)."""
    eng, reqs, budget = _shock_engine(served)

    class Boom(RuntimeError):
        pass

    def bomb(e):
        if e._running and e._preempted:
            raise Boom("fault injection")

    with pytest.raises(Boom):
        eng.run(reqs, budget_trace=_kv_staircase(eng, budget, 3, 10 ** 9,
                                                 frac=0.7), on_tick=bomb)
    st = eng.pool.stats()
    assert st["live_requests"] == 0 and st["spilled_requests"] == 0
    assert st["free_pages"] == st["n_pages"]
    assert not eng._running and not eng._preempted and not eng._prefilling
    # the engine is reusable: a fresh run serves normally
    rep = eng.run(reqs)
    assert all(r.status == "done" for r in rep.results)
    st = eng.pool.stats()
    assert st["live_requests"] == 0 and st["free_pages"] == st["n_pages"]


def test_kv_pool_spill_restore_roundtrip_bitwise():
    """Unit-level spill→restore on a physical int8 pool: page contents
    and scale rows written back bitwise into freshly granted pages, the
    free list and commitments restored exactly."""
    import jax.numpy as jnp
    pt, K, D, layers = 2, 2, 4, 2
    page_bytes = 2 * layers * pt * K * D * 1 + 2 * layers * K * 4
    pool = KVPool(8 * page_bytes, page_bytes=page_bytes, tokens_per_page=pt)
    pool.allocate_physical(n_layers=layers, n_kv_heads=K, head_dim=D,
                           dtype=jnp.float32, kv_dtype="int8")
    pool.alloc_tokens("a", 2, 3, max_tokens=6, in_use_bytes=6.0,
                      in_use_per_token=1.0, kv_dtype="int8")
    rows = pool.row_pages("a")
    rng = np.random.default_rng(0)
    ids = [p for row in rows for p in row]
    k_ref = rng.integers(-127, 127, (layers, len(ids), K, pt, D),
                         dtype=np.int8)
    s_ref = rng.uniform(0.1, 2.0, (layers, len(ids), K)).astype(np.float32)
    idx = jnp.asarray(np.asarray(ids, np.int32))
    pool.k_pages = pool.k_pages.at[:, idx].set(jnp.asarray(k_ref))
    pool.v_pages = pool.v_pages.at[:, idx].set(jnp.asarray(k_ref))
    pool.k_scales = pool.k_scales.at[:, idx].set(jnp.asarray(s_ref))
    pool.v_scales = pool.v_scales.at[:, idx].set(jnp.asarray(s_ref))
    reserved_before = pool.bytes_reserved
    freed = pool.spill("a")
    assert freed == reserved_before
    assert pool.bytes_reserved == 0 and pool.committed_pages == 0
    assert sorted(pool._free) == list(range(pool.n_pages))
    assert pool.spilled_requests() == ["a"]
    # clobber the old pages: restore must not depend on them
    pool.k_pages = pool.k_pages.at[:, idx].set(0)
    pool.k_scales = pool.k_scales.at[:, idx].set(0.0)
    # occupy some pages so the restore lands on a DIFFERENT layout
    pool.alloc_tokens("b", 1, 2 * pt, max_tokens=2 * pt,
                      in_use_bytes=1.0, in_use_per_token=0.5,
                      kv_dtype="int8")
    assert pool.can_restore("a")
    new_rows = pool.restore("a")
    assert pool.bytes_reserved == reserved_before + pool.page_bytes * 2
    new_ids = [p for row in new_rows for p in row]
    nidx = jnp.asarray(np.asarray(new_ids, np.int32))
    np.testing.assert_array_equal(np.asarray(pool.k_pages[:, nidx]), k_ref)
    np.testing.assert_array_equal(np.asarray(pool.v_pages[:, nidx]), k_ref)
    np.testing.assert_array_equal(np.asarray(pool.k_scales[:, nidx]), s_ref)
    np.testing.assert_array_equal(np.asarray(pool.v_scales[:, nidx]), s_ref)
    # token extension works after restore exactly as before the spill
    pool.extend("a", 3)
    pool.free("a")
    pool.free("b")
    assert pool.bytes_reserved == 0
    assert sorted(pool._free) == list(range(pool.n_pages))
    # drop_spilled is idempotent like free(missing_ok=True)
    assert pool.drop_spilled("a", missing_ok=True) is False
    with pytest.raises(ValueError, match="drop_spilled"):
        pool.drop_spilled("a")


def test_kv_pool_spill_guards():
    """Spill/restore edge contracts: unknown rids raise with the spilled
    set named, double-spill is impossible (rid leaves the live set), and
    a rid cannot be re-allocated while spilled."""
    pool = KVPool(800, page_bytes=100, tokens_per_page=2)
    pool.alloc_tokens("a", 1, 2, max_tokens=4, in_use_bytes=2.0,
                      in_use_per_token=1.0)
    pool.spill("a")
    with pytest.raises(ValueError, match="spill"):
        pool.spill("a")                    # no longer live
    with pytest.raises(ValueError, match="already"):
        pool.alloc_tokens("a", 1, 2, max_tokens=4, in_use_bytes=2.0,
                          in_use_per_token=1.0)
    with pytest.raises(ValueError, match="restore"):
        pool.restore("zzz")
    pool.restore("a")
    assert pool.spilled_requests() == []
    pool.free("a")
