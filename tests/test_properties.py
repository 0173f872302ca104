"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
from hypothesis import given, settings

from repro.configs import get_smoke_config
from repro.core import masks, memory
from repro.data import SyntheticCorpus
from repro.kernels import ref
from repro.optim import adamw
from repro.runtime import KVPool, PoolExhausted

CFG = get_smoke_config("llama2-7b").replace(n_layers=4)
MM = memory.build_memory_model(CFG)
L = CFG.n_layers

mask_strategy = st.lists(st.booleans(), min_size=2 * L, max_size=2 * L)


@settings(max_examples=50, deadline=None)
@given(mask=mask_strategy, bs=st.integers(1, 64), sql=st.integers(1, 8192))
def test_memory_model_monotone(mask, bs, sql):
    """Peak memory is monotone: removing any block never increases it, and
    every peak is ≥ the embedding floor."""
    m = np.asarray(mask, bool)
    peak = MM.peak_bytes(m, bs, sql)
    assert peak >= MM.embed_bytes - 1e-6
    live = np.nonzero(m)[0]
    if len(live):
        m2 = masks.remove_block(m, int(live[0]))
        assert MM.peak_bytes(m2, bs, sql) <= peak + 1e-6


@settings(max_examples=30, deadline=None)
@given(bs=st.integers(1, 32), s1=st.integers(1, 2048), s2=st.integers(1, 2048))
def test_kv_linear_in_seq(bs, s1, s2):
    """Eq. (1): KV state is linear in seq_len (dense full mask)."""
    full = masks.full_mask(L)
    a = MM.state_bytes(full, bs, s1)
    b = MM.state_bytes(full, bs, s2)
    c = MM.state_bytes(full, bs, s1 + s2)
    assert abs((a + b) - c) < 1e-3


@settings(max_examples=25, deadline=None)
@given(mask=mask_strategy)
def test_compact_layout_consistent(mask):
    """Compacted layout has exactly the retained blocks, in order."""
    m = np.asarray(mask, bool)
    layout, gather = masks.compact_layout(CFG, m)
    n_mixers = sum(1 for s in layout if s.mixer is not None)
    n_ffns = sum(1 for s in layout if s.ffn is not None)
    assert n_mixers == int(m[:L].sum())
    assert n_ffns == int(m[L:].sum())
    # gather indices are strictly increasing per kind (order preserved)
    for kind, idxs in gather.items():
        assert idxs == sorted(idxs)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), batch=st.integers(1, 4),
       seq=st.integers(2, 64))
def test_corpus_deterministic_and_in_range(seed, batch, seq):
    c = SyntheticCorpus(128, seed=seed)
    b1 = c.batch(batch, seq)
    b2 = c.batch(batch, seq)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].min() >= 0 and b1["tokens"].max() < 128


@settings(max_examples=20, deadline=None)
@given(t=st.integers(1, 40), w=st.integers(1, 8))
def test_rglru_ref_contraction(t, w):
    """|h_t| stays bounded when |a|<1 and |b| bounded (stability)."""
    rng = np.random.default_rng(t * 100 + w)
    a = jnp.asarray(rng.uniform(0.0, 0.99, (1, t, w)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-1, 1, (1, t, w)).astype(np.float32))
    h = ref.rglru_ref(a, b)
    assert np.abs(np.asarray(h)).max() <= 1.0 / (1 - 0.99) + 1e-3


@settings(max_examples=10, deadline=None)
@given(steps=st.integers(1, 5))
def test_adamw_descends_quadratic(steps):
    """AdamW reduces a convex quadratic within a few steps."""
    cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                            schedule="constant", clip_norm=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw.init(params)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    l0 = float(loss(params))
    for _ in range(steps):
        g = jax.grad(loss)(params)
        params, state, _ = adamw.apply(cfg, params, g, state)
    assert float(loss(params)) < l0


@settings(max_examples=15, deadline=None)
@given(frac=st.floats(0.3, 1.0), bs=st.integers(1, 16),
       sql=st.integers(64, 4096))
def test_budget_fraction_semantics(frac, bs, sql):
    b = memory.budget_bytes(MM, bs, sql, frac)
    assert abs(b - frac * MM.dense_peak(bs, sql)) < 1e-6


# ----------------------------------------------------------------- KV pool
def _pool_invariants(pool, n_pages, overcommits_seen):
    """Structural invariants that must hold after EVERY pool operation."""
    held_byte = [p for a in pool._live.values() for p in a.pages
                 if p < n_pages]
    held_tok = [p for a in pool._tok.values() for row in a.rows for p in row]
    held = held_byte + held_tok
    # page conservation: free ∪ held partitions [0, n_pages), no duplicates
    assert sorted(pool._free + held) == sorted(set(pool._free + held))
    # overflow ids are excluded above, so real pages always partition
    assert sorted(pool._free + held) == list(range(n_pages))
    # ledger: reserved tracks pages exactly; in_use never exceeds it
    # (within fp eps) unless a byte alloc overcommitted past capacity
    n_reserved = (sum(len(a.pages) for a in pool._live.values())
                  + len(held_tok))
    assert pool.bytes_reserved == pytest.approx(n_reserved * pool.page_bytes)
    assert pool.acct.overcommit_events >= overcommits_seen[0]
    overcommits_seen[0] = pool.acct.overcommit_events
    # commitments: never negative, always rebuildable from live allocs
    commit = sum(a.committed_pages - a.held_pages for a in pool._tok.values())
    assert pool.committed_pages == commit >= 0
    # peaks are monotone cumulative maxima
    assert pool.acct.peak_reserved_bytes >= pool.bytes_reserved
    assert pool.acct.peak_in_use_bytes >= pool.bytes_in_use - 1e-6


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_kv_pool_byte_ops_never_leak(data):
    """Random alloc/free (± overcommit) sequences: pages are conserved,
    the ledger mirrors the free list, overcommit count is monotone."""
    n_pages = data.draw(st.integers(2, 12), label="n_pages")
    pool = KVPool(n_pages * 100, page_bytes=100)
    seen = [0]
    rids = [f"r{i}" for i in range(6)]
    for step in range(data.draw(st.integers(1, 25), label="n_ops")):
        rid = data.draw(st.sampled_from(rids), label=f"rid{step}")
        if rid in pool._live:
            pool.free(rid)
        else:
            nbytes = data.draw(st.integers(1, n_pages * 150),
                               label=f"bytes{step}")
            over = data.draw(st.booleans(), label=f"over{step}")
            try:
                pool.alloc(rid, nbytes, allow_overcommit=over)
            except PoolExhausted:
                # strict-only, and for a real shortage: either the free
                # list or the ledger (held over capacity by an earlier
                # overcommit) lacked headroom
                need = pool.pages_needed(nbytes)
                assert not over and (
                    not pool.can_alloc(nbytes)
                    or not pool.acct.can_reserve(need * pool.page_bytes))
        _pool_invariants(pool, n_pages, seen)
    for rid in pool.live_requests():
        pool.free(rid)
    assert sorted(pool._free) == list(range(n_pages))
    assert pool.bytes_reserved == 0 and pool.bytes_in_use == 0


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_kv_pool_token_ops_never_leak(data):
    """Random alloc_tokens/extend/free sequences: pages conserved, the
    reserved ≥ in-use invariant holds, commitments guarantee that every
    extend within max_tokens succeeds."""
    n_pages = data.draw(st.integers(2, 16), label="n_pages")
    pt = data.draw(st.integers(1, 6), label="tokens_per_page")
    pool = KVPool(n_pages * 64, page_bytes=64, tokens_per_page=pt)
    seen = [0]
    rids = [f"t{i}" for i in range(5)]
    for step in range(data.draw(st.integers(1, 25), label="n_ops")):
        rid = data.draw(st.sampled_from(rids), label=f"rid{step}")
        if rid in pool._tok:
            st_alloc = pool._tok[rid]
            if (st_alloc.seq_tokens < st_alloc.max_tokens
                    and data.draw(st.booleans(), label=f"ext{step}")):
                pool.extend(rid, 1)      # within commitment: must not raise
            else:
                pool.free(rid)
        else:
            batch = data.draw(st.integers(1, 3), label=f"b{step}")
            n_tok = data.draw(st.integers(1, 4 * pt), label=f"n{step}")
            max_tok = data.draw(st.integers(n_tok, 6 * pt),
                                label=f"m{step}")
            # in-use rate chosen ≤ the physical per-token rate so the
            # analytical cross-check can never outrun the reservation
            rate = data.draw(st.floats(0.0, 64.0 / pt), label=f"rate{step}")
            try:
                pool.alloc_tokens(rid, batch, n_tok, max_tokens=max_tok,
                                  in_use_bytes=rate * n_tok * batch,
                                  in_use_per_token=rate * batch)
            except PoolExhausted:
                assert not pool.can_alloc_tokens(batch, max_tok)
        _pool_invariants(pool, n_pages, seen)
        assert pool.bytes_in_use <= pool.bytes_reserved + 1e-6
    for rid in pool.live_requests():
        pool.free(rid)
    assert sorted(pool._free) == list(range(n_pages))
    assert pool.committed_pages == 0
    assert pool.bytes_reserved == 0
    assert pool.bytes_in_use == pytest.approx(0.0, abs=1e-6)


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_quantized_kv_pool_token_ops_conserve(data):
    """Quantized-pool variant of the token-ops suite: alloc/extend/free on
    an int8 physical pool conserves pages AND scale rows (the scale arrays
    never reshape, drop rows, or go non-finite across any op sequence),
    and the ledger's in-use side is charged at the physical byte width
    (``in_use_scale`` < 1 for narrow pages under a wide model dtype)."""
    n_pages = data.draw(st.integers(2, 10), label="n_pages")
    pt = data.draw(st.integers(1, 4), label="tokens_per_page")
    K, D, layers = 2, 4, 2
    # physical int8 page: elements (1 byte) + per-(layer, page, head) scales
    page_bytes = 2 * layers * pt * K * D * 1 + 2 * layers * K * 4
    pool = KVPool(n_pages * page_bytes, page_bytes=page_bytes,
                  tokens_per_page=pt)
    pool.allocate_physical(n_layers=layers, n_kv_heads=K, head_dim=D,
                           dtype=jnp.float32, kv_dtype="int8")
    assert pool.kv_dtype == "int8"
    assert pool.k_pages.dtype == jnp.int8
    sshape = (layers, pool.n_pages + 1, K)
    model_tok = 2 * K * D * 4 * layers
    assert pool.acct.in_use_scale == pytest.approx(
        (page_bytes / pt) / model_tok)
    seen = [0]
    rids = [f"q{i}" for i in range(4)]
    for step in range(data.draw(st.integers(1, 20), label="n_ops")):
        rid = data.draw(st.sampled_from(rids), label=f"rid{step}")
        if rid in pool._tok:
            st_alloc = pool._tok[rid]
            if (st_alloc.seq_tokens < st_alloc.max_tokens
                    and data.draw(st.booleans(), label=f"ext{step}")):
                pool.extend(rid, 1)
            else:
                pool.free(rid)
        else:
            batch = data.draw(st.integers(1, 2), label=f"b{step}")
            n_tok = data.draw(st.integers(1, 3 * pt), label=f"n{step}")
            max_tok = data.draw(st.integers(n_tok, 4 * pt), label=f"m{step}")
            rate = data.draw(st.floats(0.0, float(model_tok)),
                             label=f"rate{step}")
            try:
                pool.alloc_tokens(rid, batch, n_tok, max_tokens=max_tok,
                                  in_use_bytes=rate * n_tok * batch,
                                  in_use_per_token=rate * batch,
                                  kv_dtype="int8")
            except PoolExhausted:
                assert not pool.can_alloc_tokens(batch, max_tok)
        _pool_invariants(pool, n_pages, seen)
        assert pool.bytes_in_use <= pool.bytes_reserved + 1e-6
        # scale-row conservation: every op leaves the scale pools intact
        for s in (pool.k_scales, pool.v_scales):
            assert s.shape == sshape and s.dtype == jnp.float32
            assert bool(jnp.isfinite(s).all())
    for rid in pool.live_requests():
        pool.free(rid)
    assert sorted(pool._free) == list(range(n_pages))
    assert pool.committed_pages == 0
    assert pool.bytes_reserved == 0
    assert pool.bytes_in_use == pytest.approx(0.0, abs=1e-6)


@settings(max_examples=25, deadline=None)
@given(x=st.lists(st.floats(-50, 50), min_size=8, max_size=64))
def test_page_quant_roundtrip_bound(x):
    """Whole-page quantize→dequant error bounds, pinned: int8 error ≤
    scale/2 per element (symmetric rounding); fp8-e4m3 error ≤ 1/16
    relative (3 mantissa bits) plus the scale floor. Requantizing a
    page's own dequantized values with its scale as the floor reproduces
    the stored codes exactly (the monotone-scale append invariant)."""
    from repro.models.attention import page_dequant, page_quant
    arr = np.zeros((max(len(x) // 8, 1) * 8,), np.float32)
    arr[: len(x)] = np.asarray(x[: arr.size], np.float32)
    page = jnp.asarray(arr.reshape(1, 2, -1, 4))      # [1, K=2, pt, D=4]
    q, s = page_quant(page, jnp.int8)
    err = np.abs(np.asarray(page_dequant(q, s) - page))
    per_head = np.asarray(s)[..., None, None]
    assert (err <= per_head * 0.51 + 1e-6).all()
    q2, s2 = page_quant(page_dequant(q, s), jnp.int8, scale_floor=s)
    assert np.array_equal(np.asarray(q), np.asarray(q2))
    fp8 = getattr(jnp, "float8_e4m3fn", None)
    if fp8 is not None:
        q8, s8 = page_quant(page, fp8)
        err8 = np.abs(np.asarray(page_dequant(q8, s8) - page))
        bound = (np.abs(np.asarray(page)) * 0.0625
                 + np.asarray(s8)[..., None, None] + 1e-6)
        assert (err8 <= bound).all()


@settings(max_examples=20, deadline=None)
@given(x=st.lists(st.floats(-50, 50), min_size=4, max_size=64))
def test_int8_kv_quant_roundtrip(x):
    """Quantize→dequantize error bounded by scale/2 per element."""
    from repro.models.attention import kv_quant
    arr = jnp.asarray(np.asarray(x, np.float32).reshape(1, -1))
    q, scale = kv_quant(arr)
    deq = q.astype(jnp.float32) * scale
    err = np.abs(np.asarray(deq - arr))
    assert err.max() <= float(scale.max()) * 0.51 + 1e-6


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_kv_pool_spill_restore_interleave_conserves(data):
    """Spill/restore (DESIGN.md §11) interleaved with alloc/extend/free on
    an int8 physical pool: pages and scale rows are conserved after EVERY
    op, a spill releases exactly its reservation, ``can_restore`` is an
    accurate oracle (True ⇒ restore succeeds, token-kind False ⇒ restore
    raises PoolExhausted), and draining live + spilled ends with the full
    free list — no page can leak through any preempt/resume/cancel
    interleaving."""
    n_pages = data.draw(st.integers(2, 10), label="n_pages")
    pt = data.draw(st.integers(1, 4), label="tokens_per_page")
    K, D, layers = 2, 4, 2
    page_bytes = 2 * layers * pt * K * D * 1 + 2 * layers * K * 4
    pool = KVPool(n_pages * page_bytes, page_bytes=page_bytes,
                  tokens_per_page=pt)
    pool.allocate_physical(n_layers=layers, n_kv_heads=K, head_dim=D,
                           dtype=jnp.float32, kv_dtype="int8")
    sshape = (layers, pool.n_pages + 1, K)
    model_tok = 2 * K * D * 4 * layers
    seen = [0]
    rids = [f"s{i}" for i in range(4)]
    for step in range(data.draw(st.integers(1, 22), label="n_ops")):
        rid = data.draw(st.sampled_from(rids), label=f"rid{step}")
        if rid in pool._tok:
            op = data.draw(st.sampled_from(["extend", "spill", "free"]),
                           label=f"op{step}")
            st_alloc = pool._tok[rid]
            if op == "extend" and st_alloc.seq_tokens < st_alloc.max_tokens:
                pool.extend(rid, 1)
            elif op == "spill":
                before = pool.bytes_reserved
                released = pool.spill(rid)
                # a spill releases exactly the reservation it held
                assert released == pytest.approx(st_alloc.reserved_bytes)
                assert pool.bytes_reserved == pytest.approx(
                    before - released)
                assert rid in pool.spilled_requests()
            else:
                pool.free(rid)
        elif rid in pool._spilled:
            op = data.draw(st.sampled_from(["restore", "drop"]),
                           label=f"op{step}")
            if op == "drop":
                assert pool.drop_spilled(rid) is True
                assert pool.drop_spilled(rid, missing_ok=True) is False
            elif pool.can_restore(rid):
                rows = pool.restore(rid)
                assert rid in pool._tok and rows is not None
            else:
                with pytest.raises(PoolExhausted):
                    pool.restore(rid)
                assert rid in pool._spilled   # still restorable later
        else:
            batch = data.draw(st.integers(1, 2), label=f"b{step}")
            n_tok = data.draw(st.integers(1, 3 * pt), label=f"n{step}")
            max_tok = data.draw(st.integers(n_tok, 4 * pt),
                                label=f"m{step}")
            rate = data.draw(st.floats(0.0, float(model_tok)),
                             label=f"rate{step}")
            try:
                pool.alloc_tokens(rid, batch, n_tok, max_tokens=max_tok,
                                  in_use_bytes=rate * n_tok * batch,
                                  in_use_per_token=rate * batch,
                                  kv_dtype="int8")
            except PoolExhausted:
                assert not pool.can_alloc_tokens(batch, max_tok)
        _pool_invariants(pool, n_pages, seen)
        assert pool.bytes_in_use <= pool.bytes_reserved + 1e-6
        # scale-row conservation across spill/restore scatter-gather
        for s in (pool.k_scales, pool.v_scales):
            assert s.shape == sshape and s.dtype == jnp.float32
            assert bool(jnp.isfinite(s).all())
    for rid in pool.live_requests():
        pool.free(rid)
    for rid in pool.spilled_requests():
        pool.drop_spilled(rid)
    assert sorted(pool._free) == list(range(n_pages))
    assert pool.committed_pages == 0
    assert pool.bytes_reserved == 0
    assert pool.bytes_in_use == pytest.approx(0.0, abs=1e-6)
    assert pool.stats()["spilled_requests"] == 0
