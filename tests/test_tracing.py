"""The serving path's own spans and counters (``repro.runtime.tracing``):
the span tree of a run, the launch_s identity, the paged kernel's walk
counts, the profiler's host plane, the ring's bound, and the per-request
delivery record."""
import glob

import jax
import numpy as np
import pytest

from repro.core import masks
from repro.core.policy import RLPolicy
from repro.runtime import (EngineConfig, EngineRequest, KVPool,
                           LocalExecutor, PagedExecutor, RAPEngine)
from repro.runtime.tracing import Recorder

PROMPTS = (13, 24, 7, 20, 16)
CANCEL_AT_TICK = 7
# spans that belong to one request and carry its id
REQUEST_SPANS = {"rap.admit", "rap.policy", "rap.prefill_chunk"}


def _serve(served, kind, on_tick=None, prompts=PROMPTS, max_new=12):
    model, params, batch, mm, c = served
    toks = np.asarray(batch["tokens"])
    full = masks.full_mask(model.cfg.n_layers)
    budget = mm.param_bytes(full) + 4 * mm.state_bytes(full, 1, 48)
    ex = (PagedExecutor if kind == "paged" else LocalExecutor)(
        model, params, max_active=4)
    eng = RAPEngine(model, params, RLPolicy(c), EngineConfig(
        mode="masked", max_new_tokens=max_new, max_active=4, max_len=48,
        budget_bytes=budget, tokens_per_page=8, decode_horizon=4,
        max_prefill_tokens=8), executor=ex)
    reqs = [EngineRequest(rid=f"r{i}", prompt=toks[:1, :n],
                          arrival_t=0.001 * i)
            for i, n in enumerate(prompts)]
    launch_s0 = ex.launch_s
    rep = eng.run(reqs, on_tick=on_tick)
    return eng, rep, ex.launch_s - launch_s0


@pytest.fixture(scope="module")
def runs(served):
    """One served run per executor kind; the paged one cancels ``r1``
    mid-decode and counts its ticks."""
    out = {}

    def get(kind):
        if kind not in out:
            ticks = []

            def on_tick(engine):
                ticks.append(engine._now())
                if len(ticks) == CANCEL_AT_TICK and kind == "paged":
                    engine.cancel("r1")
            out[kind] = _serve(served, kind, on_tick) + (ticks,)
        return out[kind]
    return get


def test_span_tree_is_well_formed(runs):
    eng, rep, _, ticks = runs("paged")
    tr = rep.trace
    assert tr is eng.trace and not tr._stack           # every span closed
    spans = {s.id: s for s in tr.spans}
    assert len(spans) == len(tr.spans) == sum(n for n, _ in
                                              tr.span_totals.values())
    for s in spans.values():
        assert s.name.startswith("rap.") and s.end >= s.start
        if s.parent == -1:
            assert s.name == "rap.tick"
            continue
        p = spans[s.parent]
        assert p.start <= s.start and s.end <= p.end, (p, s)
        if p.rid is not None:
            assert s.rid == p.rid
    names = [s.name for s in spans.values()]
    assert names.count("rap.tick") == len(ticks)
    assert names.count("rap.on_tick") == len(ticks)
    # one read-back per decode launch, inside the tick's fold-back
    n_launch = names.count("rap.decode_launch")
    assert n_launch > 0 and names.count("rap.readback") == n_launch
    assert len(tr.launches) == n_launch
    for s in spans.values():
        if s.name == "rap.readback":
            assert spans[s.parent].name == "rap.foldback"
        if s.name in ("rap.page_grant", "rap.dispatch") and \
                spans[s.parent].name == "rap.decode_launch":
            assert spans[s.parent].attrs["width"] >= 1
    # each request's spans carry its id; its chunks cover its prompt
    rids = {f"r{i}" for i in range(len(PROMPTS))}
    for s in spans.values():
        if s.name in REQUEST_SPANS:
            assert s.rid in rids
        if s.name == "rap.policy":
            assert s.attrs["cached"] in (0, 1)
    for i, n in enumerate(PROMPTS):
        rid = f"r{i}"
        assert any(s.name == "rap.admit" and s.rid == rid
                   for s in spans.values())
        chunks = [c for c in tr.chunks if c.rid == rid]
        assert sum(c.tokens for c in chunks) == n
        assert [c.start for c in chunks] == list(
            np.cumsum([0] + [c.tokens for c in chunks])[:-1])


@pytest.mark.parametrize("kind", ["local", "paged"])
def test_dispatch_and_readback_add_up_to_launch_s(runs, kind):
    _, rep, launch_s, _ = runs(kind)
    timed = sum(s.end - s.start for s in rep.trace.spans
                if s.name in ("rap.dispatch", "rap.readback"))
    assert launch_s > 0 and rep.launch_s == pytest.approx(launch_s)
    assert timed == pytest.approx(launch_s, rel=0.01)


def _walk_record(tiny_model, max_len, prompts, horizon=4):
    """Seat one request per prompt length in a four-slot paged group
    (8-token pages), step the group one horizon at full width, and
    return the launch's record."""
    model, params, _ = tiny_model
    full = masks.full_mask(model.cfg.n_layers)
    toks = np.random.default_rng(0).integers(
        0, model.cfg.vocab_size, (1, max(prompts)), dtype=np.int32)
    ex = PagedExecutor(model, params, max_active=4, decode_buckets=())
    pt = 8
    page_bytes = ex.page_phys_bytes(pt)
    pool = KVPool(160 * page_bytes, page_bytes=page_bytes,
                  tokens_per_page=pt)
    ex.bind_pool(pool, max_len=max_len)
    group = ex.group_for(full, 0)
    for slot, n in enumerate(prompts):
        pool.alloc_tokens(f"r{slot}", 1, n, max_tokens=n + 16)
        ex.prefill_into(group, [slot], f"r{slot}", toks[:, :n], full)
    ex.decode_finish(ex.decode_launch(group, horizon))
    (rec,) = ex.tracer.launches
    assert ex.tracer.counter_totals["launch.pages_walked"] == rec.pages_walked
    # seated rows advance; free rows stay at position 0 on the device
    assert np.asarray(group.pos_dev).tolist() == (
        [n + horizon for n in prompts] + [0] * (4 - len(prompts)))
    for slot in range(len(prompts)):
        pool.free(f"r{slot}")
    return rec


def test_page_walk_counts_match_a_hand_count(tiny_model):
    """Two rows of 5 and 20 tokens and two free rows in a four-slot group
    stepped at full width, 8-token pages, a 64-token table. The tiny
    model's 2 KB pages (4 kv heads × 8 tokens × 16 dims, f32) make a
    block of 32 pages, capped at the table's 8: after a 4-token horizon
    each row — 9, 24, 4 and 4 tokens — copies one block of 8 pages, and
    the two requests hold ⌈9/8⌉ + ⌈24/8⌉ pages."""
    rec = _walk_record(tiny_model, 64, (5, 20))
    assert (rec.horizon, rec.rows_stepped, rec.rows_occupied) == (4, 4, 2)
    assert rec.pages_walked == 8 + 8 + 8 + 8
    assert rec.pages_with_tokens == 2 + 3


def test_page_walk_counts_a_long_row_beside_short_ones(tiny_model):
    """A 600-token row beside rows of 5 and 20 tokens and one free row,
    in a 1024-token table (128 pages, four blocks of 32): after a 4-token
    horizon the long row's 604 tokens fill 76 pages and copy three
    blocks; each other row copies one."""
    rec = _walk_record(tiny_model, 1024, (600, 5, 20))
    assert (rec.rows_stepped, rec.rows_occupied) == (4, 3)
    assert rec.pages_walked == 96 + 32 + 32 + 32
    assert rec.pages_with_tokens == 76 + 2 + 3


def test_spans_reach_the_profiler_host_plane(served, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        _, rep, _ = _serve(served, "paged", prompts=(9, 6), max_new=5)
    assert all(r.status == "done" for r in rep.results)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert {"rap.tick", "rap.readback", "rap.dispatch",
            "rap.prefill_chunk"} <= host


def test_ring_keeps_bound_and_totals_when_it_wraps():
    tr = Recorder(capacity=4)
    took = []
    for i in range(10):
        with tr.span("rap.tick", rid=f"r{i}", n=i) as sp:
            pass
        took.append(sp.seconds)
        tr.launch(t=sp.end, horizon=8, rows_stepped=4, rows_occupied=i % 3,
                  pages_walked=10, pages_with_tokens=i)
        tr.chunk(sp.end, f"r{i}", 0, 2)
    assert len(tr.spans) == len(tr.launches) == len(tr.chunks) == 4
    assert [s.attrs["n"] for s in tr.spans] == [6, 7, 8, 9]
    n, secs = tr.span_totals["rap.tick"]
    assert n == 10 and secs == pytest.approx(sum(took))
    tot = tr.counter_totals
    assert tot["launch"] == 10 and tot["launch.pages_walked"] == 100
    assert tot["launch.pages_with_tokens"] == sum(range(10))
    assert tot["chunk"] == 10 and tot["chunk.tokens"] == 20


def test_deliveries_add_up_to_tokens(runs):
    _, rep, _, _ = runs("paged")
    by_status = {}
    for r in rep.results:
        by_status.setdefault(r.status, []).append(r)
        if r.tokens is None:
            assert r.deliveries == []
            continue
        assert sum(n for _, n in r.deliveries) == r.tokens.shape[1]
        assert r.deliveries[0][0] - r.arrival_t == r.ttft_s
    (cancelled,) = by_status["cancelled"]
    assert cancelled.rid == "r1" and cancelled.tokens is not None
    assert len(by_status["done"]) == len(PROMPTS) - 1


def test_jitted_programs_have_stable_names(runs):
    eng, _, _, _ = runs("paged")
    ex = eng.executor
    fns = list(ex._prefill_fns.values()) + list(ex._hfns.values())
    assert fns and all(f.__name__.startswith("rap_paged_") for f in fns)
