"""The readers of the program's own records (``EngineReport.trace``) on
made-up records, and the public delivery record against what
``bench/probe.py`` counts from the engine's run state."""
import types

import numpy as np
import pytest

from bench import probe, spec
from repro.runtime.tracing import Launch, Recorder, Span


def _ctx(trace, lo=10.0, hi=20.0):
    return types.SimpleNamespace(
        marks={"open": {"wall": lo}, "close": {"wall": hi}},
        report=types.SimpleNamespace(trace=trace))


def _recorder(spans=(), launches=()):
    tr = Recorder()
    tr.spans.extend(Span(i, n, s, e, p, None, {})
                    for i, (n, s, e, p) in enumerate(spans))
    tr.launches.extend(launches)
    return tr


def test_host_gap_runs_from_the_last_readback_to_each_decode_dispatch():
    spans = [  # (name, start, end, parent index)
        ("rap.readback", 8.0, 9.0, -1),            # 0
        ("rap.decode_launch", 9.002, 9.006, -1),   # 1: before the window
        ("rap.dispatch", 9.004, 9.005, 1),         # 2
        ("rap.readback", 9.5, 11.0, -1),           # 3
        ("rap.decode_launch", 11.001, 11.010, -1),  # 4
        ("rap.dispatch", 11.005, 11.008, 4),       # 5: gap 8 ms
        ("rap.prefill_chunk", 11.02, 11.2, -1),    # 6
        ("rap.dispatch", 11.03, 11.2, 6),          # 7: a prefill's, not a gap
        ("rap.readback", 13.0, 14.0, -1),          # 8
        ("rap.decode_launch", 14.001, 14.005, -1),  # 9
        ("rap.dispatch", 14.002, 14.004, 9),       # 10: gap 4 ms
        ("rap.readback", 19.0, 20.5, -1),          # 11
        ("rap.decode_launch", 20.6, 20.7, -1),     # 12: after the window
        ("rap.dispatch", 20.61, 20.69, 12),        # 13
    ]
    m = spec.metric_module("host_gap_ms.decode")
    assert m.compute(_ctx(_recorder(spans))) == pytest.approx(6.0)


def test_walk_share_sums_the_window_launches():
    launches = [Launch(5.0, 8, 64, 40, 64 * 288, 9999),     # before
                Launch(12.0, 8, 64, 42, 64 * 288, 4452),
                Launch(15.0, 8, 64, 41, 64 * 288, 4300),
                Launch(25.0, 8, 64, 30, 64 * 288, 1)]       # after
    m = spec.metric_module("paged_walk_useful.decode")
    assert m.compute(_ctx(_recorder(launches=launches))) == \
        pytest.approx((4452 + 4300) / (2 * 64 * 288))


@pytest.mark.parametrize("name", ["host_gap_ms.decode",
                                  "paged_walk_useful.decode"])
def test_readers_give_nothing_without_records(name):
    m = spec.metric_module(name)
    # a program that keeps no records: its report has no trace
    no_trace = types.SimpleNamespace(
        marks={"open": {"wall": 0.0}, "close": {"wall": 1.0}},
        report=types.SimpleNamespace(pool={}))
    assert m.compute(no_trace) is None
    assert m.compute(_ctx(Recorder())) is None


def test_deliveries_match_the_probes_count_between_ticks():
    """Tokens whose delivery falls between two ``on_tick`` calls are the
    tokens ``probe.progress`` counts between them, for every pair of
    ticks, with a request cancelled mid-decode."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core import masks, memory
    from repro.core.policy import DensePolicy
    from repro.models import registry
    from repro.runtime import (EngineConfig, EngineRequest, PagedExecutor,
                               RAPEngine)

    cfg = get_smoke_config("llama2-7b").replace(n_layers=2)
    model = registry.build(cfg)
    params = model.init(jax.random.key(0))
    mm = memory.build_memory_model(cfg)
    full = masks.full_mask(cfg.n_layers)
    eng = RAPEngine(model, params, DensePolicy(mm), EngineConfig(
        mode="masked", max_new_tokens=10, max_active=3, max_len=48,
        budget_bytes=mm.param_bytes(full) + 3 * mm.state_bytes(full, 1, 48),
        tokens_per_page=8, decode_horizon=4, max_prefill_tokens=8),
        executor=PagedExecutor(model, params, max_active=3))
    rng = np.random.default_rng(0)
    reqs = [EngineRequest(rid=f"r{i}", arrival_t=0.0,
                          prompt=rng.integers(0, cfg.vocab_size, (1, n),
                                              dtype=np.int32))
            for i, n in enumerate((11, 20, 5, 17, 9))]
    ticks = []

    def on_tick(engine):
        ticks.append((probe.now(engine), probe.progress(engine)))
        if len(ticks) == 6:
            engine.cancel("r1")
    rep = eng.run(reqs, on_tick=on_tick)
    assert any(r.status == "cancelled" and r.tokens is not None
               for r in rep.results)
    assert len(ticks) > 6
    for (ta, pa), (tb, pb) in zip(ticks, ticks[1:]):
        probed = sum(tok - pa.get(rid, (0, 0))[1]
                     for rid, (_, tok) in pb.items())
        delivered = sum(n for r in rep.results for t, n in r.deliveries
                        if ta < t <= tb)
        assert delivered == probed
