"""The one place the benchmark reads the engine's run state.

``RAPEngine.run(..., on_tick=...)`` calls back once per tick with the
engine, while that tick's decode horizon is in flight and before arrivals
and admission. The engine reports per-request times only once a request
ends, so the window's token count, its clock and the prefill progress are
read here from the engine's run state. Everything else the benchmark needs
comes from the public ``RequestResult`` / ``EngineReport`` records and the
executor's ``launch_s`` counter.
"""
from __future__ import annotations

from typing import Dict, Tuple


def now(engine) -> float:
    """The engine clock (seconds since run start; idle gaps skipped)."""
    return engine._now()


def counts(engine) -> Tuple[int, int]:
    """(requests decoding, requests in chunked prefill)."""
    return len(engine._running), len(engine._prefilling)


def progress(engine) -> Dict[str, Tuple[int, int]]:
    """Per request: (prompt tokens prefilled, tokens delivered) so far."""
    out: Dict[str, Tuple[int, int]] = {}
    for r in engine._results:
        if r.tokens is not None:
            out[r.rid] = (-1, int(r.tokens.shape[1]))
    for rid, run in engine._running.items():
        out[rid] = (-1, len(run.out))
    for rid, pf in engine._prefilling.items():
        out[rid] = (int(pf.task.pos), 0)
    return out


def finished(engine) -> set:
    """Ids of requests that have ended (done, cancelled or rejected)."""
    return {r.rid for r in engine._results}
