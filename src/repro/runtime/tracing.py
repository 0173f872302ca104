"""Host spans and counters of the serving path, on the profiler's clock.

One :class:`Recorder` per engine run, shared by the engine and its
executor (``EngineReport.trace``). :meth:`Recorder.span` enters a
``jax.profiler.TraceAnnotation`` — so whenever a profiler trace is
running the span lands on its host plane, on the same clock as the
device ops — and on exit appends a :class:`Span` timed on
``time.perf_counter`` to a bounded ring. Counters (:class:`Launch`,
:class:`Chunk`) are recorded at the same boundaries into rings of their
own. Per-name span totals and per-field counter sums are kept apart from
the rings, so nothing is lost when a ring wraps.

Spans are recorded per tick, per request and per prefill chunk, never per
token, which keeps the recorder cheap enough to stay on.

Span names (all ``rap.``): ``tick`` (root of each engine tick),
``budget`` (elastic budget and preemption, a ``spill`` child per victim),
``schedule``, ``decode_launch`` (per group: ``page_grant`` and
``dispatch`` children), ``on_tick``, ``resume`` (a ``restore`` child per
request), ``admit`` (per request, a ``policy`` child), ``prefill_chunk``
(a ``dispatch`` child), ``foldback`` (the tick's read-back and what
follows it: a ``readback`` child per launch). ``dispatch`` and
``readback`` wrap exactly the intervals the executor's ``launch_s`` adds
up.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Deque, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["CAPACITY", "Chunk", "Launch", "Recorder", "Span"]

CAPACITY = 65536


class Span(NamedTuple):
    id: int
    name: str
    start: float                      # time.perf_counter()
    end: float
    parent: int                       # id of the enclosing span, -1 at root
    rid: Optional[str]                # request id, inherited from the parent
    attrs: Dict[str, Any]


class Launch(NamedTuple):
    """One paged decode horizon launch. Pages are counted per pool and
    per kernel call (one layer, one step), at the horizon's last step:
    ``pages_walked`` is what the kernel copies for the stepped rows
    (``paged_decode_attention.pages_walked``), ``pages_with_tokens`` what
    the stepped, occupied rows hold."""
    t: float                          # end of the launch's dispatch span
    horizon: int
    rows_stepped: int
    rows_occupied: int
    pages_walked: int
    pages_with_tokens: int


class Chunk(NamedTuple):
    """One prefill chunk (a monolithic prefill is one chunk)."""
    t: float                          # end of the chunk's span
    rid: str
    start: int                        # prompt tokens before the chunk
    tokens: int


class _Open:
    """A span being recorded (what ``with recorder.span(...)`` yields)."""
    __slots__ = ("_rec", "_ann", "id", "name", "rid", "attrs", "parent",
                 "start", "end")

    def __init__(self, rec: "Recorder", name: str, rid: Optional[str],
                 attrs: Dict[str, Any]):
        self._rec = rec
        self.name = name
        self.rid = rid
        self.attrs = attrs
        self.end = 0.0

    def __enter__(self) -> "_Open":
        rec = self._rec
        outer = rec._stack[-1] if rec._stack else None
        self.parent = outer.id if outer is not None else -1
        if self.rid is None and outer is not None:
            self.rid = outer.rid
        self.id = rec._next_id
        rec._next_id += 1
        rec._stack.append(self)
        if self.rid is None:
            self._ann = TraceAnnotation(self.name, **self.attrs)
        else:
            self._ann = TraceAnnotation(self.name, rid=self.rid,
                                        **self.attrs)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (call before it ends)."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._stack.pop()
        rec._add_span(Span(self.id, self.name, self.start, self.end,
                           self.parent, self.rid, self.attrs))

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Recorder:
    """Bounded rings of spans, launches and chunks, with running totals."""

    def __init__(self, capacity: int = CAPACITY):
        self.spans: Deque[Span] = collections.deque(maxlen=capacity)
        self.launches: Deque[Launch] = collections.deque(maxlen=capacity)
        self.chunks: Deque[Chunk] = collections.deque(maxlen=capacity)
        # span name -> [count, seconds], over every span ever recorded
        self.span_totals: Dict[str, List[float]] = {}
        # "launch" / "chunk" -> records; "<kind>.<field>" -> Σ field
        self.counter_totals: Dict[str, float] = {}
        self._stack: List[_Open] = []
        self._next_id = 0

    def span(self, name: str, rid: Optional[str] = None, **attrs) -> _Open:
        return _Open(self, name, rid, attrs)

    def _add_span(self, sp: Span) -> None:
        self.spans.append(sp)
        tot = self.span_totals.get(sp.name)
        if tot is None:
            tot = self.span_totals[sp.name] = [0, 0.0]
        tot[0] += 1
        tot[1] += sp.end - sp.start

    def _count(self, kind: str, **sums: int) -> None:
        tot = self.counter_totals
        tot[kind] = tot.get(kind, 0) + 1
        for field, v in sums.items():
            key = f"{kind}.{field}"
            tot[key] = tot.get(key, 0) + v

    def launch(self, t: float, horizon: int, rows_stepped: int,
               rows_occupied: int, pages_walked: int,
               pages_with_tokens: int) -> None:
        self.launches.append(Launch(t, horizon, rows_stepped, rows_occupied,
                                    pages_walked, pages_with_tokens))
        self._count("launch", rows_stepped=rows_stepped,
                    rows_occupied=rows_occupied, pages_walked=pages_walked,
                    pages_with_tokens=pages_with_tokens)

    def chunk(self, t: float, rid: str, start: int, tokens: int) -> None:
        self.chunks.append(Chunk(t, rid, start, tokens))
        self._count("chunk", tokens=tokens)
