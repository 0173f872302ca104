"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` alone. On a TPU the device plane is
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed HLO
operation, named ``%<op name> = <shape> <opcode>(...)``. A Pallas kernel's
op name is the kernel's ``name`` (``%rap_paged_decode_attention.3 = ...``).
Host spans (``jax.profiler.TraceAnnotation``) sit on the ``/host:CPU``
plane on the same clock.

* busy: the union of the device's op intervals inside the window;
* kernel time: the summed durations of the ops whose name starts with a
  given kernel name;
* idle gaps: the stretches between busy intervals, each named by the host
  span that covers most of it (``"(no span)"`` where none does).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
# ops that hold other ops (a scan's while loop): counted in busy time,
# left out of the per-op list so their body is not listed twice
CONTAINERS = ("while", "conditional", "call")


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` → ``fusion.3``."""
    name = event_name.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_ops(pd, device_prefix: str = "/device:TPU:") -> List[
        List[Tuple[str, float, float]]]:
    """Per device plane: (op name, start_ns, end_ns) of every XLA op."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            continue
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = float(ev.start_ns)
                ops.append((op_name(ev.name), s, s + float(ev.duration_ns)))
        out.append(ops)
    return out


def host_spans(pd, names: Optional[Sequence[str]] = None) -> List[
        Tuple[str, float, float]]:
    """Host events (name, start_ns, end_ns), optionally only those whose
    name starts with one of ``names``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if names is None or any(ev.name.startswith(n)
                                        for n in names):
                    s = float(ev.start_ns)
                    out.append((ev.name, s, s + float(ev.duration_ns)))
    return out


def profile_window(pd) -> Optional[Interval]:
    """(0, stop - start) in the events' clock: the profiler's own window,
    from the ``Task Environment`` plane, where the trace records it."""
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = {k: v for k, v in plane.stats}
            if "profile_start_time" in st and "profile_stop_time" in st:
                return 0.0, float(int(st["profile_stop_time"])
                                  - int(st["profile_start_time"]))
    return None


def _clip(ops, lo: float, hi: float):
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def reduce(pd, window: Optional[Interval] = None,
           kernels: Sequence[str] = (), span_prefixes: Sequence[str] = (
               "bench.",), top: int = 10) -> Dict:
    """Busy seconds (mean over device planes), window seconds, per-kernel
    device seconds (summed over planes), the ``top`` ops by device time and
    the ``top`` longest idle gaps named by host span (first plane)."""
    planes = device_ops(pd)
    if not planes or not any(planes):
        raise ValueError("the trace holds no device operations")
    if window is None:
        window = profile_window(pd)
    if window is None:
        lo = min(s for ops in planes for _, s, _ in ops)
        hi = max(e for ops in planes for _, _, e in ops)
    else:
        lo, hi = window
    busy, per_op = [], {}
    kern = {k: 0.0 for k in kernels}
    gaps: List[Interval] = []
    for i, ops in enumerate(planes):
        clipped = list(_clip(ops, lo, hi))
        merged = _union((s, e) for _, s, e in clipped)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in clipped:
            if not name.startswith(CONTAINERS):
                per_op[name] = per_op.get(name, 0.0) + (e - s)
            for k in kernels:
                if name == k or name.startswith(k + "."):
                    kern[k] += e - s
        if i == 0:
            prev = lo
            for s, e in merged:
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
            if hi > prev:
                gaps.append((prev, hi))
    spans = host_spans(pd, span_prefixes)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "(no span)", 0.0
        for name, hs, he in spans:
            c = min(e, he) - max(s, hs)
            # the innermost span that covers most of the gap names it
            if c > cover or (c == cover and c > 0 and he - hs < best_len):
                best, cover, best_len = name, c, he - hs
        if cover < 0.5 * (e - s):
            best = "(no span)"
        named.append([best, (e - s) / 1e9])
    by_time = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kern.items()},
        "device_ops": [[n, t / 1e9] for n, t in by_time],
        "idle_gaps": named,
    }
