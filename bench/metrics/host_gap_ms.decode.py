"""Engine tick loop: the host time during which no decode is queued on
the device, in ms: from the end of the previous ``rap.readback`` span to
the end of a horizon's dispatch (the ``rap.dispatch`` span inside a
``rap.decode_launch``), mean over the horizons dispatched in the window."""
import bisect

from bench.metrics._program_trace import records


def compute(ctx):
    rec = records(ctx)
    if rec is None:
        return None
    tr, lo, hi = rec
    names = {s.id: s.name for s in tr.spans}
    readbacks = sorted(s.end for s in tr.spans if s.name == "rap.readback")
    gaps = []
    for s in tr.spans:
        if (s.name == "rap.dispatch" and lo <= s.end <= hi
                and names.get(s.parent) == "rap.decode_launch"):
            i = bisect.bisect_left(readbacks, s.end)
            if i:
                gaps.append(s.end - readbacks[i - 1])
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
