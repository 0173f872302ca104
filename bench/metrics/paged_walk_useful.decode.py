"""Kernels: the share of the pages the paged decode kernel's grid walks
that hold the stepped rows' tokens, Σ ``pages_with_tokens`` ÷ Σ
``pages_walked`` over the decode launches dispatched in the window
(``repro.runtime.tracing.Launch``)."""
from bench.metrics._program_trace import records


def compute(ctx):
    rec = records(ctx)
    if rec is None:
        return None
    tr, lo, hi = rec
    walked = useful = 0
    for launch in tr.launches:
        if lo <= launch.t <= hi:
            walked += launch.pages_walked
            useful += launch.pages_with_tokens
    return useful / walked if walked else None
