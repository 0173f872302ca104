"""The program's own records of the measured run (``EngineReport.trace``,
``repro.runtime.tracing``), clipped to the window. Span and launch times
are ``time.perf_counter`` readings, the clock of the window's ``wall``
marks. A program without the records gives nothing."""


def records(ctx):
    """(recorder, window start, window end), or None."""
    tr = getattr(ctx.report, "trace", None)
    if tr is None:
        return None
    return tr, ctx.marks["open"]["wall"], ctx.marks["close"]["wall"]
