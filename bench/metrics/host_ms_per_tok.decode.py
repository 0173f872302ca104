"""Engine tick loop: host ms per delivered token in the window (see
``_host_ms_per_tok.py``)."""
from bench.metrics._host_ms_per_tok import host_ms_per_tok


def compute(ctx):
    return host_ms_per_tok(ctx)
