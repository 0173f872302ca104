"""Device: 1 − (union of device-op intervals) / traced window."""
from bench.metrics._device import idle_share


def compute(ctx):
    return idle_share(ctx)
