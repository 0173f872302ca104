"""Process start to window start: weights, policy, compile-cache loads,
the warm-up trace, and the cell's lead-in or fill (host clock)."""


def compute(ctx):
    return ctx.setup_s
