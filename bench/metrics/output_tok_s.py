"""Output tokens delivered inside the window over the window's length on
the engine clock."""


def compute(ctx):
    span = ctx.marks["close"]["t"] - ctx.marks["open"]["t"]
    return ctx.delivered("open", "close") / span
