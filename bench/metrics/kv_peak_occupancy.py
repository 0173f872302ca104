"""KV pool: peak bytes in use over the pool's capacity during the measured
run (``KVPool.stats()``: ``peak_in_use_bytes / capacity_bytes``)."""


def compute(ctx):
    pool = ctx.report.pool
    cap = pool.get("capacity_bytes", 0.0)
    return pool["peak_in_use_bytes"] / cap if cap > 0 else None
