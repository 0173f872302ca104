"""Engine tick loop: host milliseconds per delivered token in the window,
the wall time outside compiled-executable launches and read-backs
(``executor.launch_s``) over the tokens delivered."""


def host_ms_per_tok(ctx):
    o, c = ctx.marks["open"], ctx.marks["close"]
    n = ctx.delivered("open", "close")
    if n <= 0:
        return None
    host = (c["wall"] - o["wall"]) - (c["launch_s"] - o["launch_s"])
    return host / n * 1e3
