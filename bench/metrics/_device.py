"""Device readings of the traced window shared by the per-layer readers:
model FLOPs against the peak, the paged decode kernel against its
roofline, and the device's idle share. The work is what the tokens
delivered and the prompt tokens prefilled between the marks taken just
after the profiler started and just before it stopped need, by the counts
of the configuration's family module; the times are the trace's own: its
window, its busy time and the kernel's summed op time."""
KERNEL = "rap_paged_decode_attention"
MARKS = ("trace_open", "trace_close")


def mfu(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    dec, ctx_dec, pre, ctx_pre = ctx.work(*MARKS)
    cfg, fam = ctx.config, ctx.cell.family
    work = ((dec + pre) * fam.matmul_flops_per_token(cfg)
            + fam.attn_flops(cfg, ctx_dec + ctx_pre))
    if work <= 0:
        return None
    return 100.0 * work / (ctx.trace["window_s"]
                           * ctx.peaks["bf16_flops_per_s"])


def paged_attn_roofline(ctx):
    """(share %, the bound that sets the least time)."""
    if ctx.trace is None:
        return None
    kt = ctx.trace["kernel_s"].get(KERNEL, 0.0)
    dec, ctx_dec, _, _ = ctx.work(*MARKS)
    if kt <= 0 or dec <= 0:
        return None
    cfg, fam = ctx.config, ctx.cell.family
    need_bytes = (fam.decode_attn_bytes(cfg, 0.0) * dec
                  + fam.kv_bytes_per_ctx_token(cfg) * ctx_dec)
    need_flops = fam.attn_flops(cfg, ctx_dec)
    t_bytes = need_bytes / ctx.peaks["hbm_bytes_per_s"]
    t_flops = need_flops / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * max(t_bytes, t_flops) / kt


def idle_share(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]
