"""Metric arithmetic on hand-made windows: window bounds, the work counts
and the device readers' shares."""
import types

import pytest

from bench import flops, spec


def _ctx(marks, prompt_len, cfg_name="glm4-9b-l20"):
    from bench.run import Context, _request_work, delivered
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/{cfg_name}.json")
    ctx = Context(marks=marks, prompt_len=prompt_len, config=cfg,
                  cell=types.SimpleNamespace(family=spec.family_module(cfg)))
    ctx.delivered = lambda a, b: delivered(ctx, a, b)
    ctx.work = lambda a, b: _request_work(ctx, a, b)
    return ctx


def test_output_rate_and_host_share_over_the_window():
    marks = {
        "open": {"t": 10.0, "wall": 100.0, "launch_s": 5.0,
                 "progress": {"a": (-1, 4), "b": (8, 0)}},
        "close": {"t": 30.0, "wall": 120.0, "launch_s": 23.0,
                  "progress": {"a": (-1, 44), "b": (-1, 21),
                               "c": (-1, 1)}}}
    ctx = _ctx(marks, {"a": 100, "b": 16, "c": 7})
    assert ctx.delivered("open", "close") == 40 + 21 + 1
    assert spec.metric_module("output_tok_s").compute(ctx) == \
        pytest.approx(62 / 20.0)
    assert spec.metric_module("host_ms_per_tok.decode").compute(ctx) == \
        pytest.approx((20.0 - 18.0) / 62 * 1e3)
    dec, ctx_dec, pre, ctx_pre = ctx.work("open", "close")
    assert dec == 40 + 20            # first tokens come from prefill
    assert pre == 8 + 7              # b's remaining prompt, all of c's
    brute = sum(100 + i for i in range(4, 44)) + sum(16 + i
                                                     for i in range(1, 21))
    assert ctx_dec == pytest.approx(brute)
    assert ctx_pre == pytest.approx(sum(p + 1 for p in range(8, 16))
                                    + sum(p + 1 for p in range(7)))


def test_flop_counts_match_the_published_sizes():
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/glm4-9b-l20.json")
    fam = spec.family_module(cfg)
    # 20 layers of q/k/v/o and the GLU FFN, and the untied head
    assert fam.layer_params(cfg) == 4096 * (4096 + 2 * 256) + 4096 * \
        4096 + 3 * 4096 * 13696
    assert fam.matmul_flops_per_token(cfg) == pytest.approx(
        2 * (20 * fam.layer_params(cfg) + 4096 * 151552))
    # one token at ctx 1000: K and V of 1000 tokens, 2 kv heads, 20 layers
    assert fam.decode_attn_bytes(cfg, 1000) == pytest.approx(
        (2 * 1000 * 2 * 128 * 2 + 2 * 32 * 128 * 2) * 20)
    assert fam.attn_flops(cfg, 1000) == 4 * 1000 * 32 * 128 * 20
    for p, a, b in ((100, 0, 50), (7, 3, 4), (5, 2, 2)):
        assert flops.sum_ctx(p, a, b) == sum(p + i
                                             for i in range(max(a, 1), b))


def test_device_readers_return_nothing_without_a_trace():
    ctx = types.SimpleNamespace(trace=None)
    for name in ("mfu.decode", "paged_attn_roofline.decode",
                 "device_idle_share.decode"):
        assert spec.metric_module(name).compute(ctx) is None


def test_device_shares_from_a_reduced_trace():
    """Work between the trace's own marks, times from the trace: the
    kernel's summed time for the roofline, the trace's window for mfu."""
    marks = {"open": {"wall": 0.0, "progress": {"a": (-1, 1)}},
             "trace_open": {"wall": 0.1, "progress": {"a": (-1, 1)}},
             "trace_close": {"wall": 1.9, "progress": {"a": (-1, 11)}},
             "close": {"wall": 2.0, "progress": {"a": (-1, 12)}}}
    ctx = _ctx(marks, {"a": 990})
    ctx.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx.trace = {"kernel_s": {"rap_paged_decode_attention": 0.5},
                 "busy_s": 1.5, "window_s": 2.0}
    fam = ctx.cell.family
    need = sum(fam.decode_attn_bytes(ctx.config, 990 + i)
               for i in range(1, 11))
    share = spec.metric_module("paged_attn_roofline.decode").compute(ctx)
    assert share == pytest.approx(100 * need / 819e9 / 0.5)
    assert spec.metric_module("device_idle_share.decode").compute(ctx) == \
        pytest.approx(0.25)
    work = sum(fam.matmul_flops_per_token(ctx.config)
               + fam.attn_flops(ctx.config, 990 + i) for i in range(1, 11))
    assert spec.metric_module("mfu.decode").compute(ctx) == \
        pytest.approx(100 * work / (2.0 * 197e12))
