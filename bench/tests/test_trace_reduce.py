"""The trace reduction, on a small trace recorded on a TPU v5e: three
steps of a paged-decode call and a matmul, with host spans between."""
import os

import pytest

from bench import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "paged_decode_v5e.xplane.pb")


@pytest.fixture(scope="module")
def pd():
    return trace_reduce.load(TRACE)


def test_busy_window_and_kernel_time(pd):
    r = trace_reduce.reduce(pd, kernels=["rap_paged_decode_attention"])
    assert r["window_s"] == pytest.approx(0.295903086)
    # the union of device ops: the kernel dominates a few µs of others
    assert 0.0 < r["busy_s"] < r["window_s"]
    k = r["kernel_s"]["rap_paged_decode_attention"]
    assert 0.5 * r["busy_s"] < k <= r["busy_s"]
    assert r["device_ops"][0][0].startswith("rap_paged_decode_attention")
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)


def test_idle_gaps_are_named_by_host_spans(pd):
    r = trace_reduce.reduce(pd, kernels=[])
    names = {n for n, _ in r["idle_gaps"]}
    assert "bench.host_gap" in names
    gaps = [t for _, t in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-9


def test_op_names():
    assert trace_reduce.op_name(
        "%rap_paged_decode_attention.1 = bf16[4,2,16,128]{3,2,1,0} "
        "custom-call(s32[4])") == "rap_paged_decode_attention.1"
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
