"""Operations and bytes the algorithm needs, from a configuration file.

These count the work the mathematics requires, not what an implementation
happens to do: a kernel that walks pages it does not need, or steps padded
rows, spends time the count does not credit. Sizes come from the
configuration file's ``model`` block (the widths as served).
"""
from __future__ import annotations

from typing import Dict


def _m(cfg: Dict) -> Dict:
    return cfg["model"]


def layer_params(cfg: Dict) -> int:
    """Matmul parameters of one decoder layer (q/k/v/o and the GLU FFN)."""
    m = _m(cfg)
    d, h, k, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                      m["head_dim"], m["d_ff"])
    return d * (h * dh + 2 * k * dh) + h * dh * d + 3 * d * f


def head_params(cfg: Dict) -> int:
    m = _m(cfg)
    return m["d_model"] * m["vocab_padded"]


def matmul_flops_per_token(cfg: Dict) -> float:
    """2 × (layer + head parameters): every weight multiplies once."""
    return 2.0 * (_m(cfg)["n_layers"] * layer_params(cfg) + head_params(cfg))


def attn_flops(cfg: Dict, ctx: float) -> float:
    """QK and PV of one query token against ``ctx`` keys, every layer."""
    m = _m(cfg)
    return 4.0 * ctx * m["n_heads"] * m["head_dim"] * m["n_layers"]


def token_flops(cfg: Dict, ctx: float) -> float:
    """Model FLOPs of one token that attends ``ctx`` keys."""
    return matmul_flops_per_token(cfg) + attn_flops(cfg, ctx)


def decode_attn_bytes(cfg: Dict, ctx: float, kv_bytes: int = 2,
                      act_bytes: int = 2) -> float:
    """Bytes the paged decode kernel needs for one row at one step, all
    layers: the K and V of the ``ctx`` tokens it attends, q in, out."""
    m = _m(cfg)
    kv = 2.0 * ctx * m["n_kv_heads"] * m["head_dim"] * kv_bytes
    qo = 2.0 * m["n_heads"] * m["head_dim"] * act_bytes
    return (kv + qo) * m["n_layers"]


def sum_ctx(prompt: int, first: int, last: int) -> float:
    """Σ context over decode tokens ``first..last-1`` of a request: token
    ``i >= 1`` appends at position ``prompt + i - 1`` and attends
    ``prompt + i`` keys (token 0 comes from the prefill)."""
    a = max(first, 1)
    if last <= a:
        return 0.0
    n = last - a
    return float(n * prompt + (a + last - 1) * n / 2.0)


def sum_prefill_ctx(start: int, end: int) -> float:
    """Σ context over prompt positions ``start..end-1`` (causal: position
    ``p`` attends ``p + 1`` keys)."""
    if end <= start:
        return 0.0
    n = end - start
    return float((start + 1 + end) * n / 2.0)
