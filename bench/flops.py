"""Context sums of the window's work, for every family.

A family module (``bench/families/<family>.py``) counts the operations and
bytes one token needs at a given context from its configuration file;
these turn the tokens a request was served between two marks into the
summed context those counts take.
"""
from __future__ import annotations


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
