"""The benchmark's traffic generator: one general reader of the mix files in
``bench/traffic/``.

A mix file sets the arrivals, the length distributions and what an
operator sets on the engine. The generator turns it and a seed into a list
of requests. Steadiness rule: every seed gets the same multiset of prompt
lengths, output lengths and inter-arrival gaps, block by block, in another
order. Each block of ``block`` requests takes its lengths at the stratified
quantiles ``(i + 0.5) / block`` of the stated distribution, then the seed
permutes them. So two seeds do the same amount of work, and only the order
and the token ids differ.

Arrival processes:

* ``backlog``: every request is waiting at time 0 (a closed loop whose
  clients always have the next request ready). With ``in_flight: n`` the
  first ``n`` requests stand for the loop's steady state at the start:
  each is partway through its output. At a random instant a slot holds a
  request picked in proportion to its output length, at a uniform point of
  it, so the ``n`` take their output lengths at the stratified quantiles
  of the length-biased output distribution and their progress at the
  stratified quantiles of (0, 1), paired in one fixed order. The tokens
  already produced stand in the prompt (random ids) and ``max_new`` is
  what remains. Every seed gets the same (prompt total, output, progress)
  multiset, so the same contexts and commitments;
* ``poisson``: open loop, exponential gaps at ``rate_per_s``, the gaps
  stratified per block like the lengths.

This file is the benchmark's own copy of the arithmetic; the program's
generators (``repro.core.workload``, ``repro.runtime.scenarios``) are not
imported, so a change to them cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_STD_NORMAL = NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    rid: str
    arrival_s: float          # scheduled arrival on the engine clock
    prompt: np.ndarray        # int32 [1, prompt_tokens]
    max_new: int              # tokens generated, the prefill's first included


def quantile(dist: Dict, q: float) -> float:
    """Inverse CDF of a length distribution spec at ``q`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _STD_NORMAL.inv_cdf(q))
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", x), dist.get("max", x)
    return float(min(max(x, lo), hi))


def stratified(dist: Dict, n: int, block: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths: per block, the stratified quantiles of
    ``dist`` in an order drawn from ``rng``."""
    out: List[int] = []
    while len(out) < n:
        qs = (np.arange(block) + 0.5) / block
        vals = np.asarray([round(quantile(dist, q)) for q in qs], np.int64)
        out.extend(rng.permutation(vals).tolist())
    return np.asarray(out[:n], np.int64)


def length_biased(dist: Dict, n: int, grid: int = 4096) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of ``dist`` weighted by
    length (the lengths an instant finds in flight), ascending."""
    vals = np.asarray([quantile(dist, (i + 0.5) / grid)
                       for i in range(grid)])
    cdf = np.cumsum(vals) / vals.sum()
    qs = (np.arange(n) + 0.5) / n
    idx = np.minimum(np.searchsorted(cdf, qs), grid - 1)
    return np.round(vals[idx]).astype(np.int64)


def in_flight(mix: Dict, n: int, rng: np.random.Generator):
    """(prompt lengths, output lengths, tokens already produced) of the
    ``n`` requests in flight at the start of a closed loop."""
    outputs = length_biased(mix["output_tokens"], n)
    # progress at (i + 0.5) / n, paired with the outputs in an order fixed
    # for every seed, so the tokens produced sum the same
    progress = (np.random.default_rng(0).permutation(n) + 0.5) / n
    done = np.floor(progress * outputs).astype(np.int64)
    prompts = stratified(mix["prompt_tokens"], n, n, rng)
    order = rng.permutation(n)
    return prompts, outputs[order], done[order]


def stratified_gaps(rate: float, n: int, block: int,
                    rng: np.random.Generator) -> np.ndarray:
    """Exponential inter-arrival gaps at ``rate``, stratified per block."""
    out: List[float] = []
    while len(out) < n:
        qs = (np.arange(block) + 0.5) / block
        vals = -np.log1p(-qs) / rate
        out.extend(rng.permutation(vals).tolist())
    return np.asarray(out[:n], np.float64)


def arrival_horizon_s(mix: Dict, seconds: float) -> float:
    """How long arrivals continue on the engine clock: the lead-in, the
    window and the drain (a poisson mix keeps its load on while the
    window's last requests finish)."""
    arr = mix["arrivals"]
    return (arr.get("lead_in_s", 0.0) + seconds
            + arr.get("drain_cap_s", 0.0))


def generate(mix: Dict, seed: int, seconds: float,
             vocab_size: int) -> List[Request]:
    """The requests of one run of ``mix`` under ``seed``."""
    arr = mix["arrivals"]
    block = int(mix.get("block", 64))
    rng = np.random.default_rng([seed, 0x7A11C])
    n_live = 0
    if arr["process"] == "backlog":
        n = int(arr["requests"])
        n_live = int(arr.get("in_flight", 0))
        times = np.zeros(n)
    elif arr["process"] == "poisson":
        horizon = arrival_horizon_s(mix, seconds)
        rate = float(arr["rate_per_s"])
        # enough gaps to pass the horizon, whole blocks so the multiset
        # of gaps is the same for every seed
        n_blocks = int(math.ceil(horizon * rate / block)) + 1
        gaps = stratified_gaps(rate, n_blocks * block, block, rng)
        times = np.cumsum(gaps)
        times = times[times < horizon]
        n = len(times)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    p_live, o_live, done = in_flight(mix, n_live, rng)
    prompts = np.concatenate([p_live + done, stratified(
        mix["prompt_tokens"], n - n_live, block, rng)])
    outputs = np.concatenate([o_live - done, stratified(
        mix["output_tokens"], n - n_live, block, rng)])
    reqs = []
    for i in range(n):
        ids = rng.integers(0, vocab_size, size=(1, int(prompts[i])),
                           dtype=np.int32)
        reqs.append(Request(rid=f"r{i:05d}", arrival_s=float(times[i]),
                            prompt=ids, max_new=int(outputs[i])))
    return reqs


def in_flight_rids(mix: Dict):
    """Ids of the requests that stand for a closed loop's steady state."""
    n = int(mix["arrivals"].get("in_flight", 0))
    return [f"r{i:05d}" for i in range(n)]


def warmup_requests(engine_cfg: Dict, decode_buckets, decode_horizon: int,
                    vocab_size: int) -> List[Request]:
    """A warm-up trace that runs every executable shape the cell's window
    can use: each pow2 chunk width up to the prefill cap (one prompt of
    ``2 * cap - 1`` tokens), and each decode width (the program's decode
    buckets and the full slot count) at each pow2 horizon the program's
    clamp produces. Waves are spaced far apart on the engine clock, which
    skips idle gaps, so each wave runs alone."""
    cap = int(engine_cfg["max_prefill_tokens"])
    slots = int(engine_cfg["slots"])
    cap = 1 << (cap.bit_length() - 1)
    rng = np.random.default_rng(0)
    reqs: List[Request] = []
    t = 0.0

    def add(prompt_len: int, max_new: int):
        ids = rng.integers(0, vocab_size, size=(1, prompt_len),
                           dtype=np.int32)
        reqs.append(Request(rid=f"w{len(reqs):04d}", arrival_s=t,
                            prompt=ids, max_new=max_new))

    add(2 * cap - 1, 1)
    widths = sorted({int(b) for b in decode_buckets if int(b) < slots}
                    | {slots})
    horizons = [1 << k for k in range(int(decode_horizon).bit_length())
                if (1 << k) <= decode_horizon]
    for w in widths:
        for h in horizons:
            t += 1000.0
            for _ in range(w):
                # remaining need h after the prefill's token: the clamp
                # picks horizon h
                add(16, 1 + h)
    return reqs
