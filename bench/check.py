"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the requests the window served, drawn from the seed and always holding the
one with the most served tokens, is run through the float32 reference
of the configuration's family (``bench/families/<family>.py`` on
``bench/reference.py``), teacher forced over prompt + served tokens with
each request's own keep-mask as gates. For every served token the gap
between the reference's best logit and the served token's logit at the
position that produced it is read; the widest gap over the sample is held
to the cell's limit (``bench/limits/<cell>.json``). Greedy decoding serves
the program's best logit, so a sound run's gaps are rounding, and a wrong
page, position, mask, kernel or token shows as a gap of the logits' own
scale.
"""
from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import reference, serve

Item = Tuple[str, np.ndarray, np.ndarray, np.ndarray]


def sample(cell, seed: int, reqs, results, window_rids: List[str],
           process: str) -> List[Item]:
    """(rid, prompt, served tokens, mask) of the compared requests: in a
    closed loop the window's requests with two or more served tokens
    (finished, or cancelled at the close with what they had), in an open
    loop the window's finished requests."""
    by_rid = {r.rid: r for r in reqs}
    cands = []
    for rid in window_rids:
        r = results.get(rid)
        if r is None or r.tokens is None or r.mask is None:
            continue
        if process != "backlog" and r.status != "done":
            continue
        if r.tokens.shape[1] < 2:
            continue
        cands.append(r)
    if not cands:
        return []
    n = int(cell.mix["check"]["requests"])
    longest = max(cands, key=lambda r: (r.tokens.shape[1], r.rid))
    rest = sorted((r for r in cands if r.rid != longest.rid),
                  key=lambda r: r.rid)
    rng = np.random.default_rng([seed, 0xC4EC])
    pick = [longest] + [rest[i] for i in sorted(rng.choice(
        len(rest), size=min(n - 1, len(rest)), replace=False))]
    return [(r.rid, by_rid[r.rid].prompt[0], np.asarray(r.tokens[0]),
             np.asarray(r.mask)) for r in pick]


def readings(cell, seed: int, items: List[Item],
             control: bool = False) -> Dict[str, Any]:
    """The reference's gaps for ``items`` (weights rebuilt from the seed);
    with ``control`` those of the fp8 control put in the program's place
    (``reference.control_gaps`` through the family's ``hidden``)."""
    import jax
    m, fam = cell.config["model"], cell.family
    key = jax.random.key(serve.seed32(seed))
    w = jax.jit(lambda k: fam.init_weights(m, k))(key)
    gaps_fn = reference.control_gaps if control else reference.served_gaps
    gaps = gaps_fn(m, w, [(p, s, k) for _, p, s, k in items], fam.hidden)
    del w
    allg = np.concatenate(gaps) if gaps else np.zeros((0,))
    valid = all(int(s.min()) >= 0 and int(s.max()) < m["vocab_size"]
                for _, _, s, _ in items)
    return {"max_logit_gap": float(allg.max()) if allg.size else math.inf,
            "tokens_compared": int(allg.size),
            "requests_compared": len(items),
            "median_logit_gap": float(np.median(allg)) if allg.size else None,
            "share_gap_over_0": float((allg > 0).mean()) if allg.size
            else None,
            "valid_ids": valid,
            "pruned_blocks": int(sum(int((~k.astype(bool)).sum())
                                     for _, _, _, k in items))}


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e30


def compare(cell, seed: int, items: List[Item],
            control: bool = False) -> Dict[str, Any]:
    r = readings(cell, seed, items, control)
    lim = cell.limits
    gap_limit = float(lim["max_logit_gap"]["limit"])
    min_tokens = int(lim["tokens_compared"]["limit"])
    correct = (r["valid_ids"] and math.isfinite(r["max_logit_gap"])
               and r["max_logit_gap"] <= gap_limit
               and r["tokens_compared"] >= min_tokens)
    print(json.dumps({"phase": "control" if control else "check", **r}),
          flush=True)
    return {"correct": bool(correct), "readings": r, "compared": {
        "max_logit_gap": {"value": _finite(r["max_logit_gap"]),
                          "limit": gap_limit},
        "tokens_compared": {"value": r["tokens_compared"],
                            "limit": min_tokens}}}
