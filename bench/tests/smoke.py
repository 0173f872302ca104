"""Smoke-size stand-ins of the benchmark's cells for CPU tests: the same
architectures, files and code paths at widths a test run can hold."""
from __future__ import annotations

import copy
import dataclasses

from bench import spec

SMOKE_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
               "head_dim": 16, "d_ff": 160}
SMOKE_VOCAB = {"glm4-9b-l20": (512, 512)}
# an open-loop mix on the same cell: the generator's and the window's other
# arrival process (no cell of BENCHMARK.json uses it yet)
OPEN_LOOP = {"process": "poisson", "rate_per_s": 4.0, "lead_in_s": 0.5,
             "drain_cap_s": 60.0}


def smoke_cell(name: str, open_loop: bool = False,
               **mix_overrides) -> spec.Cell:
    """``name``'s cell at smoke widths, bf16 as served, with a small mix
    (closed loop as in the cell, or ``open_loop``)."""
    cell = spec.resolve(name)
    cfg = copy.deepcopy(cell.config)
    vocab, padded = SMOKE_VOCAB[cfg["name"]]
    cfg["overrides"] = dict(cfg["overrides"], **SMOKE_MODEL,
                            vocab_size=vocab, vocab_round_to=64,
                            param_dtype="bfloat16", dtype="bfloat16")
    cfg["model"] = dict(cfg["model"], **SMOKE_MODEL, vocab_size=vocab,
                        vocab_padded=padded)
    mix = copy.deepcopy(cell.mix)
    mix["engine"].update(slots=4, max_len=128, max_prefill_tokens=32)
    mix["block"] = 8
    mix["prompt_tokens"] = dict(mix["prompt_tokens"], median=16, min=4,
                                max=48)
    if open_loop:
        mix["arrivals"] = dict(OPEN_LOOP)
        mix["output_tokens"] = {"dist": "lognormal", "median": 8,
                                "sigma": 0.8, "min": 4, "max": 24}
    else:
        mix["arrivals"].update(requests=200, in_flight=3)
        mix["output_tokens"] = {"dist": "uniform", "min": 24, "max": 64}
    mix["check"] = {"requests": 3}
    for k, v in mix_overrides.items():
        mix[k] = v
    # sound runs read 0.011-0.015 at this size, the fp8 control 0.34
    limits = {"max_logit_gap": {"limit": 0.1},
              "tokens_compared": {"limit": 10}}
    return dataclasses.replace(cell, config=cfg, mix=mix, limits=limits)


def smoke_budget(cell) -> float:
    """Weights plus room for every slot's pages at ``max_len``."""
    from bench import serve
    from repro.core import masks, memory
    cfg = serve.model_config(cell)
    mm = memory.build_memory_model(cfg)
    e = cell.mix["engine"]
    full = masks.full_mask(cfg.n_layers)
    return (mm.param_bytes(full)
            + 2 * e["slots"] * mm.state_bytes(full, 1, e["max_len"]))
