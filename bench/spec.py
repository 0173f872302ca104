"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell names a configuration and a traffic mix; the configuration's file is
the one ``BENCHMARK.json`` gives, the mix is ``bench/traffic/<mix>.json``,
each metric is ``bench/metrics/<metric>.py``, the correctness limits are
``bench/limits/<cell>.json``, and the configuration's ``family`` names
``bench/families/<family>.py``, the one module that knows the
architecture (see ``FAMILY_CONTRACT``). Adding a cell, a mix, a metric or
a configuration of a new architecture therefore means adding files and
entries, never editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# What a family module defines; only it reads the model block's widths.
FAMILY_CONTRACT = (
    "check",                    # (cfg, model_block): raise on a mismatch
    "perturb",                  # (params, key, vocab_size) -> params
    "init_weights",             # (model_block, key) -> reference weights
    "hidden",                   # (model_block, w, tokens, g_mixer, g_ffn,
                                #  lowp) -> final hidden states [T, d]
    "matmul_flops_per_token",   # (cfg) -> float
    "attn_flops",               # (cfg, ctx) -> float
    "decode_attn_bytes",        # (cfg, ctx, kv_bytes, act_bytes) -> float
    "kv_bytes_per_ctx_token",   # (cfg) -> int
)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    family: Any                     # the configuration's family module
    mix: Dict[str, Any]             # the traffic file's contents
    limits: Dict[str, Any]          # the correctness limits of this cell
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix, limits and metrics."""
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    bench = os.path.join(root, "bench")
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    # a per-layer metric without a cell list is reported wherever the
    # end-to-end metric it moves is
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        family=family_module(config, root),
        mix=load_json(os.path.join(bench, "traffic", w["traffic"] + ".json")),
        limits=load_json(os.path.join(bench, "limits", name + ".json")),
        end_to_end=e2e, per_layer=per_layer)


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(config: Dict[str, Any], root: str = ROOT):
    """Import ``bench/families/<family>.py`` for a configuration file's
    contents; an error unless it defines every function of
    ``FAMILY_CONTRACT``."""
    if "family" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"family")
    family = config["family"]
    path = os.path.join(root, "bench", "families", family + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {config.get('name')!r}: "
                                f"no family module {path}")
    mod = _load(path, "bench_family_" + family)
    missing = [f for f in FAMILY_CONTRACT
               if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"family module {path} lacks "
                             f"{', '.join(missing)}")
    return mod


def metric_module(metric: str, root: str = ROOT):
    """Import ``bench/metrics/<metric>.py``; it defines
    ``compute(ctx) -> float | None``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    return _load(path, "bench_metric_" + metric.replace(".", "_"))
