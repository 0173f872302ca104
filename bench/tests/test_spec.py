"""Every entry of BENCHMARK.json resolves to its files, and a cell, a mix
or a metric is added with new files and entries alone."""
import json
import os
import shutil

import pytest

from bench import spec

BM = spec.benchmark()


def test_entries_resolve_to_their_files():
    names = {c["name"] for c in BM["configs"]}
    for c in BM["configs"]:
        assert c["file"].startswith("bench/")
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in cfg["reduced"]:
            assert key in cfg["config"] and key in cfg["reduced_from"]
    for w in BM["workloads"]:
        assert w["config"] in names
        cell = spec.resolve(w["name"])
        assert cell.mix["name"] == w["traffic"]
        assert {"max_logit_gap", "tokens_compared"} <= set(cell.limits)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in BM["end_to_end"] + BM["per_layer"]:
        mod = spec.metric_module(m["name"])
        assert callable(mod.compute)
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BM["workloads"]}


def test_per_layer_metrics_move_a_reported_metric():
    for w in BM["workloads"]:
        cell = spec.resolve(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in e2e for m in cell.per_layer)


def test_a_cell_is_added_by_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench")
    bm = json.loads(json.dumps(BM))
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                      "reason_long_kvhalf.json"))
    mix["name"] = "reason_short"
    mix["output_tokens"] = {"dist": "uniform", "min": 128, "max": 512}
    (root / "bench" / "traffic" / "reason_short.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / "glm4-9b.reason_short.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 1.0},
                    "tokens_compared": {"limit": 200}}))
    (root / "bench" / "metrics" / "window_tokens.py").write_text(
        "def compute(ctx):\n"
        "    return ctx.delivered('open', 'close')\n")
    bm["workloads"].append({"name": "glm4-9b.reason_short",
                            "config": "glm4-9b-l20",
                            "traffic": "reason_short", "chips": 1,
                            "why": "test"})
    bm["end_to_end"].append({"name": "window_tokens", "unit": "tokens",
                             "better": "higher", "bound": 0.25,
                             "source": "host_clock",
                             "workloads": ["glm4-9b.reason_short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.resolve("glm4-9b.reason_short", root=str(root))
    assert cell.config["name"] == "glm4-9b-l20"
    assert cell.mix["output_tokens"]["max"] == 512
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "window_tokens"]
    assert callable(spec.metric_module("window_tokens",
                                       root=str(root)).compute)
    # the files that were there are unchanged
    for sub in ("run.py", "traffic.py", "spec.py"):
        assert (root / "bench" / sub).read_text() == open(
            os.path.join(spec.BENCH_DIR, sub)).read()


def test_bench_json_keeps_to_the_contract():
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"] and 1 <= BM["run_seconds"] <= 51
    for m in BM["end_to_end"]:
        assert 0.0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
