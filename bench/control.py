"""Readings that set a cell's correctness limit, on the chip at the cell's
own size. Not run by the benchmark itself.

* ``--sound``: the program as configured (the lower reading). On each of
  these runs' samples the fp8 control (``reference.control_gaps``: the
  reference in the program's place with fp8 weights) is read too;
* ``--int8``: the program with its own int8 KV pages switched on, the
  program's one path below the configuration's bfloat16.

    python bench/control.py --workload <cell> --sound 11,12,13 \\
        --int8 21,22,23 --seconds 51

One process serves every seed in turn (each seed draws its own weights),
so executables compile or load once. Each run prints its compared
readings as one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402
from bench.run import check_devices, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sound", default="")
    ap.add_argument("--int8", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    check_devices(cell.chips)
    runs = [(int(s), None) for s in args.sound.split(",") if s]
    runs += [(int(s), "int8") for s in args.int8.split(",") if s]
    for seed, kv in runs:
        rec = run_cell(cell, seed, args.seconds, False, kv_dtype=kv,
                       control=kv is None)
        line = {"phase": "reading", "workload": cell.name, "seed": seed,
                "kv_dtype": kv or "as configured", "correct": rec["correct"],
                "compared": rec["compared"],
                "attempted": rec["attempted"], "metrics": rec["metrics"]}
        if "control" in rec:
            line["fp8_control"] = rec["control"]
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
