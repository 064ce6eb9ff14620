#!/usr/bin/env python3
"""Readings that the output check's limits are set from (not a run).

    python3 chipbench/calibrate.py --workload guppy.flowcell \\
        --seeds 2001-2012 --seconds 8 --control bits=4 --control matmul=high

One process: the cell's pipeline is built once, then for every seed the
program is driven for a short window at the cell's own load and its
compared numbers are read, and each control (the reference computed at a
lower bit width or matmul precision, put in the program's place) is
compared on the same lanes.  Prints one JSON line per seed.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def seeds_of(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def control_of(text: str) -> dict:
    """``bits=4`` or ``conv=high`` / ``matmul=high``."""
    k, _, v = text.partition("=")
    return {"bits": int(v)} if k == "bits" else {"precision": {k: v}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="append", default=[])
    args = ap.parse_args()

    import harness
    cell = harness.Cell(harness.load_json(HERE.parent / "BENCHMARK.json"),
                        args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(harness.WORK / "jax_cache"))
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    pipe = harness.build_pipeline(cell.cfg, "pallas")
    controls = [control_of(c) for c in args.control]
    for seed in seeds_of(args.seeds):
        out = harness.run_cell(cell, seed, args.seconds, False, pipe=pipe,
                               controls=controls)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": out["_numbers"],
                          "controls": out["_control"],
                          "metrics": out["metrics"], "run": out["_run"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
