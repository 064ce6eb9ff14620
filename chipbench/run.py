#!/usr/bin/env python3
"""Chip benchmark of the Helix serving path: one run of one cell.

    python3 chipbench/run.py --workload guppy.flowcell --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout on a machine with the chips the cell
asks for (``BENCHMARK.json``).  The run builds the cell's basecaller at
its full published widths (5-bit weights and activations, Pallas
kernels), draws its weights and traffic from ``--seed``, warms up the
cell's own shapes, turns JAX's persistent compile cache off, drives the
traffic through ``serve.api.Server`` for ``--seconds``, and checks what
the timed path produced against the plain reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; then ``checks``, each compared number with its limit
(also the last lines of stderr).  Off a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.

JAX's persistent compile cache lives in ``chipbench/_work/jax_cache``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(HERE / "_work" / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    cell = harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"),
                        args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(harness.WORK / "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
