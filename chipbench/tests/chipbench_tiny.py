"""Tiny cells for the benchmark's CPU tests: the real configurations and
traffic mixes, cut to widths and loads a test run can hold."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


class TinyCell:
    """A ``harness.Cell`` look-alike at test size: a configuration of the
    benchmark cut by its family's ``tiny`` and a traffic mix cut to 8
    lanes, checked against the limits of ``guppy.flowcell``."""

    def __init__(self, config: str, traffic: str, **mix_overrides):
        base = harness.HERE
        self.name = f"tiny.{config}.{traffic}"
        self.chips = 1
        real = harness.load_json(base / "configs" / f"{config}.json")
        self.cfg = harness.family_of(real).tiny(real)
        self.mix = harness.load_json(base / "traffic" / f"{traffic}.json")
        self.mix.update(lanes_per_chip=8, read_bases={
            "law": "lognormal", "median": 200, "sigma": 0.05, "block": 8})
        self.mix.update(mix_overrides)
        self.limits = harness.load_json(base / "limits" /
                                        "guppy.flowcell.json")
        self.end_to_end = []
        self.per_layer = []


def run(cell, seed=12345678901, seconds=1.0, **kw):
    return harness.run_cell(cell, seed, seconds, False, backend="ref", **kw)
