"""Tiny cells for the benchmark's CPU tests: the harness without its look
for a chip, at sizes a test run holds."""

import json
import time
from pathlib import Path

import jax

from bench import harness, spec

FIXTURES = Path(__file__).resolve().parent / "fixtures"
BENCHMARK = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
# any positive peaks: on the CPU the device metrics are not read
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def fixture(name: str) -> dict:
    return json.loads((FIXTURES / f"{name}.json").read_text())


def tiny_cell(config: str, traffic: str, **conf_changes) -> spec.Cell:
    conf = {**fixture(config), **conf_changes}
    return spec.Cell(name=f"{config}.{traffic}", chips=1, config=conf,
                     traffic=fixture(traffic),
                     end_to_end=BENCHMARK["end_to_end"],
                     per_layer=BENCHMARK["per_layer"])


def run(cell: spec.Cell, seed: int = 2**33 + 5, seconds: float = 2.0,
        trace: bool = False) -> dict:
    return harness.run_cell(cell, seed, seconds, trace, jax.devices(),
                            CPU_PEAKS, time.time())
