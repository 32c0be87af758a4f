"""The chip a run measures: refuse anything else, and know its peaks."""

from __future__ import annotations

import json

import jax

from .spec import BENCH_DIR


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip the peaks table lacks."""


def peaks(kind: str, table_path=BENCH_DIR / "peaks.json") -> dict:
    with open(table_path) as f:
        table = json.load(f)
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {table_path.name} "
                     f"(have {sorted(table)}): no peaks to measure against")
    return table[kind]


def require_chips(chips: int) -> tuple[list, dict]:
    """The cell's devices and their peaks, or ``NoChip``."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips], peaks(devs[0].device_kind)


def describe(devs: list) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest chip the cell used."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": jax.device_count(), "memory_peak_bytes": int(peak)}
