"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a configuration, a traffic mix or a metric by name: a
later change adds one by adding its file and its entry.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    """One workload entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries this cell reports (trace 0)
    per_layer: list           # metric entries this cell reports (trace 1)
    bench_dir: Path = field(default=BENCH_DIR, repr=False)

    @functools.cached_property
    def model(self):
        return load_model(self.config["model"], self.bench_dir)

    def reader(self, metric_name: str):
        return load_reader(metric_name, self.bench_dir)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def run_seconds(root: Path = ROOT) -> float:
    """How long one benchmark run measures."""
    return float(load_json(root / "BENCHMARK.json")["run_seconds"])


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(model: str, bench_dir: Path = BENCH_DIR):
    """The model family module ``bench/models/<model>.py``."""
    return _load_module(bench_dir / "models" / f"{model}.py",
                        f"bench_model_{model}")


def reader_path(metric_name: str, bench_dir: Path = BENCH_DIR) -> Path:
    """A metric's reader: ``metrics/<name>.py``, else the file of the name
    before its first dot (one reader serves ``x.rate`` and ``x.sat``)."""
    whole = bench_dir / "metrics" / f"{metric_name}.py"
    if whole.is_file():
        return whole
    return bench_dir / "metrics" / f"{metric_name.split('.')[0]}.py"


def load_reader(metric_name: str, bench_dir: Path = BENCH_DIR):
    mod = _load_module(reader_path(metric_name, bench_dir),
                       "bench_metric_" + metric_name.replace(".", "_"))
    return mod.read


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """Whether a cell reports ``metric``: its ``workloads`` list where it
    has one, else every cell that reports what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
              benchmark: dict | None = None) -> Cell:
    bm = benchmark if benchmark is not None else load_json(
        root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)
