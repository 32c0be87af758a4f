"""The benchmark is driven by data: its file meets the contract, and a new
configuration, traffic mix or per-layer metric is picked up from new files
and entries alone."""

import copy
import json
import re
import shutil

from bench import spec
from bench_helpers import BENCHMARK, fixture, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = {"hidden_size", "intermediate_size", "d_model", "d_state",
          "headdim", "expand", "num_attention_heads",
          "num_key_value_heads", "head_dim"}


def test_benchmark_file_meets_the_contract():
    bm = BENCHMARK
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    for p in bm["paths"]:
        assert (spec.ROOT / p).is_dir()
    rs = bm["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    configs = {c["name"]: c for c in bm["configs"]}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["source"].startswith("https://")
        conf = spec.load_json(spec.ROOT / c["file"])
        assert c["file"].startswith("bench/") and conf["name"] == c["name"]
        assert c["reduced"] == conf["reduced"]
        assert not [k for k in c["reduced"]
                    if k.endswith(("_dim", "_rank")) or k in WIDTHS]
    metrics = bm["end_to_end"] + bm["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert spec.reader_path(m["name"]).is_file(), m["name"]
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = [w["name"] for w in bm["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert w["config"] in configs and len(w["why"]) <= 200
        assert (spec.BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
        cell = spec.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:                 # what it moves is reported
            assert m["moves"] in names
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        for w in m["workloads"]:
            assert w in cells
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= len(cells) // 2


def test_new_traffic_config_and_metric_are_picked_up(tmp_path):
    root = tmp_path
    bench_dir = root / "bench"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    # new files only
    (bench_dir / "configs" / "tiny-dense.json").write_text(
        json.dumps(fixture("tiny-dense")))
    (bench_dir / "traffic" / "tiny-new.json").write_text(
        json.dumps(fixture("tiny-open")))
    (bench_dir / "metrics" / "tokens_per_request.py").write_text(
        "def read(ctx):\n"
        "    reqs = ctx.window.due_in_window()\n"
        "    return sum(len(r.times) for r in reqs) / len(reqs)\n")
    # and new entries
    bm = copy.deepcopy(BENCHMARK)
    bm["configs"].append({"name": "tiny-dense", "source": "https://x",
                          "file": "bench/configs/tiny-dense.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-dense.tiny-new",
                            "config": "tiny-dense", "traffic": "tiny-new",
                            "chips": 1, "why": "test"})
    open_loop = {"ttft_p90_ms"}
    for m in bm["end_to_end"]:
        if m["name"] in open_loop:
            m["workloads"].append("tiny-dense.tiny-new")
    bm["per_layer"].append({"name": "tokens_per_request", "unit": "tokens",
                            "better": "higher", "source": "host_clock",
                            "layer": "scheduler", "moves": "ttft_p90_ms",
                            "workloads": ["tiny-dense.tiny-new"]})
    cell = spec.load_cell("tiny-dense.tiny-new", root=root,
                          bench_dir=bench_dir, benchmark=bm)
    assert cell.traffic == fixture("tiny-open")
    assert [m["name"] for m in cell.per_layer if m["name"] ==
            "tokens_per_request"] == ["tokens_per_request"]
    traced = run(cell, trace=True)
    assert traced["correct"] and "tokens_per_request" in traced["metrics"]
    plain = run(cell)
    assert set(plain["metrics"]) == {m["name"] for m in bm["end_to_end"]
                                     if m["name"] in open_loop | {"setup_s"}}
    # the cells already there load as before from the copy
    for w in BENCHMARK["workloads"]:
        old = spec.load_cell(w["name"])
        new = spec.load_cell(w["name"], root=root, bench_dir=bench_dir,
                             benchmark=bm)
        assert (old.config, old.traffic) == (new.config, new.traffic)
        assert [m["name"] for m in old.end_to_end + old.per_layer] == \
            [m["name"] for m in new.end_to_end + new.per_layer]
