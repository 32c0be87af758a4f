"""A run measures a TPU it knows the peaks of, or prints nothing."""

import shutil
import subprocess
import sys

import pytest

from bench import device, spec

RUN = ["--workload", "minicpm-2b.prefill-heavy", "--seed", "1",
       "--seconds", "1", "--trace", "0"]


def test_no_tpu_is_refused():
    with pytest.raises(device.NoChip):
        device.require_chips(1)


def test_unknown_device_kind_is_refused():
    with pytest.raises(device.NoChip):
        device.peaks("TPU v99")
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    p = subprocess.run([sys.executable, str(spec.BENCH_DIR / "run.py"), *RUN],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    for p in ("bench", "tests/bench"):
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", *RUN],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""
