"""The reduction from trace events to metrics: busy union, per-program
device time, and idle gaps by the host span around them."""

import gzip
import json

import pytest

from bench import tracing
from bench_helpers import FIXTURES

MS = 1_000_000          # nanoseconds


def _synthetic():
    """A 100 ms window: two decode programs and one chunk program, with
    ops that overlap inside the first program."""
    return {
        "host": [["traced_window", 0, 100 * MS],
                 ["engine_step", 0, 40 * MS],
                 ["collect", 40 * MS, 5 * MS],
                 ["idle_wait", 45 * MS, 30 * MS],
                 ["engine_step", 75 * MS, 25 * MS]],
        "devices": [{
            "modules": [["jit_decode_step(7)", 5 * MS, 20 * MS],
                        ["jit_chunk_step(9)", 26 * MS, 10 * MS],
                        ["jit_decode_step(7)", 80 * MS, 15 * MS]],
            "ops": [["fusion.1", 5 * MS, 12 * MS],
                    ["fusion.2", 10 * MS, 15 * MS],      # overlaps fusion.1
                    ["dot.3", 26 * MS, 10 * MS],
                    ["fusion.1", 80 * MS, 15 * MS],
                    ["fusion.9", 120 * MS, 5 * MS]],     # after the window
        }],
    }


def test_busy_is_the_union_of_op_intervals():
    tr = tracing.reduce(_synthetic())
    assert tr.window_s == pytest.approx(0.100)
    # [5, 25] + [26, 36] + [80, 95] = 20 + 10 + 15 ms
    assert tr.busy_s == pytest.approx(0.045)
    assert tr.devices == 1


def test_device_time_per_program():
    tr = tracing.reduce(_synthetic())
    assert tr.programs["jit_decode_step"] == [2, pytest.approx(0.035)]
    assert tr.programs["jit_chunk_step"] == [1, pytest.approx(0.010)]
    assert tr.top_ops[0] == ["fusion.1", pytest.approx(0.027)]


def test_idle_gaps_by_enclosing_host_span():
    tr = tracing.reduce(_synthetic())
    idle = dict(tr.idle_by_span)
    # gaps [0,5] [25,26] [36,80] [95,100]: 0-5, 25-26 and 36-40 inside the
    # first engine step; 40-45 in collect; 45-75 idle_wait; 75-80 and
    # 95-100 in the second engine step
    assert idle["engine_step"] == pytest.approx(0.020)
    assert idle["collect"] == pytest.approx(0.005)
    assert idle["idle_wait"] == pytest.approx(0.030)
    assert sum(idle.values()) == pytest.approx(0.100 - 0.045)


def test_union_merges_touching_and_nested():
    assert tracing.union([[5, 9], [0, 2], [2, 3], [6, 7]]) == \
        [[0, 3], [5, 9]]


def _recorded():
    """60 ms of a traced minicpm-2b.prefill-heavy window on one v5e chip:
    the end of an engine step that ran a prefill chunk, the host's collect
    and submit, and the start of the next step; the window span is set to
    the slice, and op names are shortened by ``op_label``."""
    with gzip.open(FIXTURES / "trace-prefill-heavy.json.gz") as f:
        return json.load(f)


def _busy_by_mask(events, lo, hi, step=1000):
    """Busy seconds counted on a 1 us grid, independently of ``union``."""
    import numpy as np
    busy = np.zeros((hi - lo) // step + 1, bool)
    for _, s, d in events["devices"][0]["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            busy[(a - lo) // step:-(-(b - lo) // step)] = True
    return busy.sum() * step / 1e9


def test_recorded_trace_busy_union():
    ev = _recorded()
    _, lo, dur = ev["host"][0]
    tr = tracing.reduce(ev)
    assert tr.window_s == pytest.approx(dur / 1e9)
    assert tr.busy_s == pytest.approx(_busy_by_mask(ev, lo, lo + dur),
                                      abs=2e-4)
    assert 0 < tr.busy_s < tr.window_s


def test_recorded_trace_program_time():
    ev = _recorded()
    _, lo, dur = ev["host"][0]
    tr = tracing.reduce(ev)
    chunk = [d for n, s, d in ev["devices"][0]["modules"]
             if n.startswith("jit_chunk_step(") and lo <= s < lo + dur]
    assert tr.programs["jit_chunk_step"] == [len(chunk),
                                             pytest.approx(sum(chunk) / 1e9)]
    assert len(chunk) == 1 and tr.programs["jit_chunk_step"][1] > 0.05


def test_recorded_trace_idle_by_host_span():
    tr = tracing.reduce(_recorded())
    idle = dict(tr.idle_by_span)
    assert sum(idle.values()) == pytest.approx(tr.window_s - tr.busy_s)
    assert max(idle, key=idle.get) == "engine_step"
    assert set(idle) <= set(tracing.HOST_SPANS) | {"other"}


def test_recorded_trace_top_ops_are_leaves():
    tr = tracing.reduce(_recorded())
    assert len(tr.top_ops) == 10
    assert not any(name.endswith(" while") for name, _ in tr.top_ops)
    assert sum(sec for _, sec in tr.top_ops) <= tr.busy_s
    assert [s for _, s in tr.top_ops] == sorted(
        (s for _, s in tr.top_ops), reverse=True)


def test_leaf_ops_drop_containers():
    ops = [["while.1", 0, 100], ["fusion.1", 0, 40], ["fusion.2", 50, 50],
           ["copy.3", 120, 5]]
    assert [o[0] for o in tracing.leaf_ops(ops)] == ["fusion.1", "fusion.2",
                                                      "copy.3"]
