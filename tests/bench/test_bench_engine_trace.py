"""The engine's spans and step records beside the benchmark's trace: idle
gaps and dispatches given to the innermost span, the two readings built on
them, and the step record against what the window infers."""

import gzip
import json
from types import SimpleNamespace

import jax
import pytest

from bench import arrivals, engine_trace, harness, spec, tracing, window
from bench_helpers import FIXTURES, tiny_cell

MS = 1_000_000          # nanoseconds


def _base():
    """The 100 ms window of ``test_bench_trace``: device busy [5, 25],
    [26, 36] and [80, 95] ms, so idle [0, 5], [25, 26], [36, 80] and
    [95, 100]."""
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
                    ["fusion.2", 10 * MS, 15 * MS],
                    ["dot.3", 26 * MS, 10 * MS],
                    ["fusion.1", 80 * MS, 15 * MS],
                    ["fusion.9", 120 * MS, 5 * MS]],
        }],
    }


def _with_engine():
    """The same window with the engine's spans inside the two benchmark
    ``engine_step`` spans (steps 7 and 8), and its dispatches."""
    ev = _base()
    spans = [("engine.step", 1, 39, 7, None),
             ("engine.admit", 1, 3, 7, 0), ("engine.chunk", 3, 6, 7, 0),
             ("engine.sync", 24, 27, 7, None),
             ("engine.record", 37, 38, 7, None),
             ("engine.step", 76, 99, 8, None),
             ("engine.decode", 76, 79, 8, None),
             ("engine.sync", 79, 96, 8, None),
             ("engine.record", 97, 98, 8, None)]
    ev["engine"] = [[n, a * MS, (b - a) * MS, step, rid]
                    for n, a, b, step, rid in spans]
    ev["dispatch"] = [["chunk_step", int(4.5 * MS), 10],
                      ["dynamic_slice", int(37.5 * MS), 10],
                      ["add", int(38.5 * MS), 10],
                      ["convert_element_type", 42 * MS, 10],
                      ["convert_element_type", MS // 2, 10],
                      ["decode_step", int(76.5 * MS), 10],
                      ["add", 120 * MS, 10]]          # after the window
    return ev


def _recorded():
    with gzip.open(FIXTURES / "trace-prefill-heavy.json.gz") as f:
        return json.load(f)


def test_idle_goes_to_the_innermost_span():
    tr = engine_trace.reduce(_with_engine())
    idle = dict(tr.idle_by_span)
    # [0,5]: engine_step 0-1, admit 1-3, chunk 3-5; [25,26]: sync;
    # [36,80]: engine.step 36-37 and 38-39, record 37-38, engine_step
    # 39-40 and 75-76, collect 40-45, idle_wait 45-75, decode 76-79,
    # sync 79-80; [95,100]: sync 95-96, engine.step 96-97 and 98-99,
    # record 97-98, engine_step 99-100
    want = {"engine_step": 4, "engine.admit": 2, "engine.chunk": 2,
            "engine.sync": 3, "engine.step": 4, "engine.record": 2,
            "collect": 5, "idle_wait": 30, "engine.decode": 3}
    assert idle == {k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(tr.window_s - tr.busy_s)
    assert tr.engine_steps == [7, 8]
    assert engine_trace.engine_idle_ms(tr) == pytest.approx(16 / 2)


@pytest.mark.parametrize("events", [_base, _recorded])
def test_without_engine_spans_the_reduction_is_unchanged(events):
    old = tracing.reduce(events())
    new = engine_trace.reduce(events())
    for field in ("window_s", "busy_s", "programs", "top_ops", "devices"):
        assert getattr(new, field) == getattr(old, field), field
    assert dict(new.idle_by_span) == pytest.approx(dict(old.idle_by_span))
    assert new.engine_steps == [] and new.dispatch_by_phase == {}
    assert engine_trace.engine_idle_ms(new) is None


def test_engine_spans_leave_the_other_fields_as_they_were():
    old = tracing.reduce(_base())
    new = engine_trace.reduce(_with_engine())
    for field in ("window_s", "busy_s", "programs", "top_ops", "devices"):
        assert getattr(new, field) == getattr(old, field), field
    assert sum(dict(new.idle_by_span).values()) == pytest.approx(
        sum(dict(old.idle_by_span).values()))


def test_dispatches_by_innermost_span():
    tr = engine_trace.reduce(_with_engine())
    assert tr.dispatch_by_phase == {
        "engine.chunk": {"chunk_step": 1},
        "engine.record": {"dynamic_slice": 1},
        "engine.step": {"add": 1},
        "collect": {"convert_element_type": 1},
        "engine_step": {"convert_element_type": 1},
        "engine.decode": {"decode_step": 1}}


def _minicpm():
    conf = spec.load_json(spec.BENCH_DIR / "configs" / "minicpm-2b.json")
    return conf, spec.load_model("dense")


def test_chunk_cost_counts_weights_resident_rows_and_new_rows():
    conf, m = _minicpm()
    f, b = engine_trace.chunk_cost(m, conf, 512, 256, 1024)
    assert f == window.chunk_flops(m, conf, 512, 256, 1024)
    assert b == m.param_count(conf) * 2 + (512 + 256) * 368_640
    # a full first chunk: 2 FLOPs per layer weight per row, about 1.26
    # TFLOP (6.4 ms at 197 TFLOP/s); the embedding table is no matmul
    f0, _ = engine_trace.chunk_cost(m, conf, 0, 256, 1024)
    layers = m.param_count(conf) - 122_753 * 2304 - 2304
    assert f0 == pytest.approx(2 * layers * 256, rel=0.01)
    assert f0 == pytest.approx(1.26e12, rel=0.01)


def test_chunk_roofline_over_the_traced_steps():
    conf, m = _minicpm()
    tr = engine_trace.reduce(_with_engine())          # chunk program: 10 ms
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    records = [SimpleNamespace(step=7, chunks=((0, 0, 256, 300),)),
               SimpleNamespace(step=8, chunks=()),
               SimpleNamespace(step=9, chunks=((1, 0, 256, 300),))]
    f, b = engine_trace.chunk_cost(m, conf, 0, 256, 300)
    least = max(f / 197e12, b / 819e9)
    assert engine_trace.chunk_roofline(tr, records, m, conf, peaks) == \
        pytest.approx(100 * least / 0.010)
    # a trace without the engine's spans has nothing to read
    assert engine_trace.chunk_roofline(engine_trace.reduce(_base()),
                                       records, m, conf, peaks) is None


def test_load_engine_reads_spans_stats_and_dispatches(tmp_path):
    f = jax.jit(lambda x: x + 1)
    f(1).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("engine.step", step=3):
            with jax.profiler.TraceAnnotation("engine.admit", step=3,
                                              rid="a"):
                f(2).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ev = engine_trace.load_engine(str(tmp_path))
    spans = {n: (step, rid) for n, _, _, step, rid in ev["engine"]}
    assert spans == {"engine.step": (3, None), "engine.admit": (3, "a")}
    (_, a0, ad, _, _), = [e for e in ev["engine"] if e[0] == "engine.admit"]
    progs = [(p, s) for p, s, _ in ev["dispatch"]]
    assert any(a0 <= s < a0 + ad for p, s in progs if p == "<lambda>")


def test_step_record_equals_what_the_window_infers():
    cell = tiny_cell("tiny-dense", "tiny-open")
    seed, seconds = 2**33 + 11, 1.5
    cfg, _, eng = harness.set_up(
        cell, seed, arrivals.prompt_lengths(cell.traffic, seconds))
    sched = arrivals.schedule(cell.traffic, seed, seconds, cfg.vocab_size,
                              cell.config["serve"]["kv_len"])
    drv = window.Runner(eng, cell.model, cell.config,
                        cell.config["serve"]["prefill_chunk"])
    win = window.run_open(drv, sched, seconds)
    records = engine_trace.engine_steps(eng)
    assert len(win.steps) > 5
    for s in win.steps:
        rec, = [r for r in records if s.t0 <= r.t0 and r.t1 <= s.t1]
        assert len(rec.admitted) == s.admitted
        assert all(win.reqs[rid].admitted == s.t0 for rid in rec.admitted)
        assert len(rec.chunks) == s.chunks
        assert sorted(p for _, p in rec.decoded) == sorted(
            s.decode_positions)
        flops = sum(window.chunk_flops(cell.model, cell.config, start,
                                       valid, total)
                    for _, start, valid, total in rec.chunks)
        if rec.decoded:
            flops += cell.model.decode_cost(
                cell.config, [p for _, p in rec.decoded])[0]
        assert flops == pytest.approx(s.flops, rel=1e-12)
