"""Spans and step records inside ``ContinuousEngine.run``: the phases of a
step as profiler spans on the device trace's clock, the ``ServeStep``
fields filled where the work happens, and step telemetry that runs no
program on the device."""

import functools
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get
from repro.models import lm
from repro.serve import ContinuousEngine

PHASES = {"engine.admit", "engine.chunk", "engine.sync", "engine.grow_tables",
          "engine.decode", "engine.finish", "engine.record",
          "engine.speculate"}
MODES = {"chunk": {"prefill_chunk": 8}, "spec": {"speculate": 2}}
# the phases each mode's steps go through
SEEN = {"chunk": PHASES - {"engine.speculate"},
        "spec": {"engine.admit", "engine.speculate", "engine.finish",
                 "engine.record"}}
PROMPTS = ((13, 5), (20, 4), (6, 3))         # (prompt length, max new)


@functools.cache
def _model():
    cfg = get("tinyllama-1.1b").reduced()
    init = jax.jit(lambda key: lm.init_params(cfg, key, jnp.float32))
    return cfg, init(jax.random.PRNGKey(0))


def _engine(**kw):
    cfg, params = _model()
    return ContinuousEngine(cfg, params, kv_len=32, n_slots=2, paged=True,
                            **kw)


def _submit(eng, base_rid):
    for i, (n, new) in enumerate(PROMPTS):
        eng.submit(list(range(1, n + 1)), new, rid=base_rid + i, arrival=i)


def _profiled_run(eng, log_dir):
    """Warm every program with one pass, then profile a second pass of the
    same requests; returns the host events named ``engine.*`` and the
    ``PjitFunction`` dispatches, as (name, start, end, stats)."""
    _submit(eng, 0)
    eng.run()
    eng.telemetry.reset()
    _submit(eng, 100)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # the engine's spans need no $-events
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    spans, dispatches = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith("engine."):
                    spans.append(ev + (dict(e.stats),))
                elif e.name.startswith("PjitFunction("):
                    dispatches.append(ev)
    return spans, dispatches


@pytest.fixture(scope="module", params=sorted(MODES))
def profiled(request, tmp_path_factory):
    eng = _engine(**MODES[request.param])
    spans, dispatches = _profiled_run(eng,
                                      tmp_path_factory.mktemp("trace"))
    return request.param, eng, spans, dispatches


def test_phases_lie_inside_their_step_and_do_not_overlap(profiled):
    mode, _, spans, _ = profiled
    steps = {st["step"]: (s, e) for n, s, e, st in spans
             if n == "engine.step"}
    phases = sorted((s, e, n, st) for n, s, e, st in spans
                    if n != "engine.step")
    assert {n for _, _, n, _ in phases} == SEEN[mode]
    for s, e, name, st in phases:
        s0, e0 = steps[st["step"]]
        assert s0 <= s <= e <= e0, (name, st)
    for (_, e, a, _), (s, _, b, _) in zip(phases, phases[1:]):
        assert e <= s, (a, b)


def test_spans_carry_rid_and_step_stats(profiled):
    mode, eng, spans, _ = profiled
    rids = {100, 101, 102}
    for name in ("engine.admit", "engine.finish",
                 "engine.chunk" if mode == "chunk" else "engine.speculate"):
        got = {st["rid"] for n, _, _, st in spans if n == name}
        assert got == rids, (name, got)
    recorded = {s.step for s in eng.telemetry.steps}
    assert {st["step"] for n, _, _, st in spans
            if n == "engine.record"} == recorded
    if mode == "chunk":
        chunks = sorted((st["rid"], st["start"], st["valid"])
                        for n, _, _, st in spans if n == "engine.chunk")
        assert chunks == sorted(c[:3] for s in eng.telemetry.steps
                                for c in s.chunks)


def test_step_record_phases_match_the_spans(profiled):
    _, eng, spans, _ = profiled
    for rec in eng.telemetry.steps:
        assert rec.t0 < rec.t1
        assert rec.seconds == pytest.approx(rec.t1 - rec.t0)
        assert sum(rec.phases.values()) <= rec.t1 - rec.t0
        traced = {n for n, _, _, st in spans
                  if st["step"] == rec.step and n != "engine.step"}
        assert set(rec.phases) == traced, rec.step


def test_record_dispatches_no_device_program(profiled):
    _, _, spans, dispatches = profiled
    records = [(s, e) for n, s, e, _ in spans if n == "engine.record"]
    assert records and dispatches
    inside = [d for d in dispatches
              if any(s <= d[1] < e for s, e in records)]
    assert inside == []


def test_step_record_counts_the_work(profiled):
    mode, eng, _, _ = profiled
    steps = list(eng.telemetry.steps)
    assert sorted(r for s in steps for r in s.admitted) == [100, 101, 102]
    for s in steps:
        assert s.prefill_chunks == len(s.chunks)
        assert len(s.decoded) == len(s.active_slots)
    if mode == "chunk":
        # each prompt's chunks cover it once, in order
        for rid, (n, _) in zip((100, 101, 102), PROMPTS):
            rows = [c for s in steps for c in s.chunks if c[0] == rid]
            assert [c[1] for c in rows] == list(range(0, n, 8))
            assert sum(c[2] for c in rows) == n
            assert {c[3] for c in rows} == {n}
    # a lane decodes from the row after its prompt on; a plain decode
    # step writes one row, a speculative round one or more
    for rid, (n, _) in zip((100, 101, 102), PROMPTS):
        pos = [p for s in steps for r, p in s.decoded if r == rid]
        assert pos[0] == n and pos == sorted(set(pos))
        if mode == "chunk":
            assert pos == list(range(n, n + len(pos)))


def _pool_stores(arch):
    cfg = get(arch).reduced()
    kv_len = {"phi-3-vision-4.2b": 56}.get(arch, 64)
    eng = ContinuousEngine(cfg, None, kv_len=kv_len, n_slots=2, paged=True)
    return eng.allocator.stores


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_block_bytes_from_shapes_equals_the_slice(arch):
    stores = _pool_stores(arch)
    for store in stores:
        per_k, per_v = store.k_pages[:, 0], store.v_pages[:, 0]
        sliced = (per_k.size * per_k.dtype.itemsize
                  + per_v.size * per_v.dtype.itemsize)
        assert store.block_bytes == sliced
