"""The engine's own spans and step records beside the benchmark's trace:
which engine phase each idle gap of the device and each eager program
falls in, and what the chunk program needed against what it took.

The engine writes one ``engine.step`` span per step and, inside it, one
flat span per phase (``engine.admit``, ``engine.chunk``, ``engine.sync``,
...), each with its ``step`` (and ``rid``) as stats; each step also ends
in a ``ServeStep`` record with the same ``step``.  ``load_engine`` keeps
those spans and the ``PjitFunction(<program>)`` dispatch events from the
profiler's host plane; ``reduce`` adds them to ``tracing.reduce``:

* ``idle_by_span`` gives each idle gap to the innermost span covering it,
  an engine phase, else ``engine.step``, else the benchmark's own span,
  else "other"; it sums to the idle time, and without engine spans it is
  ``tracing.reduce``'s, untruncated;
* ``engine_steps``: the steps whose ``engine.step`` lies in the window;
* ``dispatch_by_phase``: {phase: {program: calls}} by the same rule.

The harness does not read these yet (its loader keeps only its own spans
and removes the trace before the metric readers run).  ``python3
bench/engine_trace.py --workload <cell> --seed <n> --seconds <s>`` runs
one traced window of a cell and prints the reduction as one JSON line.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import shutil
import statistics
import sys
from dataclasses import dataclass

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0:1] = [_root, os.path.join(_root, "src")]

import jax.numpy as jnp  # noqa: E402

from bench import tracing, window  # noqa: E402
from bench.refmath import SERVE_DTYPE  # noqa: E402

ENGINE = "engine."
STEP = "engine.step"
DISPATCH = "PjitFunction("


def load_engine(out_dir: str) -> dict:
    """The engine's spans, [[name, start, dur, step, rid]] (rid None where
    the span has none), and the dispatches, [[program, start, dur]], from
    the host planes of the trace under ``out_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    engine, dispatch = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ENGINE):
                    st = dict(e.stats)
                    engine.append([e.name, e.start_ns, e.duration_ns,
                                   st.get("step"), st.get("rid")])
                elif e.name.startswith(DISPATCH):
                    dispatch.append([e.name[len(DISPATCH):-1], e.start_ns,
                                     e.duration_ns])
    return {"engine": engine, "dispatch": dispatch}


@dataclass
class EngineTrace(tracing.Trace):
    engine_steps: list                 # step values, in order
    dispatch_by_phase: dict            # phase -> {program: calls}


def _layers(events: dict) -> list:
    """Sorted [start, end, name] spans, innermost layer first: the engine's
    phases, its steps, the benchmark's own spans.  The spans of one layer
    do not overlap one another."""
    engine = events.get("engine", [])
    layers = [[[s, s + d, n] for n, s, d, *_ in engine if n != STEP],
              [[s, s + d, n] for n, s, d, *_ in engine if n == STEP],
              [[s, s + d, n] for n, s, d in events["host"]
               if n in tracing.HOST_SPANS]]
    return [sorted(layer) for layer in layers]


def _covering(layer: list, starts: list, t: int):
    """The span of ``layer`` that holds instant ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return layer[i] if i >= 0 and t < layer[i][1] else None


def _share(layers, starts, g0, g1, into: dict, scale: float) -> None:
    """Give [g0, g1] to the innermost spans that cover it; "other" gets
    what none covers."""
    pieces = [(g0, g1)]
    for layer, st in zip(layers, starts):
        rest = []
        for a, b in pieces:
            i = max(0, bisect.bisect_right(st, a) - 1)
            cur = a
            while i < len(layer) and layer[i][0] < b:
                s, e, name = layer[i]
                lo, hi = max(s, cur), min(e, b)
                if hi > lo:
                    if lo > cur:
                        rest.append((cur, lo))
                    into[name] = into.get(name, 0.0) + (hi - lo) * scale
                    cur = hi
                i += 1
            if b > cur:
                rest.append((cur, b))
        pieces = rest
    left = sum(b - a for a, b in pieces)
    if left > 0:
        into["other"] = into.get("other", 0.0) + left * scale


def reduce(events: dict, top: int = 10) -> EngineTrace:
    """``tracing.reduce`` of ``events`` (its dict, with ``load_engine``'s
    keys added), with the idle gaps given to the innermost span."""
    base = tracing.reduce(events, top)
    host = events["host"]
    win = [h for h in host if h[0] == tracing.WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(h[1] for h in host)
        hi = max(h[1] + h[2] for h in host)
    layers = _layers(events)
    starts = [[x[0] for x in layer] for layer in layers]
    n = max(1, len(events["devices"]))
    idle: dict = {}
    for dev in events["devices"]:
        merged = tracing._clip(tracing.union([[s, s + d]
                                              for _, s, d in dev["ops"]]),
                               lo, hi)
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                _share(layers, starts, prev, s, idle, 1e-9 / n)
            prev = max(prev, e)
    dispatch: dict = {}
    for prog, s, _ in events.get("dispatch", []):
        if lo <= s < hi:
            span = next((sp for layer, st in zip(layers, starts)
                         if (sp := _covering(layer, st, s))), None)
            into = dispatch.setdefault(span[2] if span else "other", {})
            into[prog] = into.get(prog, 0) + 1
    steps = sorted(st for n_, s, d, st, _ in events.get("engine", [])
                   if n_ == STEP and lo <= s and s + d <= hi)
    ranked = sorted(([k, v] for k, v in idle.items()), key=lambda kv: -kv[1])
    return EngineTrace(**{**vars(base), "idle_by_span": ranked},
                       engine_steps=steps, dispatch_by_phase=dispatch)


def engine_steps(eng) -> list:
    """The engine's ``ServeStep`` records, oldest first."""
    return list(eng.telemetry.steps)


def engine_idle_ms(tr: EngineTrace):
    """Device idle inside ``engine.step`` spans, phases included, per
    engine step of the traced window."""
    if not tr.engine_steps:
        return None
    idle = sum(v for k, v in tr.idle_by_span if k.startswith(ENGINE))
    return 1e3 * idle / len(tr.engine_steps)


def chunk_cost(model, conf: dict, start: int, valid: int,
               total: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) one prompt chunk needs: ``window.chunk_flops``
    for its ``valid`` rows; the weights read once, the K/V of the ``start``
    positions before it read once, its ``valid`` new rows written once."""
    weights = model.param_count(conf) * jnp.dtype(SERVE_DTYPE).itemsize
    kv = model.kv_bytes_per_token(conf)
    return (window.chunk_flops(model, conf, start, valid, total),
            float(weights + kv * (start + valid)))


def chunk_roofline(tr: EngineTrace, records, model, conf: dict,
                   peaks: dict):
    """Percent: the least time the chunks of the traced steps need at the
    chip's peaks (the larger of FLOPs and bytes over peak, per chunk),
    over the trace's ``jit_chunk_step`` device seconds."""
    if not tr.engine_steps or "jit_chunk_step" not in tr.programs:
        return None
    traced = set(tr.engine_steps)
    least = 0.0
    for rec in records:
        if rec.step in traced:
            for _, start, valid, total in rec.chunks:
                f, b = chunk_cost(model, conf, start, valid, total)
                least += max(f / peaks["bf16_flops_per_s"],
                             b / peaks["hbm_bytes_per_s"])
    return 100.0 * least / tr.programs["jit_chunk_step"][1]


def run_traced(workload: str, seed: int, seconds: float) -> dict:
    """One window of ``workload`` with its last seconds traced, as the
    harness runs it (no reference check); the engine's view of it."""
    from bench import arrivals, device, harness, spec

    harness.use_checkout_cache()
    cell = spec.load_cell(workload)
    devs, peaks = device.require_chips(cell.chips)
    traffic, serve = cell.traffic, cell.config["serve"]
    cfg, _, eng = harness.set_up(cell, seed,
                                 arrivals.prompt_lengths(traffic, seconds))
    sched = arrivals.schedule(traffic, seed, seconds, cfg.vocab_size,
                              serve["kv_len"])
    drv = window.Runner(eng, cell.model, cell.config, serve["prefill_chunk"])
    length = min(harness.TRACE_SECONDS, 0.5 * seconds)
    tracer = tracing.Tracer(str(harness.TRACE_DIR), seconds - length, length)
    win = window.run_open(drv, sched, seconds, tracer)
    tracer.stop()
    events = tracing.load_xplane(str(harness.TRACE_DIR))
    events.update(load_engine(str(harness.TRACE_DIR)))
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    tr = reduce(events)
    records = engine_steps(eng)
    ctx = harness.Ctx(win, tr, cell.model, cell.config, peaks, 0.0)
    traced_host = [s.t1 - s.t0 for s in ctx.traced_steps()]
    in_window = [r for r in records if win.t_open <= r.t0 < win.t_close]
    traced = set(tr.engine_steps)
    by_phase: dict = {}
    for r in records:
        if r.step in traced:
            for k, v in r.phases.items():
                by_phase[k] = by_phase.get(k, 0.0) + v
    longest = sorted(in_window, key=lambda r: r.t0 - r.t1)[:3]
    out = {
        "workload": workload, "seed": seed,
        "device": device.describe(devs),
        "ttft_p90_ms": cell.reader("ttft_p90_ms")(ctx),
        "window_s": tr.window_s, "busy_s": tr.busy_s,
        "programs": tr.programs,
        "idle_by_span": tr.idle_by_span,
        "dispatch_by_phase": tr.dispatch_by_phase,
        "engine_steps": len(tr.engine_steps),
        "engine_idle_ms": engine_idle_ms(tr),
        "chunk_roofline": chunk_roofline(tr, records, cell.model,
                                         cell.config, peaks),
        "traced_step_s_mean": (statistics.fmean(traced_host)
                               if traced_host else None),
        "traced_phase_s_per_step": {
            k: v / max(1, len(tr.engine_steps)) for k, v in by_phase.items()},
        "longest_steps": [{"s": r.t1 - r.t0, "at": r.t0 - win.t_open,
                           "phases": r.phases, "admitted": r.admitted,
                           "chunks": len(r.chunks),
                           "decoded": len(r.decoded)} for r in longest],
    }
    harness.log("[trace] dispatches by engine phase "
                + json.dumps(tr.dispatch_by_phase))
    return out


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out = run_traced(args.workload, args.seed, args.seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
