"""One run of one cell: set up, measure a window, check the tokens, print
the result line.

Set-up makes the weights on the device from the seed, builds the engine,
draws the whole schedule, and warms every program the window will use; it
ends when the window opens, and ``setup_s`` counts it from process start.
Compilations inside the window are counted and printed.  After the
window: the device's peak memory, the metrics, then the program's state is
freed and the reference checks a sample of the requests it served.

Everything but the last line of standard output goes to standard error;
its last lines are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass

import jax
import numpy as np
from jax import monitoring

from . import arrivals, correct, device, spec, tracing, window
from . import engine_adapter as ad

TRACE_DIR = spec.ROOT / ".bench_trace"
TRACE_SECONDS = 8.0         # the traced part: the window's last seconds


def use_checkout_cache() -> str:
    """Keep JAX's compilation cache at a fixed path inside the checkout,
    whatever the environment names: only a checkout's first run compiles,
    and two checkouts share nothing."""
    path = str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs lowered and compiled in this process, and the seconds the
    backend took; a program read from the persistent cache counts as
    compiled too, in a small share of the time."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.lowered = self.compiled = 0
        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.BACKEND:
            self.compiled += 1
            self.compile_s += duration

    def count(self) -> tuple:
        return self.lowered, self.compiled, self.compile_s


@functools.cache
def compile_counter() -> CompileCounter:
    """The process's one counter: JAX keeps every listener registered."""
    return CompileCounter()


class GcPauses:
    """Python's garbage collections: (host clock at the start, seconds)."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, _info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0))


@functools.cache
def gc_pauses() -> GcPauses:
    return GcPauses()


def log_stalls(win: window.Window) -> None:
    """Where the window's host loop stalled: its longest steps, and the
    garbage collections inside it."""
    longest = sorted(win.window_steps(), key=lambda s: s.t0 - s.t1)[:3]
    log("[window] longest steps: " + "; ".join(
        f"{s.t1 - s.t0:.3f} s at {s.t0 - win.t_open:.2f} s ({s.chunks} "
        f"chunks, {len(s.decode_positions)} decoding, {s.admitted} "
        f"admitted)" for s in longest))
    gcs = [d for t, d in gc_pauses().pauses
           if win.t_open <= t < win.t_close]
    log(f"[window] garbage collections {len(gcs)}, {sum(gcs):.3f} s, "
        f"longest {max(gcs, default=0.0):.3f} s")


@dataclass
class Ctx:
    """What a metric reader may read."""

    window: window.Window
    trace: tracing.Trace | None
    model: object
    conf: dict
    peaks: dict
    setup_s: float

    def program_ms(self, name: str):
        if self.trace is None or name not in self.trace.programs:
            return None
        calls, secs = self.trace.programs[name]
        return 1e3 * secs / calls

    def traced_steps(self) -> list:
        a, b = self.window.traced
        return self.window.steps[a:b]


def warm_up(eng, lengths, serve: dict) -> None:
    """Run every program the window will run, once: the host-side pieces
    of each prompt length in ``lengths``, and one request through two
    prompt chunks, decode across a block boundary, and retirement."""
    chunk = serve["prefill_chunk"]
    ad.warm_prompt_shapes(lengths, chunk)
    ad.submit(eng, np.zeros(chunk + 1, np.int32), serve["block_size"] + 4,
              "warm-up")
    while ad.has_work(eng):
        ad.step(eng)


def set_up(cell: spec.Cell, seed: int, lengths) -> tuple:
    """The program's config, its weights made on the device from the seed,
    and the engine with every program of the window warmed; logs the
    seconds and the compilations of each phase."""
    model, conf, serve = cell.model, cell.config, cell.config["serve"]
    cc = compile_counter()
    marks = [(time.perf_counter(), cc.count())]
    cfg = ad.program_config(model, conf)
    params = jax.block_until_ready(ad.build_params(cfg, model, conf)(seed))
    marks.append((time.perf_counter(), cc.count()))
    eng = ad.build_engine(cfg, params, serve)
    marks.append((time.perf_counter(), cc.count()))
    warm_up(eng, lengths, serve)
    marks.append((time.perf_counter(), cc.count()))
    parts = [f"{name} {t1 - t0:.1f} s ({c1[1] - c0[1]} programs, "
             f"{c1[2] - c0[2]:.1f} s compiling or loading)"
             for name, (t0, c0), (t1, c1) in zip(
                 ("weights", "engine", "warm-up"), marks, marks[1:])]
    log("[setup] " + ", ".join(parts))
    return cfg, params, eng


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devs: list, peaks: dict, t_process: float) -> dict:
    model, conf, serve = cell.model, cell.config, cell.config["serve"]
    traffic = cell.traffic
    cfg, params, eng = set_up(cell, seed,
                              arrivals.prompt_lengths(traffic, seconds))
    sched = arrivals.schedule(traffic, seed, seconds, cfg.vocab_size,
                              serve["kv_len"])

    drv = window.Runner(eng, model, conf, serve["prefill_chunk"])
    length = min(TRACE_SECONDS, 0.5 * seconds)
    tracer = (tracing.Tracer(str(TRACE_DIR), seconds - length, length)
              if trace else None)
    setup_s = time.time() - t_process
    gc_pauses()
    before = compile_counter().count()
    if traffic["loop"] == "closed":
        win = window.run_closed(drv, sched, int(traffic["clients"]), seconds,
                                tracer)
    else:
        win = window.run_open(drv, sched, seconds, tracer)
    lowered, compiled, _ = (a - b for a, b in
                            zip(compile_counter().count(), before))
    if tracer is not None:
        tracer.stop()
    dev = device.describe(devs)

    due = win.due_in_window()
    lateness = [r.submitted - r.due for r in due]
    log(f"[window] requests due {len(due)}, steps {len(win.window_steps())}, "
        f"generator lateness p50 {1e3 * float(np.median(lateness)):.3f} ms "
        f"max {1e3 * max(lateness):.3f} ms")
    log(f"[window] compilations inside the window: lowered {lowered}, "
        f"compiled {compiled}")
    log_stalls(win)
    log(f"[device] peak_bytes_in_use {dev['memory_peak_bytes']}")

    tr = None
    if trace:
        tr = tracing.reduce(tracing.load_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev["busy_s"], dev["window_s"] = tr.busy_s, tr.window_s
        n_dec = sum(1 for s in win.steps[win.traced[0]:win.traced[1]]
                    if s.decode_positions)
        log(f"[trace] window {tr.window_s:.3f} s, busy {tr.busy_s:.3f} s, "
            f"programs {json.dumps(tr.programs)}, decode steps on the host "
            f"{n_dec}")
    ctx = Ctx(win, tr, model, conf, peaks, setup_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the reference runs once the program's state is gone
    served = ad.served_tokens(eng)
    prompts = {rid: sched[r.idx].prompt for rid, r in win.reqs.items()}
    del eng, drv, params, ctx
    gc.collect()
    ck = conf["correct"]
    sample = correct.pick_sample(served, win.reqs, prompts, seed,
                                 ck["sample_requests"], ck["reference_len"])
    ref = correct.Reference(model, conf, ck["sample_requests"],
                            ck["reference_len"])
    t_ref = time.perf_counter()
    gaps = ref.gaps(seed, sample) if sample else np.asarray([])
    log(f"[check] reference over {len(sample)} requests, {gaps.size} served "
        f"tokens, {time.perf_counter() - t_ref:.1f} s")
    gap = float(gaps.max()) if gaps.size else None
    limit = ck["max_logit_gap"]
    ok = (gap is not None and limit is not None and gap <= limit
          and correct.in_vocab(sample, cfg.vocab_size))
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "tokens_compared": {"value": int(gaps.size), "limit": 1}}

    failed = (0 if traffic["loop"] == "closed"
              else sum(1 for r in due if not r.times))
    result = {"correct": ok, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": dev}
    if tr is not None:
        result["breakdown"] = {"device_ops": tr.top_ops,
                               "idle_gaps": tr.idle_by_span}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv, t_process: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_checkout_cache()
    cell = spec.load_cell(args.workload)
    try:
        devs, peaks = device.require_chips(cell.chips)
    except device.NoChip as e:
        log(f"[device] {e}")
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                      peaks, t_process)
    print(json.dumps(result), flush=True)
    return 0
