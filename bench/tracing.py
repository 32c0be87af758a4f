"""The traced run: take a profiler trace of part of the window, keep the
events the metrics need, and reduce them.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small dict of
events (nanoseconds on one clock):

    {"host": [[span, start, dur], ...],          # the benchmark's spans
     "devices": [{"ops": [[name, start, dur], ...],       # XLA Ops line
                  "modules": [[name, start, dur], ...]}]}  # XLA Modules line

``reduce`` works on that dict alone, so a trimmed recording of one chip
run is enough to test it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from dataclasses import dataclass

import jax

HOST_SPANS = ("submit", "engine_step", "idle_wait", "collect")
WINDOW_SPAN = "traced_window"


class Tracer:
    """Starts the profiler ``start_s`` into the window and marks the traced
    part ``length_s`` long (or up to the window's close), between engine
    steps.  ``stop`` ends the profiler once the run has left the window:
    collecting the trace takes seconds, and the window must not wait."""

    def __init__(self, out_dir: str, start_s: float, length_s: float):
        self.out_dir, self.start_s, self.length_s = out_dir, start_s, length_s
        self._span = None
        self.steps = None                  # (first, last + 1) step indices

    def tick(self, elapsed: float, n_steps: int) -> None:
        if self.steps is None and elapsed >= self.start_s:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            jax.profiler.start_trace(self.out_dir)
            self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._span.__enter__()
            self.steps = (n_steps, None)
        elif (self._span is not None
              and elapsed >= self.start_s + self.length_s):
            self.close(n_steps)

    def close(self, n_steps: int) -> None:
        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        self.steps = (self.steps[0], n_steps)

    def stop(self) -> None:
        if self.steps is not None:
            jax.profiler.stop_trace()


def op_label(name: str) -> str:
    """A device op's name, result shape and opcode, without its operands:
    '%copy.5 = bf16[8,16]{1,0} copy(...)' -> 'copy.5 bf16[8,16] copy'."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    opcode = re.search(r"[})\]] ([a-z][\w\-]*)\(", rest)
    return " ".join(x for x in (head.lstrip("%"), shape and shape.group(1),
                                opcode and opcode.group(1)) if x)


def _base(name: str) -> str:
    """Program name without the run's unique suffix: 'jit_f(12)' -> 'jit_f'."""
    return re.sub(r"\(\d+\)$", "", name)


def load_xplane(out_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {out_dir}")
    pd = ProfileData.from_file(paths[-1])
    events = {"host": [], "devices": []}
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
            events["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events["host"] += [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events if e.name in wanted]
    return events


def leaf_ops(ops) -> list:
    """The ops that hold no other op: a ``while`` or ``call`` op spans the
    ops of its body on the same line, and would count their time twice."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[1] >= o[1] + o[2]
            or nxt[1] + nxt[2] > o[1] + o[2]]


def union(intervals) -> list:
    """Merge [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Trace:
    window_s: float
    busy_s: float                      # mean over devices
    programs: dict                     # base name -> [calls, device seconds]
    top_ops: list                      # [[name, seconds]], most first
    idle_by_span: list                 # [[host span, seconds]], most first
    devices: int


def reduce(events: dict, top: int = 10) -> Trace:
    host = events["host"]
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(h[1] for h in host)
        hi = max(h[1] + h[2] for h in host)
    spans = sorted(([s, s + d, n] for n, s, d in host if n in HOST_SPANS),
                   key=lambda x: x[0])
    starts = [x[0] for x in spans]
    n = max(1, len(events["devices"]))
    busy, programs, ops, idle = [], {}, {}, {}
    for dev in events["devices"]:
        merged = _clip(union([[s, s + d] for _, s, d in dev["ops"]]), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, d in dev["modules"]:
            if lo <= s < hi:
                p = programs.setdefault(_base(name), [0, 0.0])
                p[0] += 1
                p[1] += d / 1e9
        for name, s, d in leaf_ops(dev["ops"]):
            if lo <= s < hi:
                label = op_label(name)
                ops[label] = ops.get(label, 0.0) + d / 1e9
        gaps, prev = [], lo
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
        for g0, g1 in gaps:
            _split(spans, starts, g0, g1, idle, 1e-9 / n)
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return Trace(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n,
                 programs=programs, top_ops=rank(ops),
                 idle_by_span=rank(idle),
                 devices=len(events["devices"]))


def _split(spans, starts, g0, g1, into: dict, scale: float) -> None:
    """Share the idle gap [g0, g1] out among the host spans it overlaps
    (the benchmark's spans follow one another); what none covers is
    "other"."""
    left = g1 - g0
    i = bisect.bisect_left(starts, g1) - 1
    while i >= 0 and spans[i][1] > g0:
        s, e, name = spans[i]
        ov = min(e, g1) - max(s, g0)
        if ov > 0:
            into[name] = into.get(name, 0.0) + ov * scale
            left -= ov
        i -= 1
    if left > 0:
        into["other"] = into.get("other", 0.0) + left * scale
