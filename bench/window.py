"""The measured window: send the schedule, step the engine, stamp every
token on the host clock when its step returns.

Open loop: each request is submitted once its due time has passed, and
timed from that due time, so a stall counts against every request queued
behind it.  Closed loop: each client sends its next request when its last
one finishes.  After an open window closes, the engine keeps stepping,
uncounted in any rate, until every request due in the window has its
first token.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from . import engine_adapter as ad

DRAIN_LIMIT_S = 120.0


@dataclass
class ReqRec:
    idx: int
    prompt_len: int
    max_new: int
    due: float = 0.0                  # host clock
    submitted: float = 0.0
    admitted: float | None = None     # start of the step that admitted it
    times: list = field(default_factory=list)   # one stamp per token
    chunks: int = 0


@dataclass
class StepRec:
    t0: float
    t1: float
    flops: float
    decode_positions: list            # new-token positions of active lanes
    chunks: int
    admitted: int                     # requests that entered a lane


@dataclass
class Window:
    t_open: float
    t_close: float
    reqs: dict                        # rid -> ReqRec
    steps: list                       # StepRec, window and drain
    traced: tuple | None = None       # (first, last + 1) StepRec indices

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def window_steps(self) -> list:
        return [s for s in self.steps if s.t1 <= self.t_close]

    def due_in_window(self) -> list:
        return [r for r in self.reqs.values() if r.due < self.t_close]


def chunk_flops(model, conf: dict, start: int, valid: int,
                prompt_len: int) -> float:
    """FLOPs the ``valid`` prompt positions from ``start`` need; the last
    prompt position also needs the unembedding (its logits give the first
    token).  ``token_flops`` is affine in the position."""
    f0 = model.token_flops(conf, start, False)
    slope = model.token_flops(conf, start + 1, False) - f0
    total = valid * f0 + slope * valid * (valid - 1) / 2
    if start + valid >= prompt_len:
        last = prompt_len - 1
        total += (model.token_flops(conf, last, True)
                  - model.token_flops(conf, last, False))
    return total


class Runner:
    """Steps one engine and keeps the host-side record of every request."""

    def __init__(self, eng, model, conf: dict, chunk: int):
        self.eng, self.model, self.conf, self.chunk = eng, model, conf, chunk
        self.reqs: dict = {}
        self.steps: list = []
        self.live: set = set()        # admitted, not yet finished
        self._rids = itertools.count()    # request ids, unique per engine
        self._cursor = [len(eng.scheduler.finished)]

    def submit(self, req, due: float) -> int:
        rid = next(self._rids)
        with TraceAnnotation("submit"):
            ad.submit(self.eng, req.prompt, req.max_new, rid)
        self.reqs[rid] = ReqRec(req.idx, req.prompt_len, req.max_new, due,
                                time.perf_counter())
        return rid

    def step(self) -> list:
        """One engine step; returns the rids that finished in it."""
        before = {rid: len(self.reqs[rid].times) for rid in self.live}
        t0 = time.perf_counter()
        with TraceAnnotation("engine_step"):
            ad.step(self.eng)
        t1 = time.perf_counter()
        with TraceAnnotation("collect"):
            return self._collect(before, t0, t1)

    def _collect(self, before: dict, t0: float, t1: float) -> list:
        seen = ad.progress(self.eng, self._cursor)
        flops, decode_pos, chunks, admitted = 0.0, [], 0, 0
        for rid, n in seen:
            r = self.reqs[rid]
            if r.admitted is None:
                r.admitted = t0
                self.live.add(rid)
                admitted += 1
            had = before.get(rid, 0)
            if had:                                   # decoded one token
                decode_pos.append(r.prompt_len + had - 1)
            else:                                     # ran one prompt chunk
                start = r.chunks * self.chunk
                valid = min(self.chunk, r.prompt_len - start)
                flops += chunk_flops(self.model, self.conf, start, valid,
                                     r.prompt_len)
                r.chunks += 1
                chunks += 1
                if n > 1:        # the last chunk's lane decoded in this step
                    decode_pos.append(r.prompt_len)
            r.times.extend([t1] * (n - len(r.times)))
        if decode_pos:
            flops += self.model.decode_cost(self.conf, decode_pos)[0]
        finished = [rid for rid in self.live
                    if len(self.reqs[rid].times) >= self.reqs[rid].max_new]
        self.live -= set(finished)
        self.steps.append(StepRec(t0, t1, flops, decode_pos, chunks,
                                  admitted))
        return finished

    def idle(self, until: float) -> None:
        with TraceAnnotation("idle_wait"):
            delay = until - time.perf_counter()
            if delay > 0:
                time.sleep(delay)

    def drain(self) -> None:
        """Step until nothing is queued or running (not measured)."""
        while ad.has_work(self.eng):
            self.step()


def run_open(drv: Runner, schedule: list, seconds: float,
             tracer=None) -> Window:
    pending = deque(schedule)
    t_open = time.perf_counter()
    t_close = t_open + seconds
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if tracer is not None:
            tracer.tick(now - t_open, len(drv.steps))
        while pending and t_open + pending[0].due <= now:
            req = pending.popleft()
            drv.submit(req, t_open + req.due)
        if ad.has_work(drv.eng):
            drv.step()
        else:
            drv.idle(min(t_close, t_open + pending[0].due) if pending
                     else t_close)
    if tracer is not None:
        tracer.close(len(drv.steps))
    for req in pending:                       # due in the window, not sent
        drv.submit(req, t_open + req.due)
    due = [rid for rid, r in drv.reqs.items() if r.due < t_close]
    deadline = time.perf_counter() + DRAIN_LIMIT_S
    while (ad.has_work(drv.eng) and time.perf_counter() < deadline
           and any(not drv.reqs[rid].times for rid in due)):
        drv.step()
    return Window(t_open, t_close, drv.reqs, drv.steps,
                  None if tracer is None else tracer.steps)


def run_closed(drv: Runner, pool: list, clients: int, seconds: float,
               tracer=None) -> Window:
    t_open = time.perf_counter()
    t_close = t_open + seconds
    sent = 0

    def send(at: float) -> None:
        nonlocal sent
        drv.submit(pool[sent % len(pool)], at)
        sent += 1

    for _ in range(clients):
        send(t_open)
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        if tracer is not None:
            tracer.tick(now - t_open, len(drv.steps))
        for _ in drv.step():
            send(drv.steps[-1].t1)
    if tracer is not None:
        tracer.close(len(drv.steps))
    return Window(t_open, t_close, drv.reqs, drv.steps,
                  None if tracer is None else tracer.steps)
