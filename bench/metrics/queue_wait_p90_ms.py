"""Scheduler: 90th percentile of due time to admission into a lane (the
start of the engine step that admitted it), over the requests due in the
window; one never admitted counts as missing."""

import math

from bench.stats import percentile


def read(ctx):
    vals = [(r.admitted - r.due) * 1e3 if r.admitted is not None
            else math.inf for r in ctx.window.due_in_window()]
    p = percentile(vals, 90) if vals else math.inf
    return None if math.isinf(p) else p
