"""90th percentile, over every request due in the window, of first token
delivered minus due time; a request that never got one counts as missing
(infinite), and a percentile that lands on one is not reported."""

import math

from bench.stats import percentile


def read(ctx):
    vals = [(r.times[0] - r.due) * 1e3 if r.times else math.inf
            for r in ctx.window.due_in_window()]
    p = percentile(vals, 90) if vals else math.inf
    return None if math.isinf(p) else p
