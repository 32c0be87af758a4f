"""The knee: the highest rate at which, and below which, every window
held its backlog and its time to first token."""

import pytest

from bench import knee


def _rows(*rows):
    return [{"rate_per_s": rate, "seed": seed, "backlog_at_close": backlog,
             "ttft_p90_ms": ttft} for rate, seed, backlog, ttft in rows]


# the sweep of minicpm-2b.prefill-heavy on one v5e chip, 51 s windows
SWEEP = _rows((0.3, 11, 0, 1907.2), (0.35, 11, 0, 2725.0),
              (0.4, 11, 1, 1832.7), (0.45, 11, 0, 5292.1),
              (0.5, 11, 0, 6110.7), (0.55, 11, 4, 9541.4),
              (0.3, 12, 0, 1919.1), (0.35, 12, 0, 2174.4),
              (0.4, 12, 1, 1813.0), (0.45, 12, 0, 5224.8),
              (0.5, 12, 0, 5970.8), (0.55, 12, 4, 9348.0))


@pytest.mark.parametrize("rows,want", [
    (SWEEP, 0.4),
    # a backlog of two at the close fails the rate
    (_rows((0.3, 1, 0, 1000.0), (0.4, 1, 2, 1100.0)), 0.3),
    # a rate that holds above one that failed does not count
    (_rows((0.3, 1, 0, 1000.0), (0.4, 1, 0, 2500.0),
           (0.5, 1, 0, 1900.0)), 0.3),
    # every seed has to hold, each against its own lowest rate
    (_rows((0.3, 1, 0, 1000.0), (0.4, 1, 0, 1500.0),
           (0.3, 2, 0, 500.0), (0.4, 2, 0, 1500.0)), 0.3),
])
def test_knee_is_below_the_lowest_failing_rate(rows, want):
    assert knee.knee(rows) == want
