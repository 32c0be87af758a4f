"""Find the highest rate an open-loop cell sustains: one set-up, then a
window at each rate for each seed, the queue drained between them.

    python3 bench/knee.py --workload <cell> --rates 0.3,0.4,0.5 --seeds 1,2

Each window is as long as a benchmark run's (``run_seconds``) and sends
the schedule the cell's generator gives at that rate; a seed picks the
prompts' token ids.  A window holds its rate when at most one request due
in it is still waiting for a lane at the close, and its time to first
token (p90) is within twice that of the same seed's window at the lowest
rate tried, which the sweep takes as the unloaded reading: start it well
below the knee.  The knee is the highest rate at which, and at every rate
below which, every window held; the cell's traffic file then takes 0.8 x
the knee as a fixed number.  This command is not part of a benchmark run.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not __package__:                     # run as a script
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import json  # noqa: E402

from bench import arrivals, device, spec, window  # noqa: E402
from bench.harness import (gc_pauses, log, log_stalls, set_up,  # noqa: E402
                           use_checkout_cache)
from bench.stats import percentile  # noqa: E402

MAX_BACKLOG = 1
MAX_SLOWDOWN = 2.0


def measure(drv, traffic, rate, seed, seconds, vocab, kv_len) -> dict:
    t = {**traffic, "arrivals": {**traffic["arrivals"], "rate_per_s": rate}}
    sched = arrivals.schedule(t, seed, seconds, vocab, kv_len)
    drv.reqs, drv.steps = {}, []
    gc_pauses()
    win = window.run_open(drv, sched, seconds)
    log_stalls(win)
    due = win.due_in_window()
    out = {
        "rate_per_s": rate, "seed": seed, "due": len(due),
        "backlog_at_close": sum(1 for r in due if r.admitted is None
                                or r.admitted > win.t_close),
        "ttft_p90_ms": percentile([(r.times[0] - r.due) * 1e3 if r.times
                                   else float("inf") for r in due], 90),
        "longest_step_s": max(s.t1 - s.t0 for s in win.window_steps()),
    }
    drv.drain()
    return out


def held(r: dict, unloaded: dict) -> bool:
    return (r["backlog_at_close"] <= MAX_BACKLOG
            and r["ttft_p90_ms"] <= MAX_SLOWDOWN * unloaded["ttft_p90_ms"])


def knee(results: list) -> float | None:
    """The highest rate tried at which, and at every lower rate tried,
    every window held (each seed against its own lowest rate)."""
    unloaded = {}
    for r in sorted(results, key=lambda r: r["rate_per_s"]):
        unloaded.setdefault(r["seed"], r)
    best = None
    for rate in sorted({r["rate_per_s"] for r in results}):
        if not all(held(r, unloaded[r["seed"]]) for r in results
                   if r["rate_per_s"] == rate):
            break
        best = rate
    return best


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1,2")
    args = ap.parse_args(argv)
    use_checkout_cache()
    cell = spec.load_cell(args.workload)
    seconds = spec.run_seconds()
    try:
        device.require_chips(cell.chips)
    except device.NoChip as e:
        log(f"[device] {e}")
        return 2
    serve = cell.config["serve"]
    rates = sorted(float(r) for r in args.rates.split(","))
    seeds = [int(s) for s in args.seeds.split(",")]
    lengths = set()
    for rate in rates:
        t = {**cell.traffic, "arrivals": {**cell.traffic["arrivals"],
                                          "rate_per_s": rate}}
        lengths |= set(arrivals.prompt_lengths(t, seconds))
    cfg, _, eng = set_up(cell, seeds[0], sorted(lengths))
    drv = window.Runner(eng, cell.model, cell.config, serve["prefill_chunk"])
    results = []
    for seed in seeds:
        for rate in rates:
            r = measure(drv, cell.traffic, rate, seed, seconds,
                        cfg.vocab_size, serve["kv_len"])
            log(f"[knee] {json.dumps(r)}")
            results.append(r)
    k = knee(results)
    print(json.dumps({"workload": cell.name, "seconds": seconds, "knee": k,
                      "rate_per_s": None if k is None else round(0.8 * k, 4),
                      "rates": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
