"""Read the numbers a cell's correctness limit is set from: the program's
widest logit gap on many seeds, and the float8 control's on a few, all in
one process, at the cell's own sizes and load.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3

Each seed gets its own weights, engine and schedule, a window as long as
a benchmark run's at the cell's load, and the reference over the same
sample a benchmark run compares.  This command is not part of a
benchmark run.
"""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not __package__:                     # run as a script
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402

from bench import arrivals, correct, device, spec, window  # noqa: E402
from bench import engine_adapter as ad  # noqa: E402
from bench.harness import (gc_pauses, log, log_stalls, set_up,  # noqa: E402
                           use_checkout_cache)


def readings(cell: spec.Cell, seeds: list, control: set,
             seconds: float) -> list[dict]:
    """One dict per seed: the program's widest gap, and the control's where
    the seed is in ``control``."""
    model, conf, serve, traffic = (cell.model, cell.config,
                                   cell.config["serve"], cell.traffic)
    ck = conf["correct"]
    ref = correct.Reference(model, conf, ck["sample_requests"],
                            ck["reference_len"])
    out = []
    for seed in seeds:
        cfg, params, eng = set_up(cell, seed,
                                  arrivals.prompt_lengths(traffic, seconds))
        sched = arrivals.schedule(traffic, seed, seconds, cfg.vocab_size,
                                  serve["kv_len"])
        drv = window.Runner(eng, model, conf, serve["prefill_chunk"])
        gc_pauses()
        if traffic["loop"] == "closed":
            win = window.run_closed(drv, sched, int(traffic["clients"]),
                                    seconds)
        else:
            win = window.run_open(drv, sched, seconds)
        log_stalls(win)
        served = ad.served_tokens(eng)
        prompts = {rid: sched[r.idx].prompt for rid, r in win.reqs.items()}
        sample = correct.pick_sample(served, win.reqs, prompts, seed,
                                     ck["sample_requests"],
                                     ck["reference_len"])
        del eng, drv, params
        gc.collect()
        t0 = time.perf_counter()
        gaps = ref.gaps(seed, sample)
        row = {"seed": seed, "requests": len(sample),
               "tokens": int(gaps.size), "program_gap": float(gaps.max()),
               "in_vocab": correct.in_vocab(sample, cfg.vocab_size)}
        if seed in control:
            row["control_gap"] = float(ref.gaps(seed, sample,
                                                control=True).max())
        row["reference_s"] = time.perf_counter() - t0
        log(f"[calibrate] {json.dumps(row)}")
        out.append(row)
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    use_checkout_cache()
    cell = spec.load_cell(args.workload)
    try:
        device.require_chips(cell.chips)
    except device.NoChip as e:
        log(f"[device] {e}")
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    seconds = spec.run_seconds()
    rows = readings(cell, seeds, control, seconds)
    prog = [r["program_gap"] for r in rows]
    ctl = [r["control_gap"] for r in rows if "control_gap" in r]
    print(json.dumps({"workload": cell.name, "seconds": seconds,
                      "program_gap_max": max(prog),
                      "control_gap_min": min(ctl) if ctl else None,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
