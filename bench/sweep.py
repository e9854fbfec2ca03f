#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate served
without a growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 0.3,0.5 --seeds 1,2 --seconds 51

In one process, one run per rate and seed, with the cell's traffic mix at
that rate and everything else (the pre-roll included) as ``bench/run.py``
runs it.  Per run it prints the requests due in the window, admitted and
completed, the slots held at the opening and on average, and the backlog:
requests due but not yet admitted, at the opening and at the end.  A rate
is sustained when on every seed the backlog grows by at most one request
over the window; the knee is the highest sustained rate.  The cell's rate
is then written into its traffic file by hand.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
if sys.path[1] == str(Path(__file__).resolve().parent):
    sys.path.pop(1)

from bench import loop, run  # noqa: E402


def backlog(w: loop.Window, at: float) -> int:
    """Requests due by ``at`` and not admitted before it."""
    return sum(1 for r in w.reqs if r.due <= at
               and (r.admitted is None or r.admitted >= at))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Knee sweep of an open-loop cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = run.spec.load_cell(run.ROOT, args.workload)
    with open(run.ROOT / "bench" / "peaks.json") as f:
        peaks = json.load(f)
    run.setup_jax()
    try:
        devices, peak = run.chips_for(cell, peaks)
    except run.NoChip as e:
        print(f"[sweep] {e}", file=sys.stderr, flush=True)
        return 2
    mix = dict(cell.traffic)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(mix, rate_per_s=rate)
        growth = []
        for seed in (int(x) for x in args.seeds.split(",")):
            out = run.run_cell(cell, seed, args.seconds, False, devices, peak,
                               t_start=time.perf_counter())
            w = out["window"]
            due = w.due_in_window()
            held_open, held_mean = w.slots_held()
            growth.append(backlog(w, w.end) - backlog(w, 0.0))
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "due": len(due),
                "admitted": sum(r.admitted is not None for r in due),
                "completed": sum(r.done and not r.shed for r in due),
                "slots_held_at_open": held_open, "slots_held_mean": held_mean,
                "backlog_at_open": backlog(w, 0.0), "backlog_at_end": backlog(w, w.end),
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "correct": out["correct"],
            }), flush=True)
        sustained = max(growth) <= 1
        if sustained:
            knee = rate
        print(json.dumps({"rate_per_s": rate, "backlog_growth": growth,
                          "sustained": sustained}), flush=True)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
