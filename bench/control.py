#!/usr/bin/env python3
"""The readings a configuration's correctness limit is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

In one process, for each seed: one run of the cell as ``bench/run.py`` runs
it, then, on the same sampled requests, two readings of the widest logit
gap (see ``bench/reference.py``): the served tokens' (the program), and the
tokens the float8 control ranks first at the same positions.  The control's
tokens also go through the run's own check as if they had been served; its
verdict (``control_correct``) has to read false.  The lower reading is the
largest of the program's over the seeds, the upper one the smallest of the
control's; ``max_logit_gap`` in the configuration file lies between the
two.  The benchmark's own runs never run the control.
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

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Correctness readings of one cell.")
    ap.add_argument("--workload", required=True)
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
        print(f"[control] {e}", file=sys.stderr, flush=True)
        return 2
    served, control, verdicts = [], [], []
    t0 = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, args.seconds, False, devices, peak,
                           t_start=t0, control=True)
        t0 = time.perf_counter()
        s, c = out["readings"]["served"], out["readings"]["control"]
        served.append(max(s))
        control.append(max(c))
        verdicts.append(out["control"]["correct"])
        print(json.dumps({"seed": seed, "served_max_gap": served[-1],
                          "control_max_gap": control[-1], "tokens": len(s),
                          "correct": out["correct"],
                          "control_correct": out["control"]["correct"],
                          "control_check": out["control"]["check"],
                          "metrics": out["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(served),
                      "lower": max(served), "upper": min(control),
                      "served": served, "control": control,
                      "control_correct": verdicts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
