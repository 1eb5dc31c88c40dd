"""Readings that the correctness limits are set from.

    python bench/control.py --workload <name> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds <s>

Runs the cell (the timed path at the cell's own load, a short window)
once per seed in one process, the engine compiled once, and prints per
seed the widest served-token gap against the reference (the program's
reading).  On the control seeds the float8 control is put in the
program's place: the run's ``correct`` (``control_correct``, which has
to be false) and the widest gap of the tokens the control puts first.  The limit goes between the largest
program reading and the smallest control reading (PERF.md says where).
"""

import argparse
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload, seeds, control_seeds, seconds, *, root=ROOT,
             require_chip=True, log=None):
    from bench import harness

    held = types.SimpleNamespace(eng=None)
    out = []
    for seed in seeds:
        kw = {} if log is None else {"log": log}
        ctl = seed in control_seeds
        r = harness.run(workload, seed, seconds, False, root=root,
                        require_chip=require_chip, control=ctl,
                        prepared=held, **kw)
        row = {"seed": seed, "attempted": r["attempted"],
               "failed": r["failed"]}
        if ctl:
            row.update(control_correct=r["correct"],
                       control_max_gap_sd=r["checks"]["max_gap_sd"]["value"],
                       max_gap_sd=r["program_max_gap_sd"])
        else:
            row["correct"] = r["correct"]
            row.update({k: c["value"] for k, c in r["checks"].items()})
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    ctrl = {int(s) for s in a.control_seeds.split(",") if s}
    t0 = time.perf_counter()
    rows = readings(a.workload, seeds, ctrl, a.seconds)
    prog = [r["max_gap_sd"] for r in rows if "max_gap_sd" in r]
    cont = [r["control_max_gap_sd"] for r in rows if "control_max_gap_sd" in r]
    print(json.dumps({"workload": a.workload,
                      "program_max": max(prog) if prog else None,
                      "control_min": min(cont) if cont else None,
                      "control_ever_correct": any(
                          r.get("control_correct", False) for r in rows),
                      "seconds_total": time.perf_counter() - t0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
