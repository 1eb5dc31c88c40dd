"""Run one cell of the serving benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, with
``--trace 1``, ``breakdown``; ``checks`` comes last, each number compared
beside its limit.  Off a chip (or with fewer chips than the cell asks for)
it prints no result and exits 2.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), root=ROOT, t_process=T_PROCESS,
                             trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    checks = result["checks"]
    for key, c in checks.items():
        print(f"{key}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
