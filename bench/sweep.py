"""Rate sweep of an open-loop cell: where its knee lies.

    python bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 2,4,6,8

One process, one warm engine; for each offered rate the cell's mix runs
one window and one line reports what was offered and what came back:
requests handed over and finished by the close, time to first token p50
and p90, time per output token p90, tokens per second, and the backlog
at the close (handed over, not yet admitted).  The knee is the highest
rate at which the backlog stays near zero and the tails stay flat; the
cell's rate is set once from it (PERF.md).
"""

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)

    import numpy as np

    from bench import harness, latency, traffic

    _, cell = harness.find_cell(ROOT, a.workload)
    harness.enable_cache(ROOT)
    harness.device_info(int(cell.entry["chips"]))
    prep = harness.prepare(cell, a.seed)
    for rate in (float(r) for r in a.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"] = {"process": "poisson", "rate_per_s": rate}
        sched = traffic.generate(mix, a.seed, a.seconds, prep.cfg.vocab_size)
        s, stats, compiles, _ = harness.measure(prep, sched, a.seconds)
        fin_in = int(np.sum(s.t_last[s.finished()] <= s.t_close))
        backlog = int(np.sum(s.admit_start[s.attempted()] > s.t_close)
                      + np.sum(np.isnan(s.admit_start[s.attempted()])))
        print(json.dumps({
            "rate": rate, "handed": int(s.attempted().size),
            "finished_by_close": fin_in, "backlog_at_close": backlog,
            "ttft_p50_ms": latency.tail_ms(latency.ttft_s(s), 50),
            "ttft_p90_ms": latency.tail_ms(latency.ttft_s(s), 90),
            "tpot_p90_ms": latency.tail_ms(latency.tpot_s(s), 90),
            "queue_wait_p90_ms": latency.tail_ms(latency.queue_wait_s(s), 90),
            "tokens_per_s": s.tokens_in_window / s.window_s,
            "admissions": stats["admissions"],
            "windows": stats["decode_dispatches"],
            "compiles_in_window": compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
