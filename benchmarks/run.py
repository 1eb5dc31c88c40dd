"""Benchmark driver — one section per paper table/figure.

  rodinia      -> paper Fig. 11 (speedup) + Fig. 12 (energy) analogs
  delta_cdf    -> paper Fig. 5 (ΔTID CDF)
  roofline     -> §Roofline table from the dry-run artifacts (if present)

Prints ``name,us_per_call,derived`` CSV blocks.  These are CPU figures
and models, not device measurements; the on-chip serving benchmark is
``bench/`` (``BENCHMARK.json``).

``--smoke`` runs every section on tiny shapes with no timing loops — a
CI-speed regression check for the bench *paths* (import errors, dispatch
wiring fail loudly instead of rotting until the next real bench run).
"""

from __future__ import annotations

import argparse


def main(smoke: bool = False) -> None:
    from benchmarks import delta_cdf, rodinia

    print("== rodinia (paper Fig. 11/12 analog) ==")
    rodinia.main(smoke=smoke)
    print()
    print("== delta CDF (paper Fig. 5 analog) ==")
    delta_cdf.main()
    print()
    print("== roofline table (from dry-run artifacts) ==")
    try:
        from benchmarks import roofline_table

        roofline_table.main()
    except Exception as e:  # noqa: BLE001
        print(f"(roofline table unavailable: {e})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no timing loops")
    main(smoke=ap.parse_args().smoke)
