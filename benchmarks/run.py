"""Benchmark aggregator: one section per paper table/figure + the roofline.

Prints ``name,...`` CSV lines; exits nonzero on correctness failures.

``--smoke`` runs every section at reduced sizes with perf assertions off —
a fast CI gate that catches harness breakage (import errors, solver/oracle
drift, dispatch regressions) without paying full benchmark wall-clock.
"""
from __future__ import annotations

import argparse


def main(smoke: bool = False, check_dispatch: bool = False) -> None:
    from benchmarks import dp_zoo_bench, mcm_bench, roofline, table1_sdp

    if smoke:
        print("# smoke mode: reduced sizes, correctness checks only")
    print("# Table I — S-DP implementations (paper §III-B)")
    if smoke:
        table1_sdp.run(sizes=[(2**10, 2**4), (2**11, 2**5)], check_perf=False)
    else:
        table1_sdp.run()
    print("# MCM — pipeline vs wavefront vs blocked (paper §IV)")
    # smoke sizes stay multiples of the blocked solver's tile (16)
    mcm_bench.run(sizes=[16, 32, 64] if smoke else None)
    print("# DP zoo — problems × backends × sizes (repro.dp)")
    # --check-dispatch calibrates every cell first (measured-cost dispatch),
    # then fails on post-calibration regret > gates (DESIGN.md §6)
    if smoke:
        dp_zoo_bench.run(out_path="", sizes=(8, 12), batch=4,
                         calibrate=check_dispatch,
                         check_dispatch=check_dispatch)
    else:
        dp_zoo_bench.run(calibrate=check_dispatch,
                         check_dispatch=check_dispatch)
    print("# Roofline — dry-run derived terms (EXPERIMENTS.md §Roofline)")
    roofline.run()


if __name__ == "__main__":
    from repro.jax_cache import use_persistent_cache

    use_persistent_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes, skip speedup-threshold assertions "
                         "(CI gate)")
    ap.add_argument("--check-dispatch", action="store_true",
                    help="calibrate the dp zoo cells, then gate on dispatch "
                         "regret (median ≤ 1.5×, every cell ≤ 3×)")
    args = ap.parse_args()
    main(smoke=args.smoke, check_dispatch=args.check_dispatch)
