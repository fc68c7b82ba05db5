"""Benchmark orchestrator — one benchmark per paper table/figure + kernel
µbenches + the roofline report.

    PYTHONPATH=src python -m benchmarks.run          # full
    PYTHONPATH=src python -m benchmarks.run --fast   # CI-scale

Emits ``name,us_per_call,derived`` CSV lines; JSON artifacts land in
artifacts/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced grids/steps (CI)")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of benches to run, e.g. "
                         "'kernels,stream' "
                         "(table1|fig2|fig4|kernels|roofline|stream|"
                         "stream_adapt)")
    args = ap.parse_args()
    from repro.utils import init_compile_cache
    init_compile_cache()

    from benchmarks import (fig2_bandwidth_energy, fig4_leakage, kernel_bench,
                            roofline_report, stream_adapt, stream_serving,
                            table1_acc_traintime)

    benches = {
        "table1": table1_acc_traintime.run,
        "fig2": fig2_bandwidth_energy.run,
        "fig4": fig4_leakage.run,
        "kernels": kernel_bench.run,
        "roofline": roofline_report.run,
        "stream": stream_serving.run,
        "stream_adapt": stream_adapt.run,
    }
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in benches]
        if unknown:
            print(f"error: unknown bench(es) {unknown}; choose from "
                  f"{sorted(benches)}", file=sys.stderr)
            return 2
        benches = {n: benches[n] for n in names}

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches.items():
        t0 = time.perf_counter()
        try:
            fn(fast=args.fast)
            print(f"bench/{name},{(time.perf_counter() - t0) * 1e6:.0f},ok",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"bench/{name},-,FAILED:{type(e).__name__}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
