"""Run every paper-figure benchmark: ``python -m benchmarks.run [--quick]``.

One module per paper table/figure (Fig. 8-14) + kernel benches.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="reduced dataset sizes (CI-speed)")
    p.add_argument("--only", default=None,
                   help="comma-separated subset: fig8,fig9,...,kernels")
    args = p.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (chaos_bench, fig8_datasets, fig9_skew,
                   fig10_reduce_tasks, fig11_sorted, fig12_map_output,
                   fig13_scaling, fig_sn_window, kernel_bench,
                   mesh_bench, schedule_bench, serve_bench, steal_bench,
                   tune_bench)

    suites = {
        "fig8": lambda: fig8_datasets.run(quick=args.quick),
        "fig9": lambda: fig9_skew.run(quick=args.quick),
        "fig10": lambda: fig10_reduce_tasks.run(quick=args.quick),
        "fig11": lambda: fig11_sorted.run(quick=args.quick),
        "fig12": lambda: fig12_map_output.run(quick=args.quick),
        "fig13": lambda: fig13_scaling.run(quick=args.quick),
        "sn_window": lambda: fig_sn_window.run(quick=args.quick),
        "kernels": lambda: kernel_bench.run(quick=args.quick),
        "schedule": lambda: schedule_bench.run(quick=args.quick),
        "serve": lambda: serve_bench.run(quick=args.quick),
        "mesh": lambda: mesh_bench.run(quick=args.quick),
        "chaos": lambda: chaos_bench.run(quick=args.quick),
        "steal": lambda: steal_bench.run(quick=args.quick),
        "tune": lambda: tune_bench.run(quick=args.quick),
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    t0 = time.time()
    for name, fn in suites.items():
        if name not in only:
            continue
        print(f"\n######## {name} ########", flush=True)
        fn()
    print(f"\ntotal bench time: {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
