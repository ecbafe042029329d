"""Benchmark driver: one section per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` style CSV blocks.

  PYTHONPATH=src python -m benchmarks.run            # quick tier
  PYTHONPATH=src python -m benchmarks.run --full     # paper scale
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale simulations (hours of CPU)")
    ap.add_argument("--only", default=None,
                    help="comma list: kernels,agg,table2,fig3,roofline")
    args = ap.parse_args()
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    t0 = time.perf_counter()
    if want("kernels"):
        print("== bench_kernels (name,us_per_call,max_err) ==", flush=True)
        from benchmarks import bench_kernels
        for name, us, err in bench_kernels.run():
            print(f"{name},{us:.1f},{err:.2e}")

    if want("agg"):
        print("== bench_agg_scale (n_params,chain_us,fused_us,speedup) ==",
              flush=True)
        from benchmarks import bench_agg_scale
        for p, c, f, s in bench_agg_scale.run():
            print(f"{p},{c:.0f},{f:.0f},{s:.2f}")

    if want("roofline"):
        print("== bench_roofline (from runs/roofline artifacts) ==",
              flush=True)
        from benchmarks import bench_roofline
        bench_roofline.main()

    if want("table2"):
        print("== bench_table2 (paper Table II) ==", flush=True)
        from benchmarks import bench_table2
        rows = bench_table2.run(quick=not args.full)
        print("method,final_acc,rounds,sim_hours")
        for r in rows:
            print(f"{r['method']},{r['final_acc']},{r['rounds']},"
                  f"{r['sim_hours']}")

    if want("fig3"):
        print("== bench_fig3 panel d (two HAPs) ==", flush=True)
        from benchmarks import bench_fig3
        res = bench_fig3.run("d", quick=not args.full)
        print("curve,final_acc")
        for name, r in res.items():
            print(f"{name},{r['final_acc']}")

    print(f"== benchmarks done in {time.perf_counter()-t0:.1f}s ==")


if __name__ == "__main__":
    main()
