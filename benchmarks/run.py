"""Benchmark harness: one module per paper table/figure + kernel/roofline.

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

Usage: PYTHONPATH=src python -m benchmarks.run [--scale 0.2] [--only fig3,...]
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.2,
                    help="size multiplier (1.0 ~ small-GPU scale; CPU default 0.2)")
    ap.add_argument("--only", default="",
                    help="comma list: engine,copy,capacity,serving,fig3,"
                         "fig4,fig5,fig6,kernel,roofline")
    args = ap.parse_args()

    from repro.api import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (capacity_bench, common, copy_cost,
                            engine_bench, fig3_chunks,
                            fig4_multidevice, fig5_scaling, fig6_outliers,
                            kernel_bench, roofline_table, serving_bench)

    mods = {
        "engine": engine_bench, "copy": copy_cost,
        "capacity": capacity_bench,
        "serving": serving_bench,
        "fig3": fig3_chunks, "fig4": fig4_multidevice, "fig5": fig5_scaling,
        "fig6": fig6_outliers, "kernel": kernel_bench,
        "roofline": roofline_table,
    }
    only = [x for x in args.only.split(",") if x]
    common.emit_header()
    for name, mod in mods.items():
        if only and name not in only:
            continue
        mod.run(scale=args.scale)


if __name__ == "__main__":
    main()
