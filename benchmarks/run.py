"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines; JSON records land in
experiments/paper/.  Scale up with REPRO_BENCH_FULL=1.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig5]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (distill_bench, fig2_local_epochs,
                        fig4_heterogeneous, fig5_distill_sources,
                        fig6_distill_steps, kernels_bench, roofline_report,
                        round_engine_bench, table1_rounds_to_target,
                        table2_normalization, table3_dropworst,
                        table4_lowbit, table5_init_ablation,
                        table6_local_adam, table7_distill_optimizer)
from repro.common.compile_cache import use_compile_cache

MODULES = {
    "distill": distill_bench,
    "table1": table1_rounds_to_target,
    "table2": table2_normalization,
    "table3": table3_dropworst,
    "table4": table4_lowbit,
    "table5": table5_init_ablation,
    "table6": table6_local_adam,
    "table7": table7_distill_optimizer,
    "fig2": fig2_local_epochs,
    "fig4": fig4_heterogeneous,
    "fig5": fig5_distill_sources,
    "fig6": fig6_distill_steps,
    "kernels": kernels_bench,
    "roofline": roofline_report,
    "round_engine": round_engine_bench,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else list(MODULES)
    use_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for name in names:
        mod = MODULES[name]
        t0 = time.time()
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"{name},{(time.time()-t0)*1e6:.0f},FAILED:{type(e).__name__}")
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
