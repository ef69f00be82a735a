"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per table (scaffold
contract) and saves JSON artifacts under artifacts/bench/.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    from . import (fig2_mixed_precision, roofline_table, table1_granularity,
                   table2_weight_only, table3_full_quant, table4_cost,
                   table5_calib_speed, table6_deploy)

    tables = [
        ("roofline_table", roofline_table.main),  # instant: reads dry-run artifacts
        ("table1_granularity", table1_granularity.main),
        ("table2_weight_only", table2_weight_only.main),
        ("table3_full_quant", table3_full_quant.main),
        ("table4_cost", table4_cost.main),
        ("table5_calib_speed", table5_calib_speed.main),
        ("table6_deploy", table6_deploy.main),
        ("fig2_mixed_precision", fig2_mixed_precision.main),
    ]
    use_compile_cache()
    only = sys.argv[1:] if len(sys.argv) > 1 else None
    for name, fn in tables:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            fn()
            print(f"===== {name} done in {time.time()-t0:.0f}s =====")
        except Exception as e:  # one table must not sink the suite
            print(f"===== {name} FAILED after {time.time()-t0:.0f}s: "
                  f"{type(e).__name__}: {e} =====")


if __name__ == "__main__":
    main()
