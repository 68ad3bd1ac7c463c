"""Benchmark harness: one module per paper table (+ roofline summary).
Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    sys.path.insert(0, _ROOT)
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (async_overlap, fleet_scaleout, kernel_tuner,
                            roofline, scale_soak, scrub_overhead,
                            table1_overhead, table2_shell, table3_matmul,
                            table4_multitenant)

    modules = [
        ("table1", table1_overhead),
        ("table2", table2_shell),
        ("table3", table3_matmul),
        ("table4", table4_multitenant),
        ("fleet", fleet_scaleout),
        ("scale_soak", scale_soak),
        ("async_overlap", async_overlap),
        ("kernel_tuner", kernel_tuner),
        ("scrub_overhead", scrub_overhead),
        ("roofline", roofline),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        try:
            for row_name, value, derived in mod.run():
                print(f"{row_name},{value:.4f},{str(derived).replace(',', ';')}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}.FAILED,0,{type(e).__name__}: "
                  f"{str(e)[:120].replace(chr(10), ' ')}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
