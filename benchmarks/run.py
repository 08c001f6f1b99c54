"""Benchmark harness — one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (harness contract).  Usage:

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run fig5 roofline
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    which = set(sys.argv[1:]) or {"table1", "fig4", "fig5", "roofline"}
    print("name,us_per_call,derived")

    def emit(name: str, us: float, derived: str = "") -> None:
        print(f"{name},{us:.1f},{derived}", flush=True)

    if "table1" in which:
        from benchmarks import table1_conversion
        table1_conversion.run(emit)
    if "fig4" in which:
        from benchmarks import fig4_relu
        fig4_relu.run(emit)
    if "fig5" in which:
        from benchmarks import fig5_throughput
        fig5_throughput.run(emit)
    if "roofline" in which:
        from benchmarks import roofline
        roofline.run(emit)


if __name__ == "__main__":
    main()
