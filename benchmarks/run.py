"""Benchmark harness: one module per paper table/figure (+ beyond-paper).
Prints ``name,us_per_call,derived`` CSV at the end (stdout also carries the
human-readable tables)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from . import (calibration, fig4_downsampling, fig5_cdf,
                   fig6_homogeneous, roofline_table, scheduler_e2e,
                   table2_microbench, table45_factors, table6_heterogeneous,
                   tpu_cells)
    mods = [
        ("table2_microbench", table2_microbench),
        ("fig4_downsampling", fig4_downsampling),
        ("fig5_cdf", fig5_cdf),
        ("fig6_homogeneous", fig6_homogeneous),
        ("table45_factors", table45_factors),
        ("table6_heterogeneous", table6_heterogeneous),
        ("tpu_cells", tpu_cells),
        ("roofline_table", roofline_table),
        ("scheduler_e2e", scheduler_e2e),
        ("calibration", calibration),
    ]
    rows = []
    failed = 0
    for name, mod in mods:
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        try:
            rows.extend(mod.run())
        except Exception as e:
            # the sweep must keep going past any one table's failure (the
            # modules call into arbitrary kernels, so the catch stays
            # broad by design) — but the cause is bound, printed, and
            # carried into the CSV row instead of silently discarded
            failed += 1
            traceback.print_exc()
            rows.append((f"{name}.FAILED", 0.0, repr(e)))
    print("\n--- CSV (name,us_per_call,derived) ---")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
