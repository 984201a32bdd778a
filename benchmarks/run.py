"""Benchmark driver — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bench_error_dist    — Fig. S1 (error distributions vs tile/gain/noise)
  bench_quality_grid  — Table II analog (quality grid on a trained LM)
  bench_finetune      — Table III analog (QAT vs DNF + speedup)
  bench_energy        — Sec. VI (2.8x vs Rekhi et al.)
  bench_kernels       — Pallas ABFP kernel vs oracle
  roofline            — deliverable (g): reads the dry-run artifacts
"""

import sys
import time
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (
        bench_energy,
        bench_error_dist,
        bench_finetune,
        bench_kernels,
        bench_quality_grid,
        roofline,
    )

    suites = [
        ("bench_energy", bench_energy.run),
        ("bench_error_dist", bench_error_dist.run),
        ("bench_kernels", bench_kernels.run),
        ("bench_quality_grid", bench_quality_grid.run),
        ("bench_finetune", bench_finetune.run),
        ("roofline", roofline.run),
    ]
    rows: list = ["name,us_per_call,derived"]
    failures = []
    for name, fn in suites:
        t0 = time.time()
        try:
            fn(rows)
            rows.append(f"{name}_total,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((name, repr(e)))
            rows.append(f"{name}_total,{(time.time()-t0)*1e6:.0f},FAILED")
    print("\n".join(rows))
    if failures:
        print(f"FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
