"""Benchmark harness — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (harness contract) and writes JSON
under benchmarks/results/ for EXPERIMENTS.md.

  PYTHONPATH=src python -m benchmarks.run            # CPU-scaled defaults
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale lengths
  PYTHONPATH=src python -m benchmarks.run --only fig6,table2
"""
from __future__ import annotations

import argparse
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)  # CAMEO math in f64, like the paper

from benchmarks import anomaly, cameo_suite, forecast, roofline  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

BENCHES = {
    "fig6": cameo_suite.bench_fig6_line_simplification,
    "fig7": cameo_suite.bench_fig7_lossy_baselines,
    "table2": cameo_suite.bench_table2_bits_per_value,
    "fig8": cameo_suite.bench_fig8_nrmse,
    "fig9": cameo_suite.bench_fig9_blocking,
    "table3": cameo_suite.bench_table3_compression_time,
    "table4": cameo_suite.bench_table4_decompression_time,
    "fig10": cameo_suite.bench_fig10_parallel,
    "kernels": cameo_suite.bench_kernels,
    "backend": cameo_suite.bench_backend_parity,
    "store": cameo_suite.bench_store,
    "stream": cameo_suite.bench_stream,
    "mvar": cameo_suite.bench_mvar,
    "serve": cameo_suite.bench_serve,
    "fig12": forecast.bench_fig12_forecasting,
    "fig12lm": forecast.bench_fig12_lm_forecaster,
    "fig13": anomaly.bench_fig13_anomaly,
    "roofline": roofline.bench_roofline_table,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale dataset lengths")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(BENCHES)
    t0 = time.time()
    failures = []
    for name in names:
        print(f"# === {name} ===", flush=True)
        try:
            BENCHES[name](full=args.full)
        except Exception as e:  # keep the suite going; report at the end
            failures.append((name, repr(e)))
            print(f"{name}.ERROR,0,{e!r}", flush=True)
    print(f"# total {time.time() - t0:.1f}s", flush=True)
    if failures:
        print("# FAILURES:", failures, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
