"""Benchmark harness — one entry per paper table/figure + roofline.

Prints ``name,...`` CSV lines.  Heavy model-based benches (table3) train a
tiny EE model on the fly (~30 s on CPU)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig4_scaling, kernels_bench, roofline_table,
                            table2_deployment, table3_precision,
                            table4_ablation, throughput_bench)
    benches = [
        ("table2", table2_deployment.run),
        ("table4", table4_ablation.run),
        ("fig4", fig4_scaling.run),
        ("table3", table3_precision.run),
        ("kernels", kernels_bench.run),
        ("roofline", roofline_table.run),
        ("throughput", throughput_bench.run),
        ("paged_kv", throughput_bench.run_paged),
        ("async_channel", throughput_bench.run_channel),
        ("cloud_batch", throughput_bench.run_cloud_batch),
    ]
    failures = []
    for name, fn in benches:
        print(f"# --- {name} ---", flush=True)
        try:
            fn(csv=True)
        except Exception as e:
            failures.append(name)
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
