"""Benchmark entrypoint: one function per paper table/figure.
Prints `name,us_per_call,derived` CSV rows; full tables in results/bench/.
``--smoke`` is forwarded to every workload (short-but-complete runs)."""
from __future__ import annotations

import sys
import traceback


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else list(argv)
    from . import (ablation_topology, bench_kernels, bench_throughput,
                   fig2_effective_lr, fig3_straggler, fig4_noise_decomp,
                   matrix, roofline_report, table1_large_batch,
                   table4_lr_tuning, table5_asr_proxy, theorem1_smoothing)
    benches = [
        ("fig2_effective_lr", fig2_effective_lr.main),
        ("fig4_noise_decomp", fig4_noise_decomp.main),
        ("table1_large_batch", table1_large_batch.main),
        ("table4_lr_tuning", table4_lr_tuning.main),
        ("table5_asr_proxy", table5_asr_proxy.main),
        ("theorem1_smoothing", theorem1_smoothing.main),
        ("fig3_straggler", fig3_straggler.main),
        ("ablation_topology", ablation_topology.main),
        ("bench_kernels", bench_kernels.main),
        ("bench_throughput", bench_throughput.main),
        ("bench_matrix", matrix.main),
        ("roofline_report", roofline_report.main),
    ]
    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        try:
            rc = fn(argv)
            # matrix-style mains return an int exit code; figure mains may
            # return their result payload (fig2's losses dict) — not a failure
            if isinstance(rc, int) and rc:
                failed.append(name)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
