"""Benchmark driver — one section per paper table/figure + kernels +
roofline. Run: PYTHONPATH=src python -m benchmarks.run"""
from __future__ import annotations

import time

from repro.utils import init_compile_cache


def main() -> None:
    init_compile_cache()
    from benchmarks import (fig1_rates, fig2_throughput, kernels_micro,
                            kvsharer_bench, roofline, serving_continuous,
                            table1_selective, table2_quant,
                            table3_attention)
    sections = [
        ("Table1: selective compression (survey §2)", table1_selective.run),
        ("Table1b: KVSharer layer sharing (survey §2 [10])",
         kvsharer_bench.run),
        ("Table2: quantization compression (survey §3)", table2_quant.run),
        ("Table3: attention/layer-budget compression (survey §4)",
         table3_attention.run),
        ("Fig1: inference-rate improvement", fig1_rates.run),
        ("Fig2: end-to-end engine throughput (survey §5/§6)",
         fig2_throughput.run),
        ("Serving: continuous-batching decode rates",
         serving_continuous.run),
        ("Kernels: micro-benchmarks (interpret mode)", kernels_micro.run),
        ("Roofline: dry-run derived terms (single-pod)", roofline.run),
    ]
    for title, fn in sections:
        print(f"\n=== {title} ===", flush=True)
        t0 = time.perf_counter()
        try:
            print(fn())
        except Exception as e:  # noqa: BLE001
            print(f"SECTION FAILED: {e!r}")
            raise
        print(f"[{time.perf_counter() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    main()
