"""Benchmark orchestrator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,case,value`` CSV.  --full uses paper-closer step counts
(CPU-hours); default is the quick profile used by bench_output.txt.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    scale = 3 if args.full else 1
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (fig2_parabola, fig6_mnist, fig7_autoencoder,
                            memory_savings, roofline, table1_alexnet,
                            table2_comparison)

    plan = [
        ("fig2_parabola", lambda: fig2_parabola.run(steps=400 * scale)),
        ("fig6_mnist", lambda: fig6_mnist.run(steps=200 * scale)),
        ("fig7_autoencoder", lambda: fig7_autoencoder.run(steps=200 * scale)),
        ("table1_alexnet", lambda: table1_alexnet.run(steps=300 * scale)),
        ("memory_savings", lambda: memory_savings.run(steps=200 * scale)),
        ("roofline", roofline.run),
    ]
    t1_rows = None
    print("benchmark,case,value")
    for name, fn in plan:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # report, keep going
            print(f"{name},ERROR,{e!r}")
            continue
        if name == "table1_alexnet":
            t1_rows = rows
        for r in rows:
            print(",".join(r))
        print(f"{name},_wall_seconds,{time.time() - t0:.1f}", flush=True)
    if (not args.only) or "table2" in args.only:
        for r in table2_comparison.run(t1_rows):
            print(",".join(r))


if __name__ == "__main__":
    main()
