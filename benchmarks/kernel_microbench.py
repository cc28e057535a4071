"""Kernel microbenchmarks: routed lut/codebook matmuls vs dense, per shape.

Times the *routed* ops (``kernels.ops`` — i.e. exactly what the serving
engine executes: tuned Pallas on TPU, tuned XLA fallbacks elsewhere) on
the serving model's hot contraction shapes, against a dense f32
``jnp.dot`` of the same shape measured in the same process.  Each entry
reports:

* ``us``          — microseconds per call (amortized over a scan of
                    ``--calls`` distinct activations against fixed
                    weights/table, the decode access pattern; a bare
                    timing loop would measure dispatch overhead on these
                    shapes, and a scan over one input would let XLA hoist
                    the whole contraction out of the loop).
* ``tok_equiv_s`` — rows/sec through the site (M rows ≈ M tokens for a
                    decode-shaped call), the absolute number.
* ``rel_dense``   — kernel time / dense time for the same (M, K, N),
                    same run.  This ratio is the machine-portable
                    regression signal: CI boxes differ in absolute speed
                    but the kernel and its dense baseline move together.
* ``config``      — the launch config the autotune cache resolved
                    (``kernels.autotune``), so a perf change can be told
                    apart from a tuning change in the diff.

Every run first asserts parity against ``kernels.ref`` on each shape —
bit-exact for lut (integer accumulators), small f32 tolerance for
codebook — so a "fast" number can never come from a wrong kernel.

Full runs write ``benchmarks/BENCH_kernels.json`` (``--json-out``), the
checked-in baseline.  ``--smoke`` (the CI gate) writes its measurements
to ``BENCH_kernels.smoke.json`` instead and exits nonzero if any entry's
``rel_dense`` regressed more than ``--tol`` (default 20%) against the
checked-in baseline, or if parity fails.

    PYTHONPATH=src python benchmarks/kernel_microbench.py            # refresh baseline
    PYTHONPATH=src python benchmarks/kernel_microbench.py --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune, ops, ref
from repro.launch.compile_cache import use_compile_cache

# The serving model's contraction sites (d_model=128, d_ff=256 config used
# by tests and BENCH_serve):  (K, N) in {attn proj, ffn up, ffn down} ×
# M in {1: single-slot decode, 8: batch-8 decode, 64: prefill tile}.
SHAPES = [(m, k, n)
          for m in (1, 8, 64)
          for (k, n) in ((128, 128), (128, 256), (256, 128))]
LUT_TABLE = (4096, 256)       # (|A| = act levels, |W| = weight codes)
BOOK = 256                    # codebook entries


def _inputs(kernel, m, k, n, calls, seed):
    """Seeded inputs: stacked per-call activations, fixed weights/table."""
    rng = np.random.default_rng(seed)
    if kernel == "lut":
        r, c = LUT_TABLE
        a = jnp.asarray(rng.integers(0, r, (calls, m, k)), jnp.int32)
        w = jnp.asarray(rng.integers(0, c, (k, n)), jnp.int32)
        t = jnp.asarray(rng.integers(-1000, 1000, LUT_TABLE), jnp.int32)
        return a, w, t
    x = jnp.asarray(rng.standard_normal((calls, m, k)), jnp.float32)
    wi = jnp.asarray(rng.integers(0, BOOK, (k, n)), jnp.int32)
    book = jnp.asarray(rng.standard_normal((BOOK,)), jnp.float32)
    return x, wi, book


def _timed(op, stacked, *fixed, reps):
    """Min-of-reps seconds per call: scan the op over the stacked leading
    axis with an accumulating carry (distinct input each step, result
    consumed — nothing for XLA to hoist or elide)."""
    calls = stacked.shape[0]

    @jax.jit
    def run(stacked, *fixed):
        def body(c, s):
            return c + op(s, *fixed).astype(jnp.float32).sum(), None
        return jax.lax.scan(body, jnp.float32(0), stacked)[0]

    jax.block_until_ready(run(stacked, *fixed))            # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(stacked, *fixed))
        best = min(best, time.perf_counter() - t0)
    return best / calls


def _parity(kernel, m, k, n, seed):
    """Routed op vs kernels.ref on this shape; raises on mismatch."""
    a, w, t = _inputs(kernel, m, k, n, 1, seed)
    if kernel == "lut":
        got = ops.lut_matmul(a[0], w, t)
        want = ref.lut_matmul_ref(a[0], w, t)
        if not bool(jnp.all(got == want)):
            raise AssertionError(f"lut parity m{m}k{k}n{n}")
    else:
        got = ops.codebook_matmul(a[0], w, t)
        want = ref.codebook_matmul_ref(a[0], w, t)
        err = float(jnp.max(jnp.abs(got - want)))
        if err > 1e-4:
            raise AssertionError(f"codebook parity m{m}k{k}n{n}: {err}")


def measure_entry(kernel, m, k, n, *, calls, reps, seed):
    """One (kernel, shape) entry: routed-op and dense timings + config."""
    plat = "tpu" if ops.supports_compiled_pallas() else "xla"
    table_shape = LUT_TABLE if kernel == "lut" else (BOOK,)
    dt_key = "int32" if kernel == "lut" else "float32"
    op = ops.lut_matmul if kernel == "lut" else ops.codebook_matmul
    stacked, wfix, tfix = _inputs(kernel, m, k, n, calls, seed)
    dt = _timed(op, stacked, wfix, tfix, reps=reps)
    dense_w = jnp.asarray(
        np.random.default_rng(seed + 1).standard_normal((k, n)), jnp.float32)
    dt_dense = _timed(lambda x, w: jnp.dot(x, w), stacked.astype(jnp.float32),
                      dense_w, reps=reps)
    cfg = autotune.kernel_config(kernel, m, k, n, dtype=dt_key, plat=plat,
                                 table_shape=table_shape)
    return {"us": round(dt * 1e6, 2),
            "dense_us": round(dt_dense * 1e6, 2),
            "tok_equiv_s": round(m / dt, 1),
            "rel_dense": round(dt / dt_dense, 3),
            "config": cfg}


def run_bench(*, calls, reps, seed):
    plat = "tpu" if ops.supports_compiled_pallas() else "xla"
    entries = {}
    for kernel in ("lut", "codebook"):
        for (m, k, n) in SHAPES:
            _parity(kernel, m, k, n, seed)
            key = f"{kernel}|m{m}k{k}n{n}"
            ent = measure_entry(kernel, m, k, n, calls=calls, reps=reps,
                                seed=seed)
            entries[key] = ent
            print(f"[{key:24s}] {ent['us']:9.1f}us"
                  f"  dense {ent['dense_us']:7.1f}us"
                  f"  rel {ent['rel_dense']:7.2f}  cfg {ent['config']}")
    return {"meta": {"plat": plat, "calls": calls, "reps": reps,
                     "seed": seed, "lut_table": list(LUT_TABLE),
                     "codebook": BOOK},
            "entries": entries}


def smoke_gate(result, baseline_path, tol, *, retries, calls, reps, seed):
    """>tol relative-throughput regression vs the checked-in baseline on
    any entry fails the gate.  rel_dense compares kernel-to-dense in the
    SAME run, so the gate is portable across machines of different
    absolute speed.  Entries over the limit are re-measured up to
    ``retries`` times (best rel kept) before counting as regressions —
    single-digit-µs denominators make one-shot ratios noisy, and a real
    regression reproduces on every retry."""
    try:
        with open(baseline_path) as f:
            base = json.load(f)["entries"]
    except FileNotFoundError:
        print(f"[smoke] FAIL: no baseline at {baseline_path}")
        return False
    ok = True
    for key, ent in result["entries"].items():
        if key not in base:
            print(f"[smoke] WARN: {key} not in baseline (new entry)")
            continue
        want = base[key]["rel_dense"]
        lim = want * (1.0 + tol)
        attempt = 0
        while ent["rel_dense"] > lim and attempt < retries:
            attempt += 1
            kernel, shp = key.split("|")
            m, rest = shp[1:].split("k")
            k, n = rest.split("n")
            redo = measure_entry(kernel, int(m), int(k), int(n), calls=calls,
                                 reps=reps, seed=seed + attempt)
            if redo["rel_dense"] < ent["rel_dense"]:
                ent = result["entries"][key] = redo
        got = ent["rel_dense"]
        verdict = "ok" if got <= lim else "REGRESSED"
        if got > lim:
            ok = False
        retried = f" (retries {attempt})" if attempt else ""
        print(f"[smoke] {key:24s} rel {got:7.2f} vs baseline {want:7.2f}"
              f" (limit {lim:7.2f}) {verdict}{retried}")
    missing = set(base) - set(result["entries"])
    if missing:
        print(f"[smoke] FAIL: baseline entries not measured: {sorted(missing)}")
        ok = False
    print(f"[smoke] {'PASS' if ok else 'FAIL'}")
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=64,
                    help="scan length per timing (distinct activations)")
    ap.add_argument("--reps", type=int, default=5, help="min-of-N outer reps")
    ap.add_argument("--retries", type=int, default=2,
                    help="re-measurements allowed per over-limit smoke entry")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="gate rel_dense against the checked-in baseline")
    ap.add_argument("--tol", type=float, default=0.20,
                    help="allowed rel_dense regression fraction in --smoke")
    ap.add_argument("--baseline", default="benchmarks/BENCH_kernels.json")
    ap.add_argument("--json-out", default=None,
                    help="output path (default: baseline path, or "
                         "BENCH_kernels.smoke.json under --smoke)")
    args = ap.parse_args(argv)
    use_compile_cache()

    result = run_bench(calls=args.calls, reps=args.reps, seed=args.seed)
    ok = True
    if args.smoke:
        ok = smoke_gate(result, args.baseline, args.tol,
                        retries=args.retries, calls=args.calls,
                        reps=args.reps, seed=args.seed)
    out = args.json_out or ("benchmarks/BENCH_kernels.smoke.json"
                            if args.smoke else args.baseline)
    with open(out, "w") as f:
        json.dump(result, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"[json] wrote {out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
