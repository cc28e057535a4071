"""The chip benchmark's entry point: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine whose JAX sees the chips the
cell asks for.  JAX's persistent compilation cache lives at
``JAX_COMPILATION_CACHE_DIR`` where that is set, else at the checkout's
fixed ``.jax_cache``, so only a cell's first run in a checkout compiles.
The last stdout line is the result (``chipbench/harness.py``).
"""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


def main() -> int:
    if not (REPO / "src" / "repro").is_dir():
        print(f"[chip-bench] the program is not here: no {REPO}/src/repro",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(REPO / "src"), str(HERE)]
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    # every program into the cache, however fast it compiled, so that a
    # cell's second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import harness

    try:
        return harness.main(t_start=T_START)
    except SystemExit as e:
        if isinstance(e.code, str):
            print(f"[chip-bench] {e.code}", file=sys.stderr)
            return 1
        raise


if __name__ == "__main__":
    sys.exit(main())
