"""Deterministic, cache-backed kernel autotuning (DESIGN.md §12).

Every routed contraction (``ops.codebook_matmul`` / ``ops.lut_matmul``)
asks this module for its launch config at trace time — block sizes for
the compiled Pallas kernels on TPU, chunking/variant for the XLA routes
off-TPU.  Shapes are static under jit, so the lookup happens once per
traced shape and folds into the executable.

Selection is a *deterministic cost model* over the candidate space —
padded-tile memory traffic in integer bytes, largest-tile-first
tie-breaking — NOT wall-clock timing.  Two runs over the same shape set
therefore produce byte-identical tuning caches on any machine, which is
what makes the cache CI-replayable (tests/test_autotune.py pins this).
Measured tuning exists as an opt-in (``measure=True``): it times each
candidate on seeded inputs and overrides the model's pick, for operators
bringing the cache up on real hardware; CI never exercises it.

Cache format (``tuning_cache.json``, override via $REPRO_TUNING_CACHE):

    { "<kernel>|<plat>|m{M}k{K}n{N}|<dtype>|t{R}x{C}": {config...}, ... }

keyed on everything the choice depends on — kernel, platform class
(``tpu`` = compiled Pallas, ``xla`` = fallback), problem shape, activation
dtype, table/codebook shape (the table competes for VMEM).  Values are
flat JSON objects of ints/strings; the file is dumped with sorted keys so
it diffs cleanly and byte-compares across runs.
"""

from __future__ import annotations

import json
import os
import pathlib

__all__ = ["kernel_config", "autotune_shapes", "candidates", "model_cost",
           "cache_key", "load_cache", "save_cache", "default_cache_path",
           "clear_memory_cache"]

_VMEM_BUDGET = 12 * 1024 * 1024      # bytes of VMEM a kernel may plan for
_LANE = 128                          # TPU lane count: last-dim tile quantum
_SUBLANE = 8                         # f32 sublane quantum: second-minor tile


def default_cache_path() -> str:
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    return str(pathlib.Path(__file__).with_name("tuning_cache.json"))


def load_cache(path: str | None = None) -> dict:
    p = pathlib.Path(path or default_cache_path())
    if not p.exists():
        return {}
    return json.loads(p.read_text())


def save_cache(cache: dict, path: str | None = None) -> str:
    """Canonical dump: sorted keys, fixed separators — byte-stable."""
    p = pathlib.Path(path or default_cache_path())
    p.write_text(json.dumps(cache, sort_keys=True, indent=1) + "\n")
    return str(p)


def cache_key(kernel: str, plat: str, m: int, k: int, n: int,
              dtype: str, table_shape: tuple) -> str:
    t = "x".join(str(int(d)) for d in table_shape)
    return f"{kernel}|{plat}|m{int(m)}k{int(k)}n{int(n)}|{dtype}|t{t}"


def _ceil_to(x: int, q: int) -> int:
    return -(-x // q) * q


def _tile_sizes(dim: int, quantum: int, cap: int) -> list:
    """Candidate tile sizes for one axis: quantum multiples covering the
    (rounded-up) dim, largest first so equal-cost ties pick the bigger
    tile (fewer grid steps, better MXU/VPU occupancy)."""
    full = _ceil_to(max(dim, 1), quantum)
    out = []
    t = quantum
    while t < min(full, cap):
        out.append(t)
        t *= 2
    out.append(min(full, cap))
    return sorted(set(out), reverse=True)


def candidates(kernel: str, plat: str, m: int, k: int, n: int,
               dtype: str, table_shape: tuple) -> list:
    """Enumerate valid launch configs, preferred-first.

    tpu: codebook — (bm, bn, bk) Pallas tiles: bn/bk are lane-dim
         multiples of 128, bm multiples of the f32 sublane (8); everything
         that must be VMEM-co-resident (double-buffered streams, the
         accumulator, the in-kernel dequantize temporaries, the codebook)
         has to fit the budget.  lut — (bm, bn, bk) tiles of the row-gather
         kernel: bm is M up to 8 rows (its lookups unroll per row), bn a
         lane multiple up to 512, bk a multiple of 32 (the int8 id tile)
         up to 64, with the double-buffered (bm, bk, S·128) row block in
         the budget.
    xla: lut — gather variant ('rows' | 'flat') × K-chunk size; codebook —
         a single fused gather+dot, nothing to tune.
    """
    if kernel == "lut" and plat == "xla":
        return [{"impl": "xla", "variant": v, "kc": kc}
                for v in ("rows", "flat") for kc in (32, 64, 128)]
    if plat == "xla":
        return [{"impl": "xla"}]
    if kernel == "lut":
        row_bytes = 4 * _ceil_to(int(table_shape[-1]), _LANE)
        bm = min(m, _SUBLANE)
        out = []
        for bn in _tile_sizes(n, _LANE, 512):
            for bk in _tile_sizes(k, 32, 64):
                # 2× the streamed row block and id tile; 4 int32 (bk, bn)
                # planes: ids, segments, lanes, looked-up values
                vmem = (2 * (bm * bk * row_bytes + bk * bn * 4)
                        + bm * bn * 4 + 4 * bk * bn * 4)
                if vmem <= _VMEM_BUDGET:
                    out.append({"impl": "pallas", "bm": bm, "bn": bn,
                                "bk": bk})
        return out

    book_bytes = 4 * _ceil_to(int(table_shape[-1]), _LANE)
    in_bytes = 2 if dtype == "bfloat16" else 4
    out = []
    for bm in _tile_sizes(m, _SUBLANE, 256):
        for bn in _tile_sizes(n, _LANE, 512):
            for bk in _tile_sizes(k, _LANE, 512):
                # 2× on the streamed operands: double-buffered DMA windows;
                # 3 int32/f32 (bk, bn) planes: ids, segment ids, weights
                vmem = (2 * (bm * bk + bk * bn) * in_bytes
                        + bm * bn * 4 + 3 * bk * bn * 4 + book_bytes)
                if vmem > _VMEM_BUDGET:
                    continue
                out.append({"impl": "pallas", "bm": bm, "bn": bn, "bk": bk})
    return out


def model_cost(kernel: str, cfg: dict, m: int, k: int, n: int,
               dtype: str, table_shape: tuple) -> int:
    """Integer cost of one launch — deterministic across machines.

    Pallas: bytes DMA'd through VMEM over the whole padded grid (streamed
    input tiles per grid step + one output pass + the table once) — the
    memory-bound proxy; padding waste from oversized tiles on ragged dims
    is charged at full price, which is what steers ragged shapes toward
    smaller tiles.  XLA lut: XLA:CPU lowers gather to a scalar loop, so
    the element gathers dominate at ~1 cost unit per looked-up byte
    regardless of variant; 'rows' additionally pays its sequential
    row-copy traffic (so 'flat' wins on the model — 'rows' stays a
    candidate for measured tuning); per-scan-step overhead steers toward
    few chunks, a 4× spill charge on past-L2 intermediates steers large-M
    shapes back to cache-sized chunks.  Constants were fit to in-engine
    A/B timings on the serving shapes (DESIGN.md §12), not first
    principles — the committed tuning cache pins the hot shapes anyway.
    """
    if cfg.get("impl") == "xla":
        if kernel != "lut":
            return 0
        kc = int(cfg["kc"])
        kp = _ceil_to(k, kc)
        ncols = int(table_shape[-1])
        gather = 4 * m * kp * n
        if cfg["variant"] == "rows":
            gather += m * kp * ncols             # sequential row copies
        steps = kp // kc
        inter = 8 * m * kc * (max(ncols, n) if cfg["variant"] == "rows"
                              else n)            # addresses + gathered vals
        spill = 4 * max(inter - (1 << 21), 0)    # past-L2 intermediates
        return gather + steps * 50_000 + spill
    bm, bn, bk = int(cfg["bm"]), int(cfg["bn"]), int(cfg["bk"])
    gm, gn, gk = -(-m // bm), -(-n // bn), -(-k // bk)
    if kernel == "lut":
        # the (bm, bk, S·128) int32 row block streams once per N tile
        row_bytes = 4 * _ceil_to(int(table_shape[-1]), _LANE)
        return (gm * gn * gk * (bm * bk * row_bytes + bk * bn * 4)
                + gm * gn * bm * bn * 4)
    in_bytes = 2 if dtype == "bfloat16" else 4
    stream = gm * gn * gk * (bm * bk + bk * bn) * in_bytes
    out_pass = gm * gn * bm * bn * 4
    return stream + out_pass + 4 * int(table_shape[-1])


def _measure(kernel: str, cfg: dict, m: int, k: int, n: int,
             dtype: str, table_shape: tuple, seed: int) -> float:
    """Median wall-clock of one candidate on seeded inputs (opt-in path)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    if kernel == "lut":
        r, c = int(table_shape[0]), int(table_shape[1])
        a = jnp.asarray(rng.integers(0, r, (m, k)), jnp.int32)
        w = jnp.asarray(rng.integers(0, c, (k, n)), jnp.int32)
        t = jnp.asarray(rng.integers(-1000, 1000, (r, c)), jnp.int32)
        from repro.kernels.lut_matmul import (lut_matmul_pallas,
                                              lut_matmul_xla)
        if cfg.get("impl") == "xla":
            fn = lambda: lut_matmul_xla(                         # noqa: E731
                a, w, t, kc=cfg["kc"], variant=cfg["variant"])
        else:
            fn = lambda: lut_matmul_pallas(                      # noqa: E731
                a, w, t, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
                interpret=False)
    else:
        w_ = int(table_shape[-1])
        x = jnp.asarray(rng.standard_normal((m, k)),
                        jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
        wi = jnp.asarray(rng.integers(0, w_, (k, n)), jnp.int32)
        book = jnp.asarray(rng.standard_normal((w_,)), jnp.float32)
        from repro.kernels.codebook_matmul import (codebook_matmul_pallas,
                                                   codebook_matmul_xla)
        if cfg.get("impl") == "xla":
            fn = lambda: codebook_matmul_xla(x, wi, book)        # noqa: E731
        else:
            fn = lambda: codebook_matmul_pallas(                 # noqa: E731
                x, wi, book, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"],
                interpret=False)
    jax.block_until_ready(fn())                                  # compile
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


_MEM: dict = {}            # in-process cache, seeded lazily from the file
_MEM_LOADED = False

# How each kernel_config call resolved — a fixed vocabulary so snapshot
# schemas stay stable.  Process-global (like _MEM, which persists across
# engines); serving telemetry reads these as deltas from attach time.
TUNING_COUNTS = {"explicit_hit": 0, "memory_hit": 0, "model_select": 0,
                 "measured_select": 0}


def tuning_counts() -> dict:
    return dict(TUNING_COUNTS)


def reset_tuning_counts() -> None:
    for k in TUNING_COUNTS:
        TUNING_COUNTS[k] = 0


def clear_memory_cache():
    global _MEM_LOADED
    _MEM.clear()
    _MEM_LOADED = False


def kernel_config(kernel: str, m: int, k: int, n: int, *, dtype: str,
                  plat: str, table_shape: tuple, cache: dict | None = None,
                  measure: bool = False, seed: int = 0) -> dict:
    """The launch config for one (kernel, platform, shape, dtype) site.

    Resolution order: explicit ``cache`` dict → in-process cache (seeded
    from the JSON file on first miss) → cost-model selection (persisted to
    the in-process cache; ``autotune_shapes`` writes it to disk).
    """
    global _MEM_LOADED
    key = cache_key(kernel, plat, m, k, n, dtype, table_shape)
    if cache is not None and key in cache:
        TUNING_COUNTS["explicit_hit"] += 1
        return cache[key]
    if not _MEM_LOADED:
        _MEM.update(load_cache())
        _MEM_LOADED = True
    if cache is None and key in _MEM:
        TUNING_COUNTS["memory_hit"] += 1
        return _MEM[key]
    cands = candidates(kernel, plat, m, k, n, dtype, table_shape)
    if measure:
        TUNING_COUNTS["measured_select"] += 1
        best = min(cands, key=lambda c: _measure(kernel, c, m, k, n, dtype,
                                                 table_shape, seed))
    else:
        TUNING_COUNTS["model_select"] += 1
        # min() is stable: equal-cost ties resolve to the earlier
        # (larger-tile / preferred-variant) candidate — deterministically
        best = min(cands, key=lambda c: model_cost(kernel, c, m, k, n,
                                                   dtype, table_shape))
    (_MEM if cache is None else cache)[key] = best
    return best


def autotune_shapes(shapes, *, path: str | None = None, measure: bool = False,
                    seed: int = 0) -> dict:
    """Tune a shape set and persist the cache JSON; returns the cache.

    ``shapes``: iterable of dicts with keys kernel/plat/m/k/n/dtype/
    table_shape (missing dtype defaults to float32).  Starts from the
    existing file so repeated runs are cumulative and idempotent.
    """
    cache = load_cache(path)
    for s in shapes:
        kernel_config(s["kernel"], s["m"], s["k"], s["n"],
                      dtype=s.get("dtype", "float32"), plat=s["plat"],
                      table_shape=tuple(s["table_shape"]), cache=cache,
                      measure=measure, seed=seed)
    save_cache(cache, path)
    return cache
