"""Faithful §4 integer engine as a tiled Pallas kernel (DESIGN.md §12).

acc[m, n] = Σ_k  M[a_idx[m, k], w_idx[k, n]]

Both operands are *indices* into the |A|×|W| multiplication table M.  A
TPU kernel may gather only inside one 128-lane vreg, so the contraction
is split in two gathers that each stay legal and cheap:

* rows — XLA gathers the table row of every activation,
  r[m, k] = M[a_idx[m, k]], lane-padded to S·128 entries (S = ⌈|W|/128⌉,
  8 at the paper's |W| = 1000): one contiguous row per index, where an
  element gather from the 16.4 MB paper table would touch HBM once per MAC;
* lookups — the kernel splits every weight id into a segment ``w // 128``
  and a lane ``w % 128``, runs S lane gathers (``take_along_axis``) over
  the row's segments and keeps, by select, the one the id points into —
  the codebook kernel's dequantize with a per-row source — then sums over
  K into the int32 accumulator.

The grid is ``(⌈M/bm⌉, ⌈N/bn⌉, ⌈K/bk⌉)`` with K innermost, so each
(bm, bn) accumulator tile stays resident in VMEM across the K sweep while
the (bm, bk, S·128) row blocks and (bk, bn) id tiles double-buffer behind
the lookups.  The lookups unroll per row of the tile, which is why ``bm``
stays at 8 or below.

Ragged shapes are handled by *explicit masking*, not implicit padding:
the K tail of an edge block (undefined rows and ids on TPU) is zeroed by a
``k < K`` mask, every id is clamped into the table before its lookup, and
the out-of-range rows/columns of M/N edge tiles are dropped by Pallas'
masked edge-block stores.

On a TPU the serving route is this kernel, compiled; off-TPU it runs
``lut_matmul_xla`` below — the same gather-accumulate as XLA ops —
because interpret-mode Pallas re-enters the grid per block at HLO level,
orders of magnitude slower than one fused XLA gather.  Both are
bit-identical (integer addition is associative, so any accumulation order
gives the same int32 sums); parity with each other and the jnp oracle in
``kernels.ref`` is exact and property-tested.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lut_matmul_kernel", "lut_matmul_pallas", "lut_matmul_xla"]

_LANES = 128    # one vreg row: the widest gather Mosaic lowers


def _canonical_idx(idx, n: int):
    """int32 ids in [0, n) — narrow dtypes store ids ≥ 2^(bits-1) as
    negatives (two's complement); the flat address arithmetic must not."""
    idx = idx.astype(jnp.int32)
    return jnp.where(idx < 0, idx + n, idx)


def lut_matmul_kernel(r_ref, w_ref, out_ref, *, bk: int, k_total: int,
                      n_cols: int):
    """One (bm, bn) int32 accumulator tile, revisited across the K grid;
    ``r_ref`` holds the block's table rows r[m, k] = table[a[m, k]]."""
    kg = pl.program_id(2)

    @pl.when(kg == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = jnp.clip(_canonical_idx(w_ref[...], n_cols), 0, n_cols - 1)
    hi, lo = w // _LANES, w % _LANES
    n_seg = r_ref.shape[-1] // _LANES
    keep = None
    if k_total % bk:
        # the K tail of an edge block holds undefined rows and ids
        keep = (jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) + kg * bk
                < k_total)
    sums = []
    for mi in range(r_ref.shape[0]):                    # static unroll
        r = r_ref[mi]                                   # (bk, S·128)
        cols = []
        for c in range(0, w.shape[1], _LANES):
            lo_c, hi_c = lo[:, c:c + _LANES], hi[:, c:c + _LANES]
            g = jnp.zeros(lo_c.shape, jnp.int32)
            for s in range(n_seg):
                seg = r[:, s * _LANES:(s + 1) * _LANES]
                g = jnp.where(hi_c == s,
                              jnp.take_along_axis(seg, lo_c, axis=1), g)
            cols.append(g)
        g = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
        if keep is not None:
            g = jnp.where(keep, g, 0)
        sums.append(jnp.sum(g, axis=0, keepdims=True))
    out_ref[...] += (sums[0] if len(sums) == 1
                     else jnp.concatenate(sums, axis=0))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_matmul_pallas(a_idx: jnp.ndarray, w_idx: jnp.ndarray,
                      table: jnp.ndarray, *, bm: int = 8, bn: int = 512,
                      bk: int = 64, interpret: bool = True) -> jnp.ndarray:
    """a_idx: (M, K) int table rows; w_idx: (K, N) int table columns;
    table: (R, C) int32.  Returns (M, N) int32 accumulators.

    Dims need not be multiples of the block sizes (module docstring).
    """
    m, k = a_idx.shape
    k2, n = w_idx.shape
    assert k == k2, (a_idx.shape, w_idx.shape)
    rows, n_cols = table.shape
    n_seg = pl.cdiv(n_cols, _LANES)
    padded = jnp.pad(table.astype(jnp.int32),
                     ((0, 0), (0, n_seg * _LANES - n_cols)))
    a = jnp.clip(_canonical_idx(a_idx, rows), 0, rows - 1)
    r = jnp.take(padded, a, axis=0)                 # (M, K, S·128)

    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    kwargs = {}
    if not interpret:       # TPU: m,n parallel; K revisits the accumulator
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(lut_matmul_kernel, bk=bk, k_total=k, n_cols=n_cols),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk, n_seg * _LANES),
                         lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="lut_matmul_pallas",
        **kwargs,
    )(r, w_idx)


@functools.partial(jax.jit, static_argnames=("kc", "variant"))
def lut_matmul_xla(a_idx: jnp.ndarray, w_idx: jnp.ndarray,
                   table: jnp.ndarray, *, kc: int = 64,
                   variant: str = "rows") -> jnp.ndarray:
    """The identical contraction as fused XLA gathers (off-TPU fast path).

    variant 'rows' gathers each (m, k) pair's table *row* first — (M, K, C)
    sequential row copies that stay L1-resident for the inner (m, k, n)
    lookup — then indexes along C with ``w_idx``; 'flat' computes the
    ``a·C + w`` flat address directly (fewer intermediates, random access
    into the full R·C table).  ``kc`` chunks the K axis through a
    ``lax.scan`` so the (M, kc, N) gathered intermediate is cache-sized
    instead of materialising the full (M, K, N) cube.  All variants produce
    bit-identical int32 accumulators (integer addition is associative).
    """
    m, k = a_idx.shape
    n = w_idx.shape[1]
    rows, n_cols = table.shape
    a_can = _canonical_idx(a_idx, rows)
    w_can = _canonical_idx(w_idx, n_cols)
    table = table.astype(jnp.int32)
    kc = min(kc, k)

    def chunk_sum(ab, wb, kmask):
        """Masked Σ over one K chunk; kmask zeroes the ragged tail
        explicitly (no pad-and-correct)."""
        if variant == "flat":
            addr = ab[:, :, None] * n_cols + wb[None, :, :]
            g = jnp.take(table.reshape(-1), addr, axis=0, mode="clip")
        else:
            rowvals = jnp.take(table, ab, axis=0, mode="clip")  # (M, kc, C)
            idx = jnp.broadcast_to(wb[None], (ab.shape[0],) + wb.shape)
            g = jnp.take_along_axis(rowvals, idx, axis=2, mode="clip")
        return jnp.sum(jnp.where(kmask[None, :, None], g, 0), axis=1)

    pad = (-k) % kc
    if pad:
        a_can = jnp.pad(a_can, ((0, 0), (0, pad)))
        w_can = jnp.pad(w_can, ((0, pad), (0, 0)))
    kt = k + pad
    if kt == kc:
        acc = chunk_sum(a_can, w_can, jnp.arange(kc) < k)
    else:
        def body(acc, k0):
            ab = jax.lax.dynamic_slice_in_dim(a_can, k0, kc, 1)
            wb = jax.lax.dynamic_slice_in_dim(w_can, k0, kc, 0)
            return acc + chunk_sum(ab, wb, k0 + jnp.arange(kc) < k), None
        acc, _ = jax.lax.scan(body, jnp.zeros((m, n), jnp.int32),
                              jnp.arange(0, kt, kc))
    return acc
