"""TPU-native codebook matmul: ``out = x @ codebook[w_idx]`` (DESIGN.md §12).

This is the paper's §4 insight re-expressed for the TPU memory hierarchy:
weights live in HBM as *narrow integer indices* (int8 for |W|≤256, int16 up
to 65536) while the |W|-entry f32/bf16 codebook is tiny and VMEM-resident.
Each grid step:

  HBM → VMEM   x tile (bm×bk, bf16/f32) and w_idx tile (bk×bn, int8/16)
  VMEM         gather: w = codebook[w_idx]   (VPU lane gathers, below)
  MXU          acc += x_tile @ w_tile        (f32 accumulation)

Mosaic lowers a gather only within one 128-lane vreg, so the codebook
rides as an (S, 128) block (S = ⌈|W|/128⌉) and each id splits into a
segment and a lane: S ``take_along_axis`` lane gathers plus selects per
128 columns (``_dequantize``).

HBM weight traffic drops 2–4× vs bf16 (4–8× vs f32), which is the roofline
win for memory-bound decode shapes.  The multiply itself is free on the MXU —
the *no-multiply* property of the paper does not transfer to TPU, the
*no-weight-memory* property does (DESIGN.md §2).

Grid is ``(⌈M/bm⌉, ⌈N/bn⌉, ⌈K/bk⌉)`` with K innermost so the f32
accumulator tile stays VMEM-resident across the K sweep; the codebook's
BlockSpec index map is constant, so it is DMA'd once and revisited from
VMEM by every grid step while the x / w_idx streams double-buffer behind
the MXU (K marked ``arbitrary``, m/n ``parallel``).

Ragged shapes use *explicit masking*, not implicit padding: the K tail of
both operands is zeroed inside the kernel (0·0 contributes nothing to the
accumulator — and masking both sides means a TPU edge block's undefined
values can never surface as NaN·0), gather indices are clamped into the
codebook, and M/N edge tiles are trimmed by Pallas' masked edge stores.

Off-TPU the serving path takes ``codebook_matmul_xla`` — the same
dequantize-in-registers gather feeding one fused XLA dot (CPU has no
separate fast-memory tier for the codebook to exploit, so the Pallas block
walk only adds overhead there).  Parity against the Pallas kernel and the
``kernels.ref`` oracle is property-tested to f32 reduction-order tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["codebook_matmul_kernel", "codebook_matmul_pallas",
           "codebook_matmul_xla", "dequantize_pallas"]


def _canonical_idx(idx, n: int):
    """int32 ids in [0, n) — narrow dtypes store ids ≥ 2^(bits-1) as
    negatives (two's complement)."""
    idx = idx.astype(jnp.int32)
    return jnp.where(idx < 0, idx + n, idx)


_LANES = 128    # one vreg row: the widest gather Mosaic lowers


def _dequantize(idx, book, n_book: int):
    """w[k, n] = codebook[idx[k, n]] with the codebook held as (S, 128).

    Mosaic lowers a gather only inside one 128-lane vreg, so each id splits
    into a segment ``hi = id // 128`` and a lane ``lo = id % 128``: every
    segment is one lane gather (``take_along_axis`` over a row-broadcast
    of that segment), and a select keeps the segment each id points into.
    S = ⌈|W| / 128⌉ gathers per 128 columns — 8 at the paper's |W|=1000.
    """
    idx = jnp.clip(_canonical_idx(idx, n_book), 0, n_book - 1)
    hi, lo = idx // _LANES, idx % _LANES
    rows, cols = idx.shape
    cols_out = []
    for c in range(0, cols, _LANES):                     # static unroll
        lo_c, hi_c = lo[:, c:c + _LANES], hi[:, c:c + _LANES]
        w = jnp.zeros(lo_c.shape, jnp.float32)
        for s in range(book.shape[0]):
            seg = jnp.broadcast_to(book[s:s + 1, :], (rows, _LANES))
            g = jnp.take_along_axis(seg, lo_c, axis=1)
            w = jnp.where(hi_c == s, g, w)
        cols_out.append(w)
    return cols_out[0] if len(cols_out) == 1 else jnp.concatenate(cols_out,
                                                                  axis=1)


def codebook_matmul_kernel(x_ref, idx_ref, book_ref, out_ref, *,
                           bk: int, k_total: int, n_book: int):
    """One (bm, bn) f32 accumulator tile; revisited across the K grid."""
    kg = pl.program_id(2)

    @pl.when(kg == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # dequantize in VMEM from the narrow ids (canonicalised in-kernel, so
    # HBM only ever holds the int8/int16 planes)
    w = _dequantize(idx_ref[...].astype(jnp.int32), book_ref[...], n_book)
    x = x_ref[...]
    if k_total % bk:
        # explicit ragged-K masks on BOTH operands: an edge block's
        # undefined lanes (TPU) might be NaN, and NaN·0 would poison the
        # accumulator
        kw = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) + kg * bk
        w = jnp.where(kw < k_total, w, 0.0)
        kx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + kg * bk
        x = jnp.where(kx < k_total, x, jnp.zeros_like(x))
    out_ref[...] += jnp.dot(x, w.astype(x.dtype),
                            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def codebook_matmul_pallas(x: jnp.ndarray, w_idx: jnp.ndarray,
                           codebook: jnp.ndarray, *,
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """x: (M, K) float; w_idx: (K, N) int8/int16/int32; codebook: (W,).

    Dims need not be multiples of the block sizes — edge blocks are masked
    inside the kernel (module docstring), never padded by the wrapper.
    """
    m, k = x.shape
    k2, n = w_idx.shape
    assert k == k2, (x.shape, w_idx.shape)
    n_book = codebook.shape[-1]
    book2d = _book2d(codebook)

    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    kwargs = {}
    if not interpret:       # TPU: m,n parallel; K revisits the accumulator
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        functools.partial(codebook_matmul_kernel, bk=bk, k_total=k,
                          n_book=n_book),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(book2d.shape, lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="codebook_matmul_pallas",
        **kwargs,
    )(x, w_idx, book2d)
    return out


def _book2d(codebook):
    """The codebook as an (S, 128) f32 block, zero-padded (``_dequantize``)."""
    n_book = codebook.shape[-1]
    n_seg = pl.cdiv(n_book, _LANES)
    return jnp.pad(codebook.astype(jnp.float32).reshape(-1),
                   (0, n_seg * _LANES - n_book)).reshape(n_seg, _LANES)


def _dequantize_kernel(idx_ref, book_ref, out_ref, *, n_book: int):
    out_ref[...] = _dequantize(idx_ref[...].astype(jnp.int32), book_ref[...],
                               n_book).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("dtype", "bk", "bn", "interpret"))
def dequantize_pallas(w_idx: jnp.ndarray, codebook: jnp.ndarray, *,
                      dtype=jnp.float32, bk: int = 256, bn: int = 512,
                      interpret: bool = True) -> jnp.ndarray:
    """``codebook[w_idx]`` as ``dtype`` — the weights themselves, for the
    contractions that dequantize before an XLA dot (the ``dense`` backend,
    the tied lm-head).  The same lane gathers as the matmul kernel: a TPU
    runs an XLA gather from a |W|-entry table one element at a time, this
    a vreg at a time.  Bit-identical to the XLA gather: the values are the
    codebook's, cast once.  Edge blocks: ids clamped, stores masked.
    """
    k, n = w_idx.shape
    book2d = _book2d(codebook)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, n_book=codebook.shape[-1]),
        grid=(pl.cdiv(k, bk), pl.cdiv(n, bn)),
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
                  pl.BlockSpec(book2d.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), dtype),
        interpret=interpret,
        name="dequantize_pallas",
    )(w_idx, book2d)


@jax.jit
def codebook_matmul_xla(x: jnp.ndarray, w_idx: jnp.ndarray,
                        codebook: jnp.ndarray) -> jnp.ndarray:
    """The same contraction as one fused XLA gather + dot (off-TPU path).

    The |W|-entry codebook gather is L1-resident on any CPU; XLA fuses it
    into the dot's packing pass, so this runs at dense-matmul speed while
    HBM/DRAM still only ever holds the narrow indices.
    """
    w_can = _canonical_idx(w_idx, codebook.shape[-1])
    w = jnp.take(codebook.astype(jnp.float32), w_can, axis=0,
                 mode="clip").astype(x.dtype)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)
