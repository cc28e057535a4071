"""Streaming 1-D k-means assignment + partial centroid sums (paper §2.2).

The periodic clustering event must assign up to ~10⁸ weights to |W| sorted
centers and compute per-center sums/counts — a pure HBM-bandwidth-bound
streaming reduction, ideal for a fused kernel: the (|W|−1) boundaries stay
in VMEM while weight blocks stream through once, emitting partial sums that
the host (or a follow-up reduce) combines into new centroids.

Assignment uses chunked broadcast-compare (rank = Σ 1[v > boundary]) — a
`searchsorted` without data-dependent control flow, VPU-friendly.  Partial
sums use a one-hot-mask matmul over center chunks (MXU-friendly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["kmeans_assign_kernel", "kmeans_assign_pallas"]

_CHUNK = 128  # boundary/center chunk width (lane-aligned)


def kmeans_assign_kernel(v_ref, b_ref, idx_ref, sums_ref, counts_ref, *,
                         k: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    v = v_ref[0, :].astype(jnp.float32)             # (bv,)
    bounds = b_ref[0, :]                            # (kb,) padded with +inf

    # rank of each value among boundaries = assignment index
    idx = jnp.zeros_like(v, dtype=jnp.int32)
    n_chunks = bounds.shape[0] // _CHUNK
    for c in range(n_chunks):                       # static unroll
        chunk = bounds[c * _CHUNK:(c + 1) * _CHUNK]  # static slice
        idx += jnp.sum(v[:, None] >= chunk[None, :], axis=1).astype(jnp.int32)
    idx_ref[0, :] = idx

    # partial sums/counts via one-hot matmuls over center chunks
    kc = sums_ref.shape[1] // _CHUNK
    for c in range(kc):                             # static unroll
        ids = c * _CHUNK + jax.lax.broadcasted_iota(jnp.int32, (1, _CHUNK), 1)
        mask = (idx[:, None] == ids).astype(jnp.float32)       # (bv, 128)
        sl = slice(c * _CHUNK, (c + 1) * _CHUNK)
        sums_ref[:, sl] += jnp.dot(v[None, :], mask,         # 2-D for MXU
                                   preferred_element_type=jnp.float32)
        counts_ref[:, sl] += jnp.sum(mask, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bv", "interpret"))
def kmeans_assign_pallas(values: jnp.ndarray, centers: jnp.ndarray, *,
                         bv: int = 1024, interpret: bool = True):
    """values: (n,) float; centers: (k,) sorted.  Returns (idx, sums, counts).

    ``bv`` = 1024 keeps the unrolled (bv, 128) compare / one-hot
    temporaries inside the TPU's 16 MiB of scoped VMEM (4096 needs ~31 MiB).

    Padding: values padded with +inf (assigned to the last center) and the
    pad contribution removed from sums/counts afterwards; boundaries padded
    with +inf to a 128 multiple (never exceeded by real values).
    """
    v = values.reshape(-1).astype(jnp.float32)
    n = v.shape[0]
    k = centers.shape[0]
    bounds = (centers[:-1] + centers[1:]) / 2.0
    # strictly more boundary slots than real boundaries, so at least one BIG
    # pad boundary exists and padded values rank past every real center
    kb = (bounds.shape[0] // _CHUNK + 1) * _CHUNK
    # boundary padding BIG and value padding 2·BIG: padded values rank past
    # every real center (idx ≥ kb ≥ k) so they fall outside all sum chunks —
    # exact exclusion with no correction arithmetic (finite, so the masked
    # matmul never produces inf·0).  Assumes |values| < BIG.
    BIG = jnp.float32(1e30)
    bounds = jnp.pad(bounds.astype(jnp.float32),
                     (0, kb - bounds.shape[0]), constant_values=BIG)
    kk = -(-k // _CHUNK) * _CHUNK

    pad = (-n) % bv
    vp = jnp.concatenate([v, jnp.broadcast_to(2 * BIG, (pad,))]) if pad else v
    grid = (vp.shape[0] // bv,)
    idx, sums, counts = pl.pallas_call(
        functools.partial(kmeans_assign_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((1, bv), lambda i: (0, i)),
                  pl.BlockSpec((1, kb), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1, bv), lambda i: (0, i)),
                   pl.BlockSpec((1, kk), lambda i: (0, 0)),
                   pl.BlockSpec((1, kk), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, vp.shape[0]), jnp.int32),
                   jax.ShapeDtypeStruct((1, kk), jnp.float32),
                   jax.ShapeDtypeStruct((1, kk), jnp.float32)],
        interpret=interpret,
    )(vp.reshape(1, -1), bounds.reshape(1, -1))
    idx = idx[0, :n]
    sums, counts = sums[0, :k], counts[0, :k]
    return idx, sums, counts
