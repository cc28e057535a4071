"""Page-table gather for the paged KV cache (DESIGN.md §8).

Decode's hot-loop memory op: assemble each slot's logical KV sequence from
the physical page pool,

    out[b, p] = pool[page_table[b, p]]        pool: (n_pages, page, ...)

The page table is a *scalar-prefetch* operand (``PrefetchScalarGridSpec``):
it is resident in SMEM before the kernel body runs, so the (b, p) grid
step's BlockSpec index map can read ``pt[b, p]`` and DMA exactly one
physical page HBM→VMEM — no gather instruction, no materialised index
expansion.  With int8 pages the HBM traffic per step is
``tokens_in_flight · KV · hd`` bytes, the paged-cache equivalent of the
codebook kernel's narrow-weight win (DESIGN.md §2).

Trailing pool dims are free-form: the same kernel moves K/V pages
``(page, KV, hd)`` and their per-token-per-head scale pages ``(page, KV)``.

Off-TPU the serving path uses the XLA fallback in ``kernels.ops``
(``jnp.take`` fuses fine on CPU; interpret-mode Pallas would be a
python-level inner loop per decode step).  This kernel is the TPU artifact
and is parity-checked against the fallback in interpret mode by the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["page_gather_kernel", "page_gather_pallas"]


def page_gather_kernel(pt_ref, pool_ref, out_ref):
    """Copy one physical page into its (b, p) slot of the gathered output.

    The page *selection* already happened in the BlockSpec index map (which
    read ``pt_ref`` — SMEM-resident via scalar prefetch); the body is a pure
    VMEM page move.
    """
    del pt_ref
    out_ref[...] = pool_ref[...][None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def page_gather_pallas(pool: jnp.ndarray, page_table: jnp.ndarray, *,
                       interpret: bool = True) -> jnp.ndarray:
    """pool: (n_pages, page, *rest); page_table: (B, P) int32.

    Returns (B, P, page, *rest) in pool.dtype — slot b's logical sequence is
    ``out[b].reshape(P * page, *rest)``.  The allocator guarantees live ids
    < n_pages (page 0 is the shared trash page, see serving/kvcache.py),
    but an out-of-range id reaching the index map would DMA from past the
    pool — undefined on TPU, not an exception — so ids are clamped into
    the pool *explicitly* here (a bad id degrades to reading the last
    page, same bounded-garbage contract as the trash page; the masked
    attention window means it never reaches live scores).
    """
    B, P = page_table.shape
    n_pages = pool.shape[0]
    page_shape = pool.shape[1:]
    zeros = (0,) * len(page_shape)
    pt = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, P),
        in_specs=[pl.BlockSpec((1,) + page_shape,
                               lambda b, p, pt: (pt[b, p],) + zeros)],
        out_specs=pl.BlockSpec((1, 1) + page_shape,
                               lambda b, p, pt: (b, p) + zeros),
    )
    return pl.pallas_call(
        page_gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, P) + page_shape, pool.dtype),
        interpret=interpret,
        name="page_gather_pallas",
    )(pt, pool)
