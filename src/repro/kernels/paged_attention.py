"""Paged attention over the stacked int8 page pool (DESIGN.md §8).

One kernel replaces the single-device paged read's three copies (slice the
layer out of the stacked pool, gather every page of every slot's table,
dequantize the gather to f32 in HBM) with one pass over the pages that hold
something:

* The pool stays whole in HBM (``memory_space=pl.ANY``): K/V pages
  ``(L, n_pages, page, KV, hd)`` int8.  The layer index, the page table
  and the per-slot page counts are scalar-prefetch operands, and each page
  is DMA'd from ``pool[layer, page_table[b, j]]`` — no layer slice, no
  gather buffer.
* Slot b reads ``n_b`` pages: the pages its valid length reaches, cut at
  its first page id 0 (the trash page, never allocated to a live
  sequence — serving/kvcache.py).  A retired slot (all-zero table row)
  reads nothing and issues no DMA.
* Whole pages form 128-token compute blocks (8 pages of 16 tokens),
  double-buffered: block i + 1's DMAs run under block i's math.
* Dequantization happens in VMEM.  A page keeps its KV heads interleaved
  (token-major rows of KV × hd), so all of a slot's query rows meet all
  heads' keys of a block in one MXU pass, (KV·R, hd) × (hd, T·KV), and a
  mask keeps each row's own head: KV times the rows of a per-head loop,
  which decode's few rows leave the MXU room for, and no relayout of the
  pages.  int8 values are exact in bf16, so with bf16 queries that pass is
  exact, and the per-key scales then multiply the f32 scores.  The
  probabilities times the value scales meet the integer values as a bf16
  hi/lo pair (~16 significant bits).  Scores, softmax and accumulation
  are f32.
* The scales ``(L, n_pages, page, KV)`` are kept by XLA with ``n_pages``
  minor, which no page DMA can slice.  The wrapper relayouts the layer's
  scales to one f32 row of page·KV per page (2.9 MB a plane at 5633
  pages), which the kernel holds in VMEM for the call and reads by
  dynamic row.

Returned are the partial softmax statistics of the cached past, in the
shapes the fresh tokens' fold takes (``models.attention._join_fresh``):
``num`` (B, KV, R, hd), ``denom`` and ``m`` (B, KV, R), where R = G·Lq
query rows per KV head.  Off TPU the serving path keeps the XLA read
(``kernels.ops.paged_attention``); this kernel is checked against it in
interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas", "pages_to_read"]

NEG_INF = -1e30
LANES = 128
ROW_CHUNK = 256          # query rows per score tile: (256, T·KV) f32 in VMEM


def pages_to_read(page_table, lengths, page_size: int):
    """Pages slot b's attention reads: ``min(ceil(lengths[b] / page_size),
    leading nonzero ids of page_table[b])`` — on numpy arrays in numpy (the
    engine counts the same quantity on the host), else in jax."""
    xp = np if isinstance(page_table, np.ndarray) else jnp
    lead = xp.cumprod(page_table != 0, axis=1).sum(axis=1)
    need = (lengths + page_size - 1) // page_size
    return xp.minimum(need, lead)


def _lanes(x, n):
    """(M, 128) lane-broadcast statistics widened to (M, n), n % 128 == 0."""
    return x if n == LANES else jnp.concatenate([x] * (n // LANES), axis=1)


def _kernel(layer_ref, pt_ref, n_ref, len_ref,
            q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
            num_ref, l_ref, m_ref,
            kbuf, vbuf, ks_rows, vs_rows, sems,
            *, ppb, pages_per_seq, rows_per_head):
    b = pl.program_id(0)
    layer = layer_ref[0]
    n = n_ref[b]
    kv_len = len_ref[b]
    nblk = (n + ppb - 1) // ppb
    M, hd = q_ref.shape[1:]
    _, _, page, KV, _ = kbuf.shape
    T = ppb * page                       # tokens per block
    W = T * KV                           # key columns per block
    sm_scale = hd ** -0.5

    @pl.when(b == 0)
    def _():
        # this layer's scales, one row of page·KV per page, stay in VMEM
        # for the whole call
        cps = [pltpu.make_async_copy(ks_hbm, ks_rows, sems.at[2]),
               pltpu.make_async_copy(vs_hbm, vs_rows, sems.at[2])]
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    num_ref[...] = jnp.zeros_like(num_ref)
    l_ref[...] = jnp.zeros_like(l_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    def pid(j):
        return pt_ref[b * pages_per_seq + jnp.minimum(j, pages_per_seq - 1)]

    def copies(i, slot):
        out = []
        for p in range(ppb):
            j = i * ppb + p
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                out.append((j < n, pltpu.make_async_copy(
                    src.at[layer, pid(j)], dst.at[slot, p], sems.at[slot])))
        return out

    def start(i, slot):
        for live, cp in copies(i, slot):
            pl.when(live)(cp.start)

    def wait(i, slot):
        for live, cp in copies(i, slot):
            pl.when(live)(cp.wait)

    @pl.when(nblk > 0)
    def _():
        start(0, 0)

    col = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    col_tok, col_head = col // KV, col % KV

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < nblk)
        def _():
            start(i + 1, 1 - slot)

        wait(i, slot)
        # integer keys and values, exact in bf16; column c = token·KV + head
        # (a partial last block keeps stale int8 rows: masked below)
        kq = kbuf[slot].astype(jnp.float32).reshape(W, hd).astype(
            jnp.bfloat16)
        vq = vbuf[slot].astype(jnp.float32).reshape(W, hd).astype(
            jnp.bfloat16)
        rows = [pl.ds(pid(i * ppb + p), 1) for p in range(ppb)]
        ks = jnp.concatenate([ks_rows[r, :] for r in rows], axis=1)  # (1, W)
        vs = jnp.concatenate([vs_rows[r, :] for r in rows], axis=1)
        live = (i * T + col_tok) < kv_len
        for r0 in range(0, M, ROW_CHUNK):
            rc = min(ROW_CHUNK, M - r0)
            q = q_ref[0, pl.ds(r0, rc), :]
            # every query row against every head's keys, one MXU pass;
            # only the row's own head survives the mask
            s = jax.lax.dot_general(
                q, kq.astype(q.dtype), (((1,), (1,)), ((), ())),
                precision=(jax.lax.Precision.HIGHEST
                           if q.dtype == jnp.float32 else None),
                preferred_element_type=jnp.float32) * (ks * sm_scale)
            row_head = (r0 + jax.lax.broadcasted_iota(
                jnp.int32, (rc, 1), 0)) // rows_per_head
            s = jnp.where(live & (col_head == row_head), s, NEG_INF)
            m_prev = m_ref[0, pl.ds(r0, rc), :]                  # (rc, 128)
            l_prev = l_ref[0, pl.ds(r0, rc), :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, W))
            l_ref[0, pl.ds(r0, rc), :] = (
                alpha * l_prev + jnp.sum(p, axis=1, keepdims=True))
            m_ref[0, pl.ds(r0, rc), :] = m_new
            pv = p * vs
            hi = pv.astype(jnp.bfloat16)
            lo = (pv - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            o = (jnp.dot(hi, vq, preferred_element_type=jnp.float32)
                 + jnp.dot(lo, vq, preferred_element_type=jnp.float32))
            num_ref[0, pl.ds(r0, rc), :] = (
                _lanes(alpha, hd) * num_ref[0, pl.ds(r0, rc), :] + o)
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, k_pool, v_pool, k_scale, v_scale, layer,
                           page_table, lengths, *, interpret=False):
    """Partial softmax statistics of q against each slot's cached pages.

    q: (B, KV, R, hd), R query rows per KV head (bf16 queries make the
    score pass exact; f32 ones run it at HIGHEST); k_pool/v_pool:
    (L, n_pages, page, KV, hd) int8; k_scale/v_scale: (L, n_pages, page,
    KV); layer: scalar int; page_table: (B, P) int32; lengths: (B,) tokens
    of the past each slot's rows may attend (positions < lengths[b]).
    The page size must divide 128 (a block is 128 tokens of whole pages)
    and hd must be a multiple of 128.
    Returns ``(num (B, KV, R, hd), denom (B, KV, R), m (B, KV, R))`` f32;
    a slot that reads no page gives num = denom = 0, m = -1e30.
    """
    B, KV, R, hd = q.shape
    P = page_table.shape[1]
    n_pages, page = k_pool.shape[1], k_pool.shape[2]
    if LANES % page or hd % LANES:
        raise ValueError(f"page size {page} must divide {LANES} and "
                         f"head_dim {hd} be a multiple of it")
    ppb = LANES // page
    # a bad id would DMA from past the pool: clamp, as page_gather does
    pt = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    n = pages_to_read(pt, lengths, page).astype(jnp.int32)
    kv_len = jnp.minimum(lengths, n * page)
    layer = jnp.asarray(layer, jnp.int32)

    def rows(scale):
        # the layer's scales as one f32 row of page·KV per page: a small
        # relayout (the pool keeps n_pages minor) that the kernel reads by
        # dynamic row
        s = jax.lax.dynamic_index_in_dim(scale, layer, 0, keepdims=False)
        return s.reshape(n_pages, page * KV).astype(jnp.float32)

    M = KV * R
    slot_spec = pl.BlockSpec((1, M, hd), lambda b, *_: (b, 0, 0))
    stat_spec = pl.BlockSpec((1, M, LANES), lambda b, *_: (b, 0, 0))
    stat = jax.ShapeDtypeStruct((B, M, LANES), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[slot_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=[slot_spec, stat_spec, stat_spec],
        scratch_shapes=[
            pltpu.VMEM((2, ppb) + k_pool.shape[2:], k_pool.dtype),
            pltpu.VMEM((2, ppb) + v_pool.shape[2:], v_pool.dtype),
            pltpu.VMEM((n_pages, page * KV), jnp.float32),
            pltpu.VMEM((n_pages, page * KV), jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    num, l, m = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, pages_per_seq=P,
                          rows_per_head=R),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, M, hd), jnp.float32), stat, stat],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_pallas",
    )(layer.reshape(1), pt.reshape(-1), n, kv_len, q.reshape(B, M, hd),
      k_pool, v_pool, rows(k_scale), rows(v_scale))
    return (num.reshape(B, KV, R, hd), l[..., 0].reshape(B, KV, R),
            m[..., 0].reshape(B, KV, R))
