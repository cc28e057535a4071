"""Attention: GQA + RoPE / M-RoPE / qk-norm, flash-chunked softmax, KV cache.

Shapes convention: activations (B, L, D); heads live in the projection dims.
``flash_attention`` streams KV blocks with an online softmax (lax.scan), so
peak memory is O(L·block) instead of O(L²) — required for the 32k-prefill
dry-run cells and a §Perf lever everywhere else.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.kernels import probes
from repro.models.layers import dense, dense_init, rms_norm, rms_norm_init

__all__ = ["AttnConfig", "attn_init", "attn_apply", "init_kv_cache",
           "rope", "flash_attention", "chunk_attention", "attn_decode_paged",
           "attn_prefill_chunk", "attn_verify_cached", "attn_verify_paged",
           "quantize_kv", "dequantize_kv"]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int = 0                # 0 → d_model // n_heads
    qk_norm: bool = False            # qwen3 family
    rope_theta: float = 1e4
    rope_sections: tuple = ()        # M-RoPE (qwen2-vl): head_dim split
    window: int = 0                  # sliding-window size; 0 = full
    causal: bool = True              # False for encoder self-attn
    kv_block: int = 1024             # flash KV chunk

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv


def attn_init(key, cfg: AttnConfig, dtype=jnp.float32, cross: bool = False):
    ks = jax.random.split(key, 4)
    hd = cfg.hd
    p = {"wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
         "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv * hd, dtype),
         "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv * hd, dtype),
         "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype)}
    if cfg.qk_norm:
        p["q_norm"] = rms_norm_init(hd, dtype)
        p["k_norm"] = rms_norm_init(hd, dtype)
    del cross
    return p


# --- rotary ------------------------------------------------------------------

def _rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def rope(x, pos, theta: float = 1e4, sections: tuple = ()):
    """Rotary embedding.  x: (B, L, H, hd); pos: (B, L) or (3, B, L) (M-RoPE).

    M-RoPE (qwen2-vl): the head_dim frequency bands are split into
    ``sections`` (e.g. 16/24/24 of hd/2) driven by (temporal, h, w) position
    streams; with a single position stream all sections use it (text mode —
    equivalent to standard RoPE, which is the paper-accurate text behaviour).
    """
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                        # (hd/2,)
    if pos.ndim == 2:
        pos3 = jnp.broadcast_to(pos[None], (3,) + pos.shape)
    else:
        pos3 = pos
    if sections:
        sec_id = jnp.repeat(jnp.arange(len(sections)),
                            jnp.asarray(sections), total_repeat_length=hd // 2)
        sec_id = jnp.minimum(sec_id, 2)
    else:
        sec_id = jnp.zeros((hd // 2,), jnp.int32)
    # angle[b, l, f] = pos3[sec_id[f], b, l] * freqs[f]
    p_sel = jnp.take(pos3, sec_id, axis=0)                # (hd/2, B, L)
    ang = jnp.einsum("fbl,f->blf", p_sel.astype(jnp.float32), freqs)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.stack([xf1 * cos - xf2 * sin, xf1 * sin + xf2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# --- decode attention (Lq == 1): one full einsum over the cache -------------
#
# With the KV cache sequence-sharded over `model` (flash-decode layout),
# this is the form XLA parallelizes correctly: per-shard partial scores,
# softmax max/sum all-reduce of (B, H, 1) scalars, partial PV + all-reduce.
# A lax.scan over KV blocks here would instead force a cache gather.

def _put_rows(dst, new, rows):
    """Per-row cache write: dst (B, S, ...), new (B, 1, ...), rows (B,).

    Each batch row (= serving slot) writes its token at its own sequence
    offset — the vmapped dynamic_update_slice XLA lowers to a scatter the
    while-loop buffer assignment still aliases in place."""
    def put(d, n, i):
        return jax.lax.dynamic_update_slice(d, n.astype(d.dtype),
                                            (i,) + (0,) * (d.ndim - 1))
    return jax.vmap(put)(dst, new, rows)


def _per_row(val, B):
    """Scalar decode bookkeeping broadcasts as-is; a (B,) vector (per-slot
    positions, continuous batching) reshapes to broadcast over the score's
    trailing KV-sequence axis."""
    v = jnp.asarray(val)
    return v.reshape(B, 1, 1, 1, 1) if v.ndim == 1 else v


def decode_attention(q, k, v, kv_len, exclude=None, extra_kv=None):
    """q: (B,1,KV,G,hd); k/v: (B,S,KV,hd) cache (may be *stale*: the current
    token's K/V are passed via ``extra_kv`` so the cache carry can be read
    before it is written — the ordering XLA needs to alias the update in
    place).  ``exclude``: ring slot being evicted this step (masked).
    ``kv_len``/``exclude`` may be scalars (uniform batch) or (B,) vectors
    (per-slot cache positions, see ServeEngine continuous batching)."""
    B, Lq, KV, G, hd = q.shape
    Lk = k.shape[1]
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    idx = jnp.arange(Lk)[None, None, None, None, :]
    mask = idx < _per_row(kv_len, B)
    if exclude is not None:
        mask = mask & (idx != _per_row(exclude, B))
    s = jnp.where(mask, s, NEG_INF)
    if extra_kv is not None:
        k_new, v_new = extra_kv                       # (B, 1, KV, hd)
        s_new = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_new.astype(jnp.float32))
        s = jnp.concatenate([s, s_new], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    if extra_kv is not None:
        out = jnp.einsum("bkgqs,bskd->bqkgd", p[..., :Lk],
                         v.astype(jnp.float32))
        out = out + jnp.einsum("bkgqs,bskd->bqkgd", p[..., Lk:],
                               extra_kv[1].astype(jnp.float32))
    else:
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --- paged KV cache (DESIGN.md §8) -------------------------------------------
#
# The cache is a global pool of fixed-size pages (L, n_pages, page, KV, hd);
# a slot's logical sequence is its page-table row gathered in order.  Pages
# store int8 values + per-token-per-head scales (quantize_kv) or plain
# floats.  Logical position s of slot b lives at
# pool[page_table[b, s // page], s % page] — gathers therefore reassemble a
# sequence whose index IS the logical position, so the masks of
# decode_attention/chunk_attention apply unchanged.

@jax.named_scope("kv_read")
def _gather_paged_kv(k_pool, v_pool, page_table, layer, scales=None):
    """Assemble (B, P·page, KV, hd) float K/V for one layer's pool slice.

    k_pool/v_pool: (L, n_pages, page, KV, hd); page_table: (B, P);
    scales: optional (ks, vs) each (L, n_pages, page, KV).  Gathers route
    through ``kernels.ops.gather_pages`` (compiled Pallas on TPU).
    """
    from repro.kernels import ops

    B, P = page_table.shape
    page, KV, hd = k_pool.shape[2:]
    k_pl = jax.lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
    v_pl = jax.lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
    k_l = ops.gather_pages(k_pl, page_table).reshape(B, P * page, KV, hd)
    v_l = ops.gather_pages(v_pl, page_table).reshape(B, P * page, KV, hd)
    if scales is not None:
        ks_all, vs_all = scales
        ks_pl = jax.lax.dynamic_index_in_dim(ks_all, layer, 0, keepdims=False)
        vs_pl = jax.lax.dynamic_index_in_dim(vs_all, layer, 0, keepdims=False)
        ks = ops.gather_pages(ks_pl, page_table).reshape(B, P * page, KV)
        vs = ops.gather_pages(vs_pl, page_table).reshape(B, P * page, KV)
        k_l = dequantize_kv(k_l, ks)
        v_l = dequantize_kv(v_l, vs)
    return k_l, v_l


def chunk_attention(q, k_past, v_past, past_len, k_new, v_new):
    """Chunked-prefill attention: full attention to the valid past, causal
    within the chunk.

    q: (B, C, KV, G, hd) — one page-sized chunk of queries at absolute
    positions past_len..past_len+C−1.  k_past/v_past: (B, S, KV, hd)
    gathered pages, valid prefix ``past_len`` (scalar or (B,)).  k_new/v_new:
    (B, C, KV, hd) — the chunk's own K/V (not yet written to the pool; same
    read-before-write posture as decode_attention's ``extra_kv``).
    """
    B, C, KV, G, hd = q.shape
    S = k_past.shape[1]
    qf = q.astype(jnp.float32) * hd ** -0.5
    s_past = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_past.astype(jnp.float32))
    idx = jnp.arange(S)[None, None, None, None, :]
    s_past = jnp.where(idx < _per_row(past_len, B), s_past, NEG_INF)
    s_new = jnp.einsum("bqkgd,bckd->bkgqc", qf, k_new.astype(jnp.float32))
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]     # q i ≥ k j
    s_new = jnp.where(causal[None, None, None], s_new, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_past, s_new], axis=-1), axis=-1)
    out = (jnp.einsum("bkgqs,bskd->bqkgd", p[..., :S],
                      v_past.astype(jnp.float32))
           + jnp.einsum("bkgqc,bckd->bqkgd", p[..., S:],
                        v_new.astype(jnp.float32)))
    return out.astype(q.dtype)


def _attend_paged(q, k, v, k_pool, v_pool, page_table, layer, scales,
                  past_len, mesh):
    """Attention of the Lq fresh queries q (B, Lq, KV, G, hd) to each slot's
    cached past (positions < ``past_len``) and, causally, to their own K/V
    (B, Lq, KV, hd), read before they are written.

    Without a mesh the pool is read by ``kernels.ops.paged_attention``
    (live pages only, in place, dequantized in VMEM) whose partial softmax
    statistics ``_join_fresh`` completes; where that kernel does not apply
    (off TPU, float pools, a mesh), by the page gather and
    ``decode_attention`` / ``chunk_attention``.  Device scopes: the read is
    ``kv_read``, the rest ``attention``."""
    from repro.kernels import ops

    stats = None
    if mesh is None:
        with jax.named_scope("kv_read"):
            stats = ops.paged_attention(q, k_pool, v_pool, scales, layer,
                                        page_table, past_len)
    if stats is not None:
        with jax.named_scope("attention"):
            return _join_fresh(q, k, v, *stats)
    k_l, v_l = _gather_paged_kv(k_pool, v_pool, page_table, layer, scales)
    with jax.named_scope("attention"):
        if q.shape[1] == 1:
            return decode_attention(q, k_l, v_l, past_len, extra_kv=(k, v))
        return chunk_attention(q, k_l, v_l, past_len, k, v)


def attn_decode_paged(p, x, cfg: AttnConfig, *, pos, page_table, write_pid,
                      write_off, valid_len, k_pool, v_pool, layer,
                      scales=None, mesh=None, dp=None):
    """Paged decode step: read the slot's pages, attend, scatter the token's
    K/V into the tail page.

    page_table: (B, P) physical page ids per slot; write_pid/write_off: (B,)
    physical page + in-page offset receiving this token (the engine routes
    retired slots to the trash page 0).  valid_len: (B,) attendable logical
    prefix (= per-slot ``pos``; the fresh token enters via ``extra_kv``, so
    the possibly-stale tail entry is masked out by ``idx < valid_len``).
    scales present ⇒ int8 pages (quantize-what-you-store, DESIGN.md §4).
    With a mesh, each `model` shard owns an S-slice of every page and the
    softmax joins through two psums (``_paged_flash_shardmap``).
    Returns (out, k_pool, v_pool, new_scales).  Device scopes: the pool
    read is ``kv_read`` (``_attend_paged``), the rest ``attention``.
    """
    B = x.shape[0]
    with jax.named_scope("attention"):
        q, k, v = _project_qkv(p, x, cfg, pos=pos)
        if mesh is not None and k_pool.shape[2] % mesh.shape["model"] == 0:
            num, denom, m_glob, k_pool, v_pool, new_scales = \
                _paged_flash_shardmap(
                    q, k, v, k_pool, v_pool, scales, layer, page_table,
                    write_pid[:, None], write_off[:, None], valid_len, mesh,
                    dp or ("data",))
            out = _join_fresh(q, k, v, num, denom, m_glob)
            out = dense(p["wo"], out.reshape(B, 1, cfg.n_kv * cfg.groups
                                             * cfg.hd), kind="row")
            return out, k_pool, v_pool, new_scales
    out = _attend_paged(q, k, v, k_pool, v_pool, page_table, layer, scales,
                        valid_len, mesh)
    with jax.named_scope("attention"):
        if scales is not None:
            ks_all, vs_all = scales
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            ks_all = ks_all.at[layer, write_pid, write_off].set(
                ksc[:, 0].astype(ks_all.dtype))
            vs_all = vs_all.at[layer, write_pid, write_off].set(
                vsc[:, 0].astype(vs_all.dtype))
            k, v, new_scales = kq, vq, (ks_all, vs_all)
        else:
            new_scales = None
        k_pool = k_pool.at[layer, write_pid, write_off].set(
            k[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[layer, write_pid, write_off].set(
            v[:, 0].astype(v_pool.dtype))
        out = dense(p["wo"], out.reshape(B, 1, cfg.n_kv * cfg.groups
                                         * cfg.hd), kind="row")
    return out, k_pool, v_pool, new_scales


def attn_prefill_chunk(p, x, cfg: AttnConfig, *, pos, page_table, write_pid,
                       past_len, k_pool, v_pool, layer, scales=None,
                       mesh=None, dp=None):
    """One page-sized prefill chunk (batch of one) against the paged cache.

    x: (1, C, D) with C == page size; ``past_len`` (scalar) tokens already
    live in the pages of ``page_table`` (1, P).  The chunk's K/V are written
    as ONE page at physical id ``write_pid`` (page-aligned chunking makes
    the store a single dynamic_update_slice; ``write_pid`` 0 targets the
    trash page — used when the chunk's page is a shared prefix-cache hit
    recomputed only for its logits).  Returns (out, k_pool, v_pool, scales).
    Device scopes as in ``attn_decode_paged``.
    """
    B, C, _ = x.shape
    if B != 1:
        # the page store below writes k[:, None] at (layer, pid, 0, 0, 0):
        # a leading batch dim would silently span the LAYER axis
        raise ValueError(f"attn_prefill_chunk is batch-of-one (got B={B}); "
                         "prompts stream through chunks one request at a "
                         "time")
    with jax.named_scope("attention"):
        q, k, v = _project_qkv(p, x, cfg, pos=pos)
        if mesh is not None and k_pool.shape[2] % mesh.shape["model"] == 0:
            # the chunk is one full page: per-token write targets are the
            # same physical page at offsets 0..C−1, so each shard keeps
            # exactly its page slice (write_pid 0 = shared prefix-cache hit
            # → trash)
            pid_t = jnp.broadcast_to(jnp.asarray(write_pid, jnp.int32),
                                     (B, C))
            off_t = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[None],
                                     (B, C))
            num, denom, m_glob, k_pool, v_pool, new_scales = \
                _paged_flash_shardmap(
                    q, k, v, k_pool, v_pool, scales, layer, page_table,
                    pid_t, off_t, past_len, mesh, dp or ("data",))
            out = _join_fresh(q, k, v, num, denom, m_glob)
            out = dense(p["wo"], out.reshape(B, C, cfg.n_kv * cfg.groups
                                             * cfg.hd), kind="row")
            return out, k_pool, v_pool, new_scales
    out = _attend_paged(q, k, v, k_pool, v_pool, page_table, layer, scales,
                        past_len, mesh)
    with jax.named_scope("attention"):
        zero = jnp.zeros((), jnp.int32)
        if scales is not None:
            ks_all, vs_all = scales
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            ks_all = jax.lax.dynamic_update_slice(
                ks_all, ksc[:, None].astype(ks_all.dtype),
                (layer, write_pid, zero, zero))
            vs_all = jax.lax.dynamic_update_slice(
                vs_all, vsc[:, None].astype(vs_all.dtype),
                (layer, write_pid, zero, zero))
            k, v, new_scales = kq, vq, (ks_all, vs_all)
        else:
            new_scales = None
        k_pool = jax.lax.dynamic_update_slice(
            k_pool, k[:, None].astype(k_pool.dtype),
            (layer, write_pid, zero, zero, zero))
        v_pool = jax.lax.dynamic_update_slice(
            v_pool, v[:, None].astype(v_pool.dtype),
            (layer, write_pid, zero, zero, zero))
        out = dense(p["wo"], out.reshape(B, C, cfg.n_kv * cfg.groups
                                         * cfg.hd), kind="row")
    return out, k_pool, v_pool, new_scales


# --- speculative verify (DESIGN.md §9) ---------------------------------------
#
# One batched forward scores the pending token plus K draft proposals per
# slot.  The attention math is chunk_attention's: full attention to the valid
# cached prefix (per-row ``valid_len``), causal among the K1 fresh tokens,
# whose K/V enter via ``k_new``/``v_new`` before being written back — the
# same read-before-write posture that lets XLA alias the cache in place.
# Rejection needs NO cache surgery here: rejected tokens' K/V remain as
# stale rows above the engine's rolled-back per-slot ``pos`` and every later
# step's valid-length mask fences them until they are overwritten.

def attn_verify_cached(p, x, cfg: AttnConfig, *, pos, insert_at, valid_len,
                       k_all, v_all, layer, scales=None, mesh=None, dp=None):
    """Multi-token verify against the stacked (L, B, S, KV, hd) cache.

    x: (B, K1, D) — per slot, the pending last token plus K draft proposals;
    pos: (B, K1) absolute RoPE positions; insert_at: (B,) first cache row
    written (K1 rows land contiguously, clamped to the cache end so retired
    slots lockstep-verify harmlessly into their own tail); valid_len: (B,)
    attendable cached prefix (== the engine's per-slot ``pos``).
    scales: (ks_all, vs_all) when the cache is int8-quantized.
    With a mesh, the cached prefix runs through the S-sharded flash join
    (DESIGN.md §10) and the K1 fresh causal rows fold in replicated.
    Returns (out (B, K1, D), k_all, v_all, new_scales).
    """
    B, K1, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, pos=pos)
    if mesh is not None and k_all.shape[2] % mesh.shape["model"] == 0:
        S = k_all.shape[2]
        rows = jnp.clip(insert_at, 0, S - K1)
        num, denom, m_glob, k_all, v_all, new_scales = _decode_cached_shardmap(
            q, k, v, k_all, v_all, scales, layer, rows, valid_len, None,
            mesh, dp or ("data",))
        out = _join_fresh(q, k, v, num, denom, m_glob)
        out = dense(p["wo"], out.reshape(B, K1, cfg.n_kv * cfg.groups
                                         * cfg.hd), kind="row")
        return out, k_all, v_all, new_scales
    k_raw = jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False)
    v_raw = jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
    k_l, v_l = k_raw, v_raw
    if scales is not None:
        ks_all, vs_all = scales
        ks_l = jax.lax.dynamic_index_in_dim(ks_all, layer, 0, keepdims=False)
        vs_l = jax.lax.dynamic_index_in_dim(vs_all, layer, 0, keepdims=False)
        k_l = dequantize_kv(k_raw, ks_l)
        v_l = dequantize_kv(v_raw, vs_l)
    out = chunk_attention(q, k_l, v_l, valid_len, k, v)
    S = k_all.shape[2]
    rows = jnp.clip(insert_at, 0, S - K1)
    if scales is not None:
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        ks_all = jax.lax.dynamic_update_index_in_dim(
            ks_all, _put_rows(ks_l, ksc, rows).astype(ks_all.dtype), layer, 0)
        vs_all = jax.lax.dynamic_update_index_in_dim(
            vs_all, _put_rows(vs_l, vsc, rows).astype(vs_all.dtype), layer, 0)
        k, v, new_scales = kq, vq, (ks_all, vs_all)
    else:
        new_scales = None
    k_all = jax.lax.dynamic_update_index_in_dim(
        k_all, _put_rows(k_raw, k, rows).astype(k_all.dtype), layer, 0)
    v_all = jax.lax.dynamic_update_index_in_dim(
        v_all, _put_rows(v_raw, v, rows).astype(v_all.dtype), layer, 0)
    out = dense(p["wo"], out.reshape(B, K1, cfg.n_kv * cfg.groups * cfg.hd),
                kind="row")
    return out, k_all, v_all, new_scales


def attn_verify_paged(p, x, cfg: AttnConfig, *, pos, page_table, write_pid,
                      write_off, valid_len, k_pool, v_pool, layer,
                      scales=None, mesh=None, dp=None):
    """Multi-token verify against gathered pages (the paged twin of
    ``attn_verify_cached``).

    write_pid/write_off: (B, K1) per-token physical page + in-page offset —
    the K1 speculative tokens may straddle a page boundary, so each is
    scattered individually; the engine routes positions beyond a slot's
    live page span (speculative overshoot past the admission reservation)
    and retired slots to the trash page 0.  valid_len: (B,) attendable
    logical prefix.  Returns (out, k_pool, v_pool, new_scales).
    """
    B, K1, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, pos=pos)
    if mesh is not None and k_pool.shape[2] % mesh.shape["model"] == 0:
        num, denom, m_glob, k_pool, v_pool, new_scales = _paged_flash_shardmap(
            q, k, v, k_pool, v_pool, scales, layer, page_table, write_pid,
            write_off, valid_len, mesh, dp or ("data",))
        out = _join_fresh(q, k, v, num, denom, m_glob)
        out = dense(p["wo"], out.reshape(B, K1, cfg.n_kv * cfg.groups
                                         * cfg.hd), kind="row")
        return out, k_pool, v_pool, new_scales
    k_l, v_l = _gather_paged_kv(k_pool, v_pool, page_table, layer, scales)
    out = chunk_attention(q, k_l, v_l, valid_len, k, v)
    if scales is not None:
        ks_all, vs_all = scales
        kq, ksc = quantize_kv(k)
        vq, vsc = quantize_kv(v)
        ks_all = ks_all.at[layer, write_pid, write_off].set(
            ksc.astype(ks_all.dtype))
        vs_all = vs_all.at[layer, write_pid, write_off].set(
            vsc.astype(vs_all.dtype))
        k, v, new_scales = kq, vq, (ks_all, vs_all)
    else:
        new_scales = None
    k_pool = k_pool.at[layer, write_pid, write_off].set(k.astype(k_pool.dtype))
    v_pool = v_pool.at[layer, write_pid, write_off].set(v.astype(v_pool.dtype))
    out = dense(p["wo"], out.reshape(B, K1, cfg.n_kv * cfg.groups * cfg.hd),
                kind="row")
    return out, k_pool, v_pool, new_scales


# --- flash-chunked attention -------------------------------------------------
#
# Forward: online softmax over KV blocks (lax.scan).  Backward: a REAL flash
# backward via custom_vjp — naive autodiff through the forward scan would
# save every block's probability tensor (≈ the full L×L attention matrix in
# f32; tens of GB/device at 4k×remat and fatal at 32k).  We save only
# (q, k, v, out, m, denom) and re-derive per-block probabilities inside the
# backward scan:  dS = P ⊙ (dOut·Vᵀ − δ),  δ_i = Σ_d dOut_id·Out_id.

def flash_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None,
                    window: int = 0, kv_block: int = 1024):
    """Online-softmax attention over KV blocks.

    q: (B, Lq, KV, G, hd)   grouped query heads
    k, v: (B, Lk, KV, hd)
    q_offset: absolute position of q[0] (decode/prefill continuation).
    kv_len: number of valid KV entries (traced ok); None = Lk.
    Returns (B, Lq, KV, G, hd) in q.dtype.
    """
    if kv_len is None and isinstance(q_offset, int):
        # static masking pattern -> memory-safe custom-vjp path
        return _flash_cvjp(q, k, v, causal, q_offset, window, kv_block)
    out, _, _ = _flash_fwd_scan(q, k, v, causal, q_offset, kv_len, window,
                                kv_block)
    return out


def _mask_for(bi, blk, Lk, qpos, valid_len, causal, window):
    kpos = bi * blk + jnp.arange(blk)
    mask = kpos[None, :] < valid_len
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    return mask


def _flash_fwd_scan(q, k, v, causal, q_offset, kv_len, window, kv_block):
    B, Lq, KV, G, hd = q.shape
    Lk = k.shape[1]
    blk = min(kv_block, Lk)
    n_blk = -(-Lk // blk)
    pad = n_blk * blk - Lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blk, blk, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blk, blk, KV, hd).transpose(1, 0, 2, 3, 4)

    scale = hd ** -0.5
    qpos = q_offset + jnp.arange(Lq)
    valid_len = Lk if kv_len is None else kv_len

    def body(carry, blk_in):
        acc, m, denom, bi = carry
        kblk, vblk = blk_in                                   # (B, blk, KV, hd)
        # storage-dtype operands, f32 MXU accumulation: no full-sequence
        # f32 copies of q are materialised
        s = jnp.einsum("bqkgd,bckd->bkgqc", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        mask = _mask_for(bi, blk, Lk, qpos, valid_len, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        denom = denom * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (acc, m_new, denom, bi + 1), None

    acc0 = jnp.zeros((B, KV, G, Lq, hd), jnp.float32)
    m0 = jnp.full((B, KV, G, Lq), NEG_INF)
    d0 = jnp.zeros((B, KV, G, Lq))
    (acc, m, denom, _), _ = jax.lax.scan(body, (acc0, m0, d0, 0), (kb, vb))
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).astype(q.dtype)       # (B, Lq, KV, G, hd)
    return out, m, denom


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_cvjp(q, k, v, causal, q_offset, window, kv_block):
    out, _, _ = _flash_fwd_scan(q, k, v, causal, q_offset, None, window,
                                kv_block)
    return out


def _flash_cvjp_fwd(q, k, v, causal, q_offset, window, kv_block):
    out, m, denom = _flash_fwd_scan(q, k, v, causal, q_offset, None, window,
                                    kv_block)
    return out, (q, k, v, out, m, denom)


def _flash_cvjp_bwd(causal, q_offset, window, kv_block, res, g):
    q, k, v, out, m, denom = res
    B, Lq, KV, G, hd = q.shape
    Lk = k.shape[1]
    blk = min(kv_block, Lk)
    n_blk = -(-Lk // blk)
    pad = n_blk * blk - Lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blk, blk, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blk, blk, KV, hd).transpose(1, 0, 2, 3, 4)

    scale = hd ** -0.5
    # keep q/g/out in their storage dtype; upcast per-block inside the scan
    # (full-sequence f32 copies here were ~2.5 GB/device at mistral dims)
    gT = g.transpose(0, 2, 3, 1, 4)                           # (B,KV,G,Lq,hd)
    oT = out.transpose(0, 2, 3, 1, 4)
    delta = jnp.einsum("bkgqd,bkgqd->bkgq", gT.astype(jnp.float32),
                       oT.astype(jnp.float32))                # (B,KV,G,Lq)
    denom = jnp.maximum(denom, 1e-30)
    m = jnp.where(m <= NEG_INF / 2, 0.0, m)   # fully-masked rows: p stays 0
    qpos = q_offset + jnp.arange(Lq)

    def body(dq, blk_in):
        kblk, vblk, bi = blk_in
        s = jnp.einsum("bqkgd,bckd->bkgqc", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        mask = _mask_for(bi, blk, Lk, qpos, Lk, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / denom[..., None]      # (B,KV,G,Lq,c)
        pc = p.astype(g.dtype)
        dv = jnp.einsum("bkgqc,bkgqd->bckd", pc, gT,
                        preferred_element_type=jnp.float32)   # (B,c,KV,hd)
        ds = p * (jnp.einsum("bkgqd,bckd->bkgqc", gT, vblk,
                             preferred_element_type=jnp.float32)
                  - delta[..., None])
        dsc = ds.astype(q.dtype)
        dk = jnp.einsum("bkgqc,bqkgd->bckd", dsc, q,
                        preferred_element_type=jnp.float32) * scale
        dq = dq + jnp.einsum("bkgqc,bckd->bqkgd", dsc, kblk,
                             preferred_element_type=jnp.float32) * scale
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Lq, KV, G, hd), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(n_blk)))
    dk = dk_b.transpose(1, 0, 2, 3, 4).reshape(B, n_blk * blk, KV, hd)
    dv = dv_b.transpose(1, 0, 2, 3, 4).reshape(B, n_blk * blk, KV, hd)
    if pad:
        dk, dv = dk[:, :Lk], dv[:, :Lk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_cvjp.defvjp(_flash_cvjp_fwd, _flash_cvjp_bwd)


# --- full module -------------------------------------------------------------

def init_kv_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.n_kv, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _project_qkv(p, x, cfg: AttnConfig, kv_src=None, pos=None,
                 rope_ok=True):
    B, L, _ = x.shape
    hd, KV, G = cfg.hd, cfg.n_kv, cfg.groups
    kv_src = x if kv_src is None else kv_src
    Lk = kv_src.shape[1]
    q = dense(p["wq"], x, kind="col").reshape(B, L, KV, G, hd)
    k = dense(p["wk"], kv_src, kind="col").reshape(B, Lk, KV, hd)
    v = dense(p["wv"], kv_src, kind="col").reshape(B, Lk, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k)
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    if rope_ok:
        q = rope(q.reshape(B, L, KV * G, hd), pos, cfg.rope_theta,
                 cfg.rope_sections).reshape(B, L, KV, G, hd)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_sections)
    return q, k, v


KV_QMAX = 127.0


def quantize_kv(t):
    """(B, L, KV, hd) float -> (int8 values, per-(B,L,KV) bf16 scales).

    Symmetric per-token-per-head max-abs quantization — the serving-side KV
    cache representation (halves cache HBM vs bf16; the same quantize-what-
    you-store posture as the paper's §4 weight indices)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / KV_QMAX
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -KV_QMAX, KV_QMAX)
    qi, sc = q.astype(jnp.int8), scale[..., 0].astype(jnp.bfloat16)
    # Round-trip error probe against the *stored* (int8, bf16-scale) pair —
    # inert unless a probes.layer frame is open in the current trace (the
    # shard_map TP call sites are auto-fenced by the trace-token guard).
    probes.tap_kv(t, qi, sc)
    return qi, sc


def dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def _pay_dtype(dt):
    """Collective payload dtype: bf16 caches ship bf16 partial outputs
    (TPU posture — ~3 significant digits, inside the int8-KV noise floor);
    f32 caches ship f32 so the TP engine stays at the single-device noise
    floor (the tp-parity rig asserts token-for-token equality)."""
    return jnp.float32 if dt == jnp.float32 else jnp.bfloat16


def _join_fresh(q, k_new, v_new, num, denom, m_glob):
    """Fold the Lq fresh tokens (causal among themselves, all-visible to
    later ones) into the partial softmax statistics of the sharded past,
    then normalise — the online twin of chunk_attention's concat-softmax.

    q: (B, Lq, KV, G, hd); k_new/v_new: (B, Lq, KV, hd);
    num: (B, KV, G, Lq, hd); denom/m_glob: (B, KV, G, Lq).
    Returns (B, Lq, KV, G, hd) in q.dtype.
    """
    B, Lq, KV, G, hd = q.shape
    qf = q.astype(jnp.float32) * hd ** -0.5
    s_new = jnp.einsum("bqkgd,bckd->bkgqc", qf, k_new.astype(jnp.float32))
    causal = jnp.arange(Lq)[:, None] >= jnp.arange(Lq)[None, :]
    s_new = jnp.where(causal[None, None, None], s_new, NEG_INF)
    m2 = jnp.maximum(m_glob, jnp.max(s_new, axis=-1))
    corr = jnp.exp(m_glob - m2)
    e_new = jnp.exp(s_new - m2[..., None])
    num = num * corr[..., None] + jnp.einsum(
        "bkgqc,bckd->bkgqd", e_new, v_new.astype(jnp.float32))
    denom = denom * corr + jnp.sum(e_new, axis=-1)
    out = num / jnp.maximum(denom[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def _decode_cached_shardmap(q, k, v, k_all, v_all, scales, layer, ins, vlen,
                            excl, mesh, dp):
    """Explicit flash attention over the `model`-sharded cache sequence dim.

    The XLA-auto path all-gathers each layer's (B, S, KV, hd) cache slice
    inside the decode loop (SPMD cannot re-shard the traced-index
    dynamic-update/read efficiently — confirmed in the dry-run HLO, ~1 GB
    f32 per layer).  Here every shard: reads its LOCAL S-slice, computes
    partial scores, joins softmax statistics with two tiny psums
    ((B,H,Lq) max/denominator and the (B,H,Lq,hd) partial output), and
    writes the Lq fresh tokens' K/V only on their owning shards.
    Collective bytes per layer drop from O(B·S·KV·hd) to O(B·H·hd·Lq).

    Generalised from the PR-1 decode-only form (DESIGN.md §10): Lq >= 1
    fresh rows per slot (speculative verify), ``ins``/``vlen`` scalars OR
    per-row (B,) vectors (the ServeEngine's continuous batching), and an
    optional per-row ``excl`` mask (decode's stale/ring insert row).  The
    Lq rows land contiguously at ins[b]..ins[b]+Lq−1 and must fit one
    shard's slice count (Lq <= S/tp — the engine validates); non-owned
    rows rewrite their current value (in-place friendly, collision-free
    because consecutive rows map injectively under mod-S_loc).

    Returns (num, denom, m_glob, k_all, v_all, scales) — the caller folds
    in the fresh tokens' causal softmax terms (``_join_fresh``).
    """
    B, Lq = q.shape[0], q.shape[1]
    b_ax = dp if B % _dp_size(mesh, dp) == 0 else None
    qspec = P(b_ax, None, None, None, None)
    cspec = P(None, b_ax, "model", None, None)
    sspec = P(None, b_ax, "model", None)
    have_sc = scales is not None
    have_ex = excl is not None
    hd = q.shape[-1]
    ins_v = jnp.broadcast_to(jnp.asarray(ins, jnp.int32), (B,))
    vlen_v = jnp.broadcast_to(jnp.asarray(vlen, jnp.int32), (B,))
    ex_v = (jnp.broadcast_to(jnp.asarray(excl, jnp.int32), (B,))
            if have_ex else jnp.zeros((B,), jnp.int32))

    def f(q, k, v, k_all, v_all, ks, vs, layer, ins, vlen, ex):
        m_id = jax.lax.axis_index("model")
        S_loc = k_all.shape[2]
        start = m_id * S_loc
        k_raw = jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False)
        v_raw = jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
        k_l, v_l = k_raw, v_raw
        if have_sc:
            # dequantize to bf16, not f32: halves the materialised copies
            k_l = dequantize_kv(k_raw, jax.lax.dynamic_index_in_dim(
                ks, layer, 0, keepdims=False)).astype(jnp.bfloat16)
            v_l = dequantize_kv(v_raw, jax.lax.dynamic_index_in_dim(
                vs, layer, 0, keepdims=False)).astype(jnp.bfloat16)
        # scores: operands stay in cache dtype; accumulate f32 on the MXU —
        # avoids materialising f32 copies of the K/V slices (2× HBM)
        qf = (q.astype(jnp.float32) * hd ** -0.5).astype(k_l.dtype)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_l,
                       preferred_element_type=jnp.float32)
        gidx = start + jnp.arange(S_loc)
        mask = gidx[None, :] < vlen[:, None]
        if have_ex:
            mask = mask & (gidx[None, :] != ex[:, None])
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        m_glob = jnp.maximum(jax.lax.pmax(m_loc, "model"), NEG_INF / 10)
        p = jnp.exp(s - m_glob[..., None])
        denom = jax.lax.psum(jnp.sum(p, axis=-1), "model")
        pay = _pay_dtype(v_l.dtype)
        num = jax.lax.psum(
            jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_l.dtype), v_l,
                       preferred_element_type=jnp.float32)
            .astype(pay), "model").astype(jnp.float32)
        # write the Lq fresh rows on their owning shards only (same-value
        # rewrite elsewhere keeps the store unconditional => in-place
        # friendly; mod-S_loc keeps a slot's row targets collision-free)
        rows = ins[:, None] + jnp.arange(Lq)[None, :]          # (B, Lq)
        owner = (rows >= start) & (rows < start + S_loc)
        loc = (rows - start) % S_loc
        bidx = jnp.arange(B)[:, None]

        def put(sl, new):
            cur = sl[bidx, loc]
            ow = owner.reshape(owner.shape + (1,) * (cur.ndim - 2))
            upd = jnp.where(ow, new.astype(sl.dtype), cur)
            return sl.at[bidx, loc].set(upd)

        if have_sc:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            ks = jax.lax.dynamic_update_index_in_dim(
                ks, put(jax.lax.dynamic_index_in_dim(ks, layer, 0,
                                                     keepdims=False), ksc),
                layer, 0)
            vs = jax.lax.dynamic_update_index_in_dim(
                vs, put(jax.lax.dynamic_index_in_dim(vs, layer, 0,
                                                     keepdims=False), vsc),
                layer, 0)
            k, v = kq, vq
        k_all = jax.lax.dynamic_update_index_in_dim(
            k_all, put(k_raw, k), layer, 0)
        v_all = jax.lax.dynamic_update_index_in_dim(
            v_all, put(v_raw, v), layer, 0)
        return num, denom, m_glob, k_all, v_all, ks, vs

    from repro.distributed.compat import shard_map
    ks, vs = scales if have_sc else (jnp.zeros((), jnp.int8),) * 2
    num, denom, m_glob, k_all, v_all, ks, vs = shard_map(
        f, mesh=mesh,
        in_specs=(qspec, P(b_ax, None, None, None), P(b_ax, None, None, None),
                  cspec, cspec,
                  sspec if have_sc else P(),
                  sspec if have_sc else P(),
                  P(), P(b_ax), P(b_ax), P(b_ax)),
        out_specs=(P(b_ax, None, None, None, None),
                   P(b_ax, None, None, None),
                   P(b_ax, None, None, None),
                   cspec, cspec,
                   sspec if have_sc else P(),
                   sspec if have_sc else P()),
        check_vma=False,
    )(q, k, v, k_all, v_all, ks, vs, layer, ins_v, vlen_v, ex_v)
    new_scales = (ks, vs) if have_sc else None
    return num, denom, m_glob, k_all, v_all, new_scales


def _paged_flash_shardmap(q, k_new, v_new, k_pool, v_pool, scales, layer,
                          page_table, write_pid, write_off, vlen, mesh, dp):
    """The paged twin of ``_decode_cached_shardmap`` (DESIGN.md §10): each
    `model` shard owns an S-slice of EVERY page — the pool's in-page token
    axis is sharded, so the page *table* stays one (replicated) row per
    slot and every shard makes identical allocation decisions by
    construction.

    Each shard gathers its local slice of the slot's pages, masks by the
    GLOBAL token position (local index t of page p maps to
    ``p·page + shard·page_loc + t%page_loc``), joins softmax statistics
    with the same two psums, and scatters the Lq fresh tokens it owns
    (``write_off`` decides the owner; non-owned tokens are routed to the
    shard's trash page 0 so trash-bound and owned writes can never collide
    on a live location).

    q: (B, Lq, KV, G, hd); k_new/v_new: (B, Lq, KV, hd); write_pid/
    write_off: (B, Lq) per-token physical page + GLOBAL in-page offset;
    vlen: (B,) attendable logical prefix.  Returns (num, denom, m_glob,
    k_pool, v_pool, scales) for ``_join_fresh``.
    """
    B, Lq = q.shape[0], q.shape[1]
    b_ax = dp if B % _dp_size(mesh, dp) == 0 else None
    page = k_pool.shape[2]                      # global tokens per page
    have_sc = scales is not None
    hd = q.shape[-1]
    pspec = P(None, None, "model", None, None)
    sspec = P(None, None, "model", None)
    vlen_v = jnp.broadcast_to(jnp.asarray(vlen, jnp.int32), (B,))

    def f(q, k, v, k_pool, v_pool, ks, vs, layer, pt, pid, off, vlen):
        m_id = jax.lax.axis_index("model")
        page_loc = k_pool.shape[2]              # = page // tp
        k_l, v_l = _gather_paged_kv(k_pool, v_pool, pt, layer,
                                    (ks, vs) if have_sc else None)
        # int8 pages dequantize to f32 (exactly the local paged path's
        # numerics — the tp rig asserts token parity against it); bf16
        # pools stay bf16 and ship bf16 payloads
        if k_l.dtype not in (jnp.float32, jnp.bfloat16):
            k_l, v_l = k_l.astype(jnp.float32), v_l.astype(jnp.float32)
        qf = (q.astype(jnp.float32) * hd ** -0.5).astype(k_l.dtype)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_l,
                       preferred_element_type=jnp.float32)
        t = jnp.arange(k_l.shape[1])
        gpos = (t // page_loc) * page + m_id * page_loc + t % page_loc
        mask = gpos[None, :] < vlen[:, None]
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)
        m_glob = jnp.maximum(jax.lax.pmax(m_loc, "model"), NEG_INF / 10)
        pr = jnp.exp(s - m_glob[..., None])
        denom = jax.lax.psum(jnp.sum(pr, axis=-1), "model")
        pay = _pay_dtype(v_l.dtype)
        num = jax.lax.psum(
            jnp.einsum("bkgqs,bskd->bkgqd", pr.astype(v_l.dtype), v_l,
                       preferred_element_type=jnp.float32)
            .astype(pay), "model").astype(jnp.float32)
        # scatter the fresh tokens this shard owns; everyone else's land in
        # the local trash page (page 0 is never allocated — DESIGN.md §8)
        owner = (off // page_loc) == m_id
        pid_w = jnp.where(owner, pid, 0)
        off_w = jnp.where(owner, off % page_loc, 0)
        if have_sc:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            ks = ks.at[layer, pid_w, off_w].set(ksc.astype(ks.dtype))
            vs = vs.at[layer, pid_w, off_w].set(vsc.astype(vs.dtype))
            k, v = kq, vq
        k_pool = k_pool.at[layer, pid_w, off_w].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[layer, pid_w, off_w].set(v.astype(v_pool.dtype))
        return num, denom, m_glob, k_pool, v_pool, ks, vs

    from repro.distributed.compat import shard_map
    ks, vs = scales if have_sc else (jnp.zeros((), jnp.int8),) * 2
    num, denom, m_glob, k_pool, v_pool, ks, vs = shard_map(
        f, mesh=mesh,
        in_specs=(P(b_ax, None, None, None, None),
                  P(b_ax, None, None, None), P(b_ax, None, None, None),
                  pspec, pspec,
                  sspec if have_sc else P(),
                  sspec if have_sc else P(),
                  P(), P(b_ax, None), P(b_ax, None), P(b_ax, None),
                  P(b_ax)),
        out_specs=(P(b_ax, None, None, None, None),
                   P(b_ax, None, None, None),
                   P(b_ax, None, None, None),
                   pspec, pspec,
                   sspec if have_sc else P(),
                   sspec if have_sc else P()),
        check_vma=False,
    )(q, k_new, v_new, k_pool, v_pool, ks, vs, layer, page_table,
      write_pid, write_off, vlen_v)
    new_scales = (ks, vs) if have_sc else None
    return num, denom, m_glob, k_pool, v_pool, new_scales


def _dp_size(mesh, dp):
    n = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n *= mesh.shape[a]
    return n


def attn_decode_cached(p, x, cfg: AttnConfig, *, pos, insert_at, valid_len,
                       k_all, v_all, layer, scales=None, mesh=None, dp=None):
    """Decode step against a *stacked* (L, B, S, KV, hd) cache carried
    through the layer scan — the new token's K/V are dynamic-update-sliced
    into the carry (aliased in place by XLA's while-loop buffer assignment,
    so the cache is never double-buffered), then the layer's slice is read
    back for the attention einsum.

    insert_at: ring/linear write position; valid_len: attendable prefix.
    Both accept either scalars (uniform batch — training smoke tests, the
    dry-run decode cells) or (B,) vectors (per-slot cache positions — the
    ServeEngine's continuous batching, where every batch row is a slot at
    its own sequence offset); the sharded flash-decode path supports both
    (DESIGN.md §10) whenever S divides the TP degree.
    scales: (ks_all, vs_all) (L, B, S, KV) when the cache is int8-quantized.
    Returns (out, k_all, v_all, new_scales).
    """
    B = x.shape[0]
    hd = cfg.hd
    q, k, v = _project_qkv(p, x, cfg, pos=pos)
    vec = jnp.ndim(insert_at) == 1

    if mesh is not None and k_all.shape[2] % mesh.shape["model"] == 0:
        # explicit flash-decode over the S-sharded cache (see
        # _decode_cached_shardmap) + fold in the current token's term
        num, denom, m_glob, k_all, v_all, new_scales = _decode_cached_shardmap(
            q, k, v, k_all, v_all, scales, layer, insert_at, valid_len,
            insert_at, mesh, dp or ("data",))
        out = _join_fresh(q, k, v, num, denom, m_glob)
    else:
        # mesh with a non-dividing S falls through to the local form (XLA
        # gathers the cache — correct, none of §5's bandwidth win; the
        # ServeEngine validates divisibility up front)
        # READ the stale slice first — a carry read after the update forces
        # XLA to materialise a cache copy per step; read-before-write aliases.
        k_raw = jax.lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False)
        v_raw = jax.lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
        k_l, v_l = k_raw, v_raw
        if scales is not None:
            ks_all, vs_all = scales
            ks_l = jax.lax.dynamic_index_in_dim(ks_all, layer, 0,
                                                keepdims=False)
            vs_l = jax.lax.dynamic_index_in_dim(vs_all, layer, 0,
                                                keepdims=False)
            k_l = dequantize_kv(k_raw, ks_l)
            v_l = dequantize_kv(v_raw, vs_l)
        # stale cache: current slot may hold an evicted ring entry — exclude
        # it; the fresh K/V enter through extra_kv.
        out = decode_attention(q, k_l, v_l, valid_len, exclude=insert_at,
                               extra_kv=(k, v))
        zero = jnp.zeros((), jnp.int32)
        if scales is not None:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            if vec:
                ks_l = _put_rows(ks_l, ksc, insert_at)
                vs_l = _put_rows(vs_l, vsc, insert_at)
                ks_all = jax.lax.dynamic_update_index_in_dim(
                    ks_all, ks_l.astype(ks_all.dtype), layer, 0)
                vs_all = jax.lax.dynamic_update_index_in_dim(
                    vs_all, vs_l.astype(vs_all.dtype), layer, 0)
            else:
                ks_all = jax.lax.dynamic_update_slice(
                    ks_all, ksc[None].astype(ks_all.dtype),
                    (layer, zero, insert_at, zero))
                vs_all = jax.lax.dynamic_update_slice(
                    vs_all, vsc[None].astype(vs_all.dtype),
                    (layer, zero, insert_at, zero))
            k, v = kq, vq
            new_scales = (ks_all, vs_all)
        else:
            new_scales = None
        if vec:
            # per-row write offsets: vmap a row-local dynamic_update_slice
            # over the batch/slot dimension
            k_all = jax.lax.dynamic_update_index_in_dim(
                k_all, _put_rows(k_raw, k, insert_at).astype(k_all.dtype),
                layer, 0)
            v_all = jax.lax.dynamic_update_index_in_dim(
                v_all, _put_rows(v_raw, v, insert_at).astype(v_all.dtype),
                layer, 0)
        else:
            k_all = jax.lax.dynamic_update_slice(
                k_all, k[None].astype(k_all.dtype),
                (layer, zero, insert_at, zero, zero))
            v_all = jax.lax.dynamic_update_slice(
                v_all, v[None].astype(v_all.dtype),
                (layer, zero, insert_at, zero, zero))
    out = dense(p["wo"], out.reshape(B, 1, cfg.n_kv * cfg.groups * cfg.hd),
                kind="row")
    return out, k_all, v_all, new_scales


def attn_apply(p, x, cfg: AttnConfig, *, pos=None, cache=None, cache_index=None,
               kv_override=None, kv_valid_len=None, return_kv=False,
               mesh=None):
    """General attention forward.

    x: (B, L, D).  pos: positions (B, L) or (3, B, L).  If ``cache`` is given
    (decode), new K/V are written at ``cache_index`` and attention runs over
    the cache.  ``kv_override``: (B, Lk, D) encoder memory for cross-attn
    (RoPE skipped, cache unused).  ``kv_valid_len``: decode semantics — every
    cache entry below this length is attendable (causality implicit: the
    cache holds only past tokens + the one just written); used both for
    linear caches (pos+1) and ring-buffer windows (min(pos+1, window)).
    Returns (out, new_cache).
    """
    B, L, _ = x.shape
    hd, KV, G = cfg.hd, cfg.n_kv, cfg.groups
    q = dense(p["wq"], x, kind="col").reshape(B, L, KV, G, hd)
    kv_src = x if kv_override is None else kv_override
    Lk = kv_src.shape[1]
    k = dense(p["wk"], kv_src, kind="col").reshape(B, Lk, KV, hd)
    v = dense(p["wv"], kv_src, kind="col").reshape(B, Lk, KV, hd)

    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q)
        k = rms_norm(p["k_norm"], k)

    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    if kv_override is None:  # rotary only for self-attention
        q = rope(q.reshape(B, L, KV * G, hd), pos, cfg.rope_theta,
                 cfg.rope_sections).reshape(B, L, KV, G, hd)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_sections)

    new_cache = None
    q_offset = 0
    kv_len = None
    if cache is not None:
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype),
                                                 cache_index, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype),
                                                 cache_index, axis=1)
        new_cache = {"k": kc, "v": vc}
        k, v = kc, vc
        q_offset = cache_index
        kv_len = cache_index + L

    causal = cfg.causal and kv_override is None
    window = cfg.window
    if kv_valid_len is not None:
        kv_len, causal, window, q_offset = kv_valid_len, False, 0, 0

    if mesh is not None and L > 1 and L % mesh.shape["model"] == 0:
        # GQA head counts rarely divide the TP axis (kv=4..32 vs 16); left
        # to sharding propagation, q/out/g replicate at (B, S, H, hd) —
        # sequence-sharding the attention internals keeps them 1/TP-sized
        # (flash fwd/bwd are row-local in Lq; dk/dv partials psum)
        from repro.distributed.sharding import dp_axes, named
        sp = named(mesh, P(dp_axes(mesh), "model", None, None, None))
        q = jax.lax.with_sharding_constraint(q, sp)

    if L == 1 and kv_valid_len is not None:   # decode fast path
        out = decode_attention(q, k, v, kv_len)
    else:
        out = flash_attention(q, k, v, causal=causal,
                              q_offset=q_offset, kv_len=kv_len,
                              window=window, kv_block=cfg.kv_block)
        if mesh is not None and L > 1 and L % mesh.shape["model"] == 0:
            from repro.distributed.sharding import dp_axes, named
            out = jax.lax.with_sharding_constraint(
                out, named(mesh, P(dp_axes(mesh), "model", None, None,
                                   None)))
    out = dense(p["wo"], out.reshape(B, L, KV * G * hd), kind="row")
    if return_kv:  # prefill: emit this layer's K/V as the cache plane
        return out, {"k": k, "v": v}
    return out, new_cache
