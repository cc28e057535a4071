"""Model assembly for every LM-family architecture in the pool.

One entry point per phase:
    init_params(key, cfg)                       -> params pytree
    loss_fn(params, cfg, batch, mesh)           -> (loss, metrics)   [train]
    prefill(params, cfg, batch, mesh)           -> (logits, cache)   [prefill]
    decode_step(params, cfg, tokens, pos, cache, mesh) -> (logits, cache)
    init_cache(cfg, batch, max_len, dtype)      -> cache pytree

Families: dense / moe / vlm (precomputed patch embeddings in, M-RoPE),
audio (whisper enc-dec; precomputed frame embeddings in), ssm_rwkv (RWKV6),
hybrid (zamba2: Mamba2 stack + one shared attention block re-applied every
`shared_attn_every` layers with a concat-skip from the embeddings).

Depth is always a lax.scan over stacked layer params (O(1) HLO in depth);
`cfg.remat` checkpoints each block.  The paper's technique enters through
(a) `ffn_act` quantization sites and (b) dense() accepting codebook-index
weights (see models/layers.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, probes
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import rwkv as R
from repro.distributed.sharding import shard_act, dp_axes
from jax.sharding import PartitionSpec as P

__all__ = ["init_params", "loss_fn", "forward", "prefill", "decode_step",
           "init_cache", "init_paged_cache", "prefill_chunk", "verify_step",
           "attn_cfg", "moe_cfg", "ssm_cfg", "rwkv_cfg"]

_PAGED_FAMILIES = ("dense", "moe")   # KV-cache LMs the paged path serves


# --- sub-configs -------------------------------------------------------------

def attn_cfg(cfg, *, causal=True, window=None) -> A.AttnConfig:
    return A.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
        head_dim=cfg.hd, qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
        rope_sections=cfg.rope_sections,
        window=cfg.window if window is None else window,
        causal=causal, kv_block=cfg.kv_block)


def moe_cfg(cfg) -> M.MoEConfig:
    return M.MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       n_experts=cfg.n_experts, top_k=cfg.top_k,
                       capacity_factor=cfg.moe_capacity,
                       token_chunks=cfg.moe_token_chunks, fsdp=cfg.fsdp,
                       act_kind=cfg.act_kind, act_levels=cfg.act_levels)


def ssm_cfg(cfg) -> S.SSMConfig:
    return S.SSMConfig(d_model=cfg.d_model, d_state=cfg.ssm_state,
                       head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk,
                       act_kind="silu", act_levels=cfg.act_levels)


def rwkv_cfg(cfg) -> R.RWKVConfig:
    return R.RWKVConfig(d_model=cfg.d_model, head_dim=cfg.rwkv_head_dim,
                        d_ff=cfg.d_ff, chunk=cfg.ssm_chunk,
                        act_levels=cfg.act_levels)


_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16}


def _dtype(cfg):
    return _DTYPES[cfg.dtype]


# --- init --------------------------------------------------------------------

def _stack_init(key, n: int, init_one):
    """vmap an init function over layer keys → stacked (n, ...) params."""
    return jax.vmap(init_one)(jax.random.split(key, n))


def _dense_block_init(key, cfg, dt):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.rms_norm_init(cfg.d_model, dt),
            "attn": A.attn_init(k1, attn_cfg(cfg), dt),
            "ln2": L.rms_norm_init(cfg.d_model, dt),
            "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dt)}


def _moe_block_init(key, cfg, dt):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.rms_norm_init(cfg.d_model, dt),
            "attn": A.attn_init(k1, attn_cfg(cfg), dt),
            "ln2": L.rms_norm_init(cfg.d_model, dt),
            "moe": M.moe_init(k2, moe_cfg(cfg), dt)}


def _rwkv_block_init(key, cfg, dt):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.rms_norm_init(cfg.d_model, dt),
            "tm": R.rwkv_tm_init(k1, rwkv_cfg(cfg), dt),
            "ln2": L.rms_norm_init(cfg.d_model, dt),
            "cm": R.rwkv_cm_init(k2, rwkv_cfg(cfg), dt)}


def _mamba_block_init(key, cfg, dt):
    return {"ln": L.rms_norm_init(cfg.d_model, dt),
            "ssm": S.ssm_init(key, ssm_cfg(cfg), dt)}


def _shared_block_init(key, cfg, dt):
    """Zamba shared transformer block: concat(h, embed) -> d, attn, mlp."""
    k0, k1, k2 = jax.random.split(key, 3)
    return {"in_proj": L.dense_init(k0, 2 * cfg.d_model, cfg.d_model, dt),
            "ln1": L.rms_norm_init(cfg.d_model, dt),
            "attn": A.attn_init(k1, attn_cfg(cfg), dt),
            "ln2": L.rms_norm_init(cfg.d_model, dt),
            "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dt)}


def _enc_block_init(key, cfg, dt):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.layer_norm_init(cfg.d_model, dt),
            "attn": A.attn_init(k1, attn_cfg(cfg, causal=False), dt),
            "ln2": L.layer_norm_init(cfg.d_model, dt),
            "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dt)}


def _dec_block_init(key, cfg, dt):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": L.layer_norm_init(cfg.d_model, dt),
            "attn": A.attn_init(k1, attn_cfg(cfg), dt),
            "ln_x": L.layer_norm_init(cfg.d_model, dt),
            "xattn": A.attn_init(k2, attn_cfg(cfg, causal=False), dt),
            "ln2": L.layer_norm_init(cfg.d_model, dt),
            "mlp": L.mlp_init(k3, cfg.d_model, cfg.d_ff, dt)}


def init_params(key, cfg):
    dt = _dtype(cfg)
    keys = jax.random.split(key, 8)
    p = {}
    if cfg.family != "vlm":
        p["embed"] = L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dt)
    else:
        p["embed"] = L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(keys[1], cfg.d_model, cfg.padded_vocab, dt)
    p["final_norm"] = (L.layer_norm_init(cfg.d_model, dt)
                       if cfg.family == "audio"
                       else L.rms_norm_init(cfg.d_model, dt))

    if cfg.family in ("dense", "vlm"):
        p["blocks"] = _stack_init(keys[2], cfg.n_layers,
                                  lambda k: _dense_block_init(k, cfg, dt))
    elif cfg.family == "moe":
        p["blocks"] = _stack_init(keys[2], cfg.n_layers,
                                  lambda k: _moe_block_init(k, cfg, dt))
    elif cfg.family == "ssm_rwkv":
        p["blocks"] = _stack_init(keys[2], cfg.n_layers,
                                  lambda k: _rwkv_block_init(k, cfg, dt))
    elif cfg.family == "hybrid":
        p["blocks"] = _stack_init(keys[2], cfg.n_layers,
                                  lambda k: _mamba_block_init(k, cfg, dt))
        p["shared"] = _shared_block_init(keys[3], cfg, dt)
    elif cfg.family == "audio":
        p["enc_pos"] = {"table": (jax.random.normal(keys[4],
                        (cfg.enc_len, cfg.d_model)) * 0.02).astype(dt)}
        p["enc_blocks"] = _stack_init(keys[5], cfg.enc_layers,
                                      lambda k: _enc_block_init(k, cfg, dt))
        p["blocks"] = _stack_init(keys[2], cfg.n_layers,
                                  lambda k: _dec_block_init(k, cfg, dt))
        p["enc_norm"] = L.layer_norm_init(cfg.d_model, dt)
    else:
        raise ValueError(cfg.family)
    return p


# --- block forwards (single layer; scanned over the stack) -------------------

def _dense_block(p, x, cfg, mesh, pos, cache=None, ci=None, acfg=None,
                 vlen=None):
    acfg = acfg or attn_cfg(cfg)
    a, kv = A.attn_apply(p["attn"], L.rms_norm(p["ln1"], x), acfg,
                         pos=pos, cache=cache, cache_index=ci,
                         kv_valid_len=vlen, mesh=mesh)
    x = shard_act(x + a, mesh)
    if "moe" in p:
        y = M.moe_apply(p["moe"], L.rms_norm(p["ln2"], x), moe_cfg(cfg), mesh)
    else:
        y = L.swiglu(p["mlp"], L.rms_norm(p["ln2"], x),
                     cfg.act_kind, cfg.act_levels, mesh)
    return shard_act(x + y, mesh), kv


def _act_spec(cfg, mesh, x):
    """Pure-DP batch layout for sequential-scan families: sharding S or D
    over `model` wraps the time scan in per-layer gathers; batch over
    (dp × model) keeps every WKV step device-local (ZeRO-3 supplies the
    weights).  Falls back to the default policy when batch doesn't divide."""
    if mesh is None or not cfg.batch_over_model:
        return None
    ax = dp_axes(mesh) + ("model",)
    total = 1
    for a in ax:
        total *= mesh.shape[a]
    if x.shape[0] % total == 0:
        return P(ax, *([None] * (x.ndim - 1)))
    return None


def _rwkv_block(p, x, cfg, mesh, state=None, decode=False):
    rcfg = rwkv_cfg(cfg)
    f_tm = R.rwkv_tm_decode if decode else R.rwkv_tm_apply
    f_cm = R.rwkv_cm_decode if decode else R.rwkv_cm_apply
    spec = _act_spec(cfg, mesh, x)
    tm, st_tm = f_tm(p["tm"], L.rms_norm(p["ln1"], x), rcfg, state)
    x = shard_act(x + tm, mesh, spec)
    cm, st_cm = f_cm(p["cm"], L.rms_norm(p["ln2"], x), rcfg, state)
    x = shard_act(x + cm, mesh, spec)
    return x, {**st_tm, **st_cm}


def _mamba_block(p, x, cfg, mesh, cache=None, decode=False):
    scfg = ssm_cfg(cfg)
    if decode:
        y, new_cache = S.ssm_decode_step(p["ssm"], L.rms_norm(p["ln"], x),
                                         scfg, cache)
    else:
        y, new_cache = S.ssm_apply(p["ssm"], L.rms_norm(p["ln"], x), scfg), None
    return shard_act(x + y, mesh, _act_spec(cfg, mesh, x)), new_cache


def _shared_block(p, x, x0, cfg, mesh, pos, cache=None, ci=None, window=None,
                  vlen=None):
    h = L.dense(p["in_proj"], jnp.concatenate([x, x0], axis=-1))
    acfg = attn_cfg(cfg, window=window)
    a, kv = A.attn_apply(p["attn"], L.rms_norm(p["ln1"], h), acfg,
                         pos=pos, cache=cache, cache_index=ci,
                         kv_valid_len=vlen, mesh=mesh)
    h = h + a
    h = h + L.swiglu(p["mlp"], L.rms_norm(p["ln2"], h),
                     cfg.act_kind, cfg.act_levels, mesh)
    return shard_act(x + h, mesh, _act_spec(cfg, mesh, x)), kv


def _enc_block(p, x, cfg, mesh):
    acfg = attn_cfg(cfg, causal=False)
    a, _ = A.attn_apply(p["attn"], L.layer_norm(p["ln1"], x), acfg,
                        mesh=mesh)
    x = shard_act(x + a, mesh)
    y = L.mlp_block(p["mlp"], L.layer_norm(p["ln2"], x),
                    cfg.act_kind, cfg.act_levels, mesh)
    return shard_act(x + y, mesh)


def _dec_block(p, x, memory, cfg, mesh, pos, cache=None, ci=None, vlen=None):
    a, kv = A.attn_apply(p["attn"], L.layer_norm(p["ln1"], x),
                         attn_cfg(cfg), pos=pos, cache=cache, cache_index=ci,
                         kv_valid_len=vlen, mesh=mesh)
    x = shard_act(x + a, mesh)
    c, _ = A.attn_apply(p["xattn"], L.layer_norm(p["ln_x"], x),
                        attn_cfg(cfg, causal=False), kv_override=memory)
    x = shard_act(x + c, mesh)
    y = L.mlp_block(p["mlp"], L.layer_norm(p["ln2"], x),
                    cfg.act_kind, cfg.act_levels, mesh)
    return shard_act(x + y, mesh), kv


# --- scan helpers ------------------------------------------------------------

def _unroll(cfg):
    return True if cfg.scan_unroll else 1


def _scan(block_fn, x, stacked, cfg, with_cache=False, cache=None):
    """scan over stacked layer params (and per-layer caches)."""
    fn = jax.checkpoint(block_fn) if cfg.remat else block_fn

    if with_cache:
        def body(h, xs):
            p_l, c_l = xs
            h, c_new = fn(p_l, h, c_l)
            return h, c_new
        x, new_cache = jax.lax.scan(body, x, (stacked, cache),
                                    unroll=_unroll(cfg))
        return x, new_cache

    def body(h, p_l):
        h, _ = fn(p_l, h, None)
        return h, None
    x, _ = jax.lax.scan(body, x, stacked, unroll=_unroll(cfg))
    return x, None


# --- forward (train / prefill trunk) ------------------------------------------

def _logits(p, cfg, x):
    if cfg.tie_embeddings:
        t = (dispatch.dequantize(p["embed"]["w_idx"], p["embed"]["codebook"],
                                 p["embed"]["codebook"].dtype)
             if "w_idx" in p["embed"] else p["embed"]["table"])
        logits = jnp.dot(x, t.T, preferred_element_type=jnp.float32)
    else:
        logits = L.dense(p["lm_head"], x, kind="col").astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab:  # mask padded ids
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    return logits


def _encoder(p, cfg, frames, mesh):
    x = frames.astype(_dtype(cfg)) + p["enc_pos"]["table"][None, :frames.shape[1]]

    def blk(p_l, h, _):
        return _enc_block(p_l, h, cfg, mesh), None
    x, _ = _scan(blk, x, p["enc_blocks"], cfg)
    return L.layer_norm(p["enc_norm"], x)


def forward(params, cfg, batch, mesh=None):
    """Trunk forward → logits (B, L, padded_vocab) f32.

    batch keys: 'tokens' (B, L) always (labels derived by shift);
    vlm: + 'embeds' (B, L, d), 'positions' (3, B, L);
    audio: + 'frames' (B, enc_len, d).
    """
    dt = _dtype(cfg)
    tokens = batch["tokens"]
    pos = None
    if cfg.family == "vlm":
        x = batch["embeds"].astype(dt)
        pos = batch.get("positions")
    else:
        x = L.embed_lookup(params["embed"], tokens).astype(dt)
    x = shard_act(x, mesh, _act_spec(cfg, mesh, x))

    if cfg.family in ("dense", "vlm", "moe"):
        def blk(p_l, h, _):
            h, _kv = _dense_block(p_l, h, cfg, mesh, pos)
            return h, None
        x, _ = _scan(blk, x, params["blocks"], cfg)

    elif cfg.family == "ssm_rwkv":
        def blk(p_l, h, _):
            h, _st = _rwkv_block(p_l, h, cfg, mesh)
            return h, None
        x, _ = _scan(blk, x, params["blocks"], cfg)

    elif cfg.family == "hybrid":
        G = cfg.shared_attn_every
        n_groups = cfg.n_layers // G
        x0 = x
        stacked = jax.tree.map(
            lambda a: a.reshape((n_groups, G) + a.shape[1:]), params["blocks"])
        shared = params["shared"]

        def group(h, p_g):
            h, _ = _shared_block(shared, h, x0, cfg, mesh, pos)

            def blk(p_l, hh, _):
                hh, _c = _mamba_block(p_l, hh, cfg, mesh)
                return hh, None
            h, _ = _scan(blk, h, p_g, cfg)
            return h, None
        if cfg.remat:
            group = jax.checkpoint(group)
        x, _ = jax.lax.scan(group, x, stacked, unroll=_unroll(cfg))

    elif cfg.family == "audio":
        memory = _encoder(params, cfg, batch["frames"], mesh)

        def blk(p_l, h, _):
            h, _kv = _dec_block(p_l, h, memory, cfg, mesh, pos)
            return h, None
        x, _ = _scan(blk, x, params["blocks"], cfg)
    else:
        raise ValueError(cfg.family)

    norm = L.layer_norm if cfg.family == "audio" else L.rms_norm
    x = norm(params["final_norm"], x)
    logits = _logits(params, cfg, x)
    if mesh is not None:
        lspec = _act_spec(cfg, mesh, logits)
        logits = shard_act(logits, mesh,
                           lspec or P(dp_axes(mesh), None, "model"))
    return logits


def loss_fn(params, cfg, batch, mesh=None):
    """Next-token CE (teacher forcing), mean over real (non-pad) targets."""
    logits = forward(params, cfg, batch, mesh)
    targets = batch["tokens"][:, 1:]
    lg = logits[:, :-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    mask = jnp.ones_like(targets, jnp.float32) if mask is None else mask[:, 1:]
    loss = jnp.sum((lse - true) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"loss": loss, "ntokens": jnp.sum(mask)}


# --- caches & decode ----------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, mesh=None):
    """Decode cache pytree (per-family)."""
    hd, KV = cfg.hd, cfg.n_kv
    Lg = cfg.n_layers

    def _kv(layers):
        if cfg.kv_quant:
            return {"k": jnp.zeros((layers, batch, max_len, KV, hd), jnp.int8),
                    "v": jnp.zeros((layers, batch, max_len, KV, hd), jnp.int8),
                    "k_scale": jnp.zeros((layers, batch, max_len, KV),
                                         jnp.bfloat16),
                    "v_scale": jnp.zeros((layers, batch, max_len, KV),
                                         jnp.bfloat16)}
        return {"k": jnp.zeros((layers, batch, max_len, KV, hd), dtype),
                "v": jnp.zeros((layers, batch, max_len, KV, hd), dtype)}

    if cfg.family in ("dense", "vlm", "moe"):
        return {"kv": _kv(Lg), "pos": jnp.zeros((), jnp.int32)}

    if cfg.family == "ssm_rwkv":
        r = rwkv_cfg(cfg)
        H, Pd = r.n_heads, r.head_dim
        return {"s": jnp.zeros((Lg, batch, H, Pd, Pd), jnp.float32),
                "x_tm": jnp.zeros((Lg, batch, 1, cfg.d_model), dtype),
                "x_cm": jnp.zeros((Lg, batch, 1, cfg.d_model), dtype),
                "pos": jnp.zeros((), jnp.int32)}

    if cfg.family == "hybrid":
        s = ssm_cfg(cfg)
        G = cfg.shared_attn_every
        n_groups = cfg.n_layers // G
        # beyond ~64k the shared-attn cache becomes a ring buffer of
        # `long_window` (this is what makes the 500k cell sub-quadratic and
        # O(window) in memory; the SSM states carry the full context)
        win = min(max_len, cfg.long_window) if max_len > 65536 else max_len
        return {
            "h": jnp.zeros((Lg, batch, s.n_heads, s.d_state, s.head_dim),
                           jnp.float32),
            "conv": jnp.zeros((Lg, batch, s.conv_width - 1,
                               s.d_inner + 2 * s.n_groups * s.d_state), dtype),
            "shared_k": jnp.zeros((n_groups, batch, win, KV, hd), dtype),
            "shared_v": jnp.zeros((n_groups, batch, win, KV, hd), dtype),
            "pos": jnp.zeros((), jnp.int32)}

    if cfg.family == "audio":
        return {"kv": _kv(Lg),
                "memory": jnp.zeros((batch, cfg.enc_len, cfg.d_model), dtype),
                "pos": jnp.zeros((), jnp.int32)}
    raise ValueError(cfg.family)


def init_paged_cache(cfg, n_pages: int, page_size: int, dtype=jnp.bfloat16):
    """Paged KV page pool (DESIGN.md §8): (L, n_pages, page, KV, hd) arrays.

    ``dtype=jnp.int8`` stores pages quantized with per-token-per-head scales
    (``attention.quantize_kv`` — the serving-state analogue of the paper's
    §4 weight indices); any float dtype stores them plain.  Page 0 is the
    allocator's trash page (serving/kvcache.py): retired slots keep
    lockstep-decoding into it, so it is never handed out.
    """
    if cfg.family not in _PAGED_FAMILIES:
        raise NotImplementedError(
            f"paged KV cache serves families {_PAGED_FAMILIES}; got "
            f"{cfg.family!r}")
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv, cfg.hd)
    if dtype == jnp.int8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.bfloat16),
                "v_scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _paged_scales(cache):
    if "k_scale" in cache:
        return (cache["k_scale"], cache["v_scale"])
    return None


@jax.named_scope("prefill_chunk")
def prefill_chunk(params, cfg, batch, cache, mesh=None):
    """Chunked prefill: one page-sized chunk of ONE request's prompt.

    Long prompts stream through this in page-sized chunks instead of forcing
    a new power-of-two prefill bucket — the compile footprint of the paged
    engine is a single chunk shape.  batch keys:

        tokens    (1, C) int32, C == page size (final chunk right-padded —
                  padded keys are causally invisible to real queries and the
                  page's padded tail is fenced by the decode valid-length
                  mask until overwritten)
        start     scalar int32, absolute position of tokens[0] (page-aligned)
        length    scalar int32, real tokens in this chunk (logits are taken
                  at start+length−1)
        page_row  (P,) int32, the slot's page table
        write_pid scalar int32, physical page receiving this chunk's K/V
                  (0 = trash: shared prefix-cache pages are recomputed for
                  logits only, never rewritten)

    cache: paged pool (init_paged_cache).  Returns (logits (1, 1, V) at the
    chunk's last real position, new cache).
    """
    if cfg.family not in _PAGED_FAMILIES:
        raise NotImplementedError(cfg.family)
    dt = _dtype(cfg)
    dp = dp_axes(mesh) if mesh is not None else None
    tokens = batch["tokens"]
    start = jnp.asarray(batch["start"], jnp.int32)
    length = jnp.asarray(batch["length"], jnp.int32)
    page_table = jnp.asarray(batch["page_row"], jnp.int32)[None]    # (1, P)
    write_pid = jnp.asarray(batch["write_pid"], jnp.int32)
    B, C = tokens.shape
    pos = start + jnp.arange(C)[None]                               # (1, C)
    x = L.embed_lookup(params["embed"], tokens).astype(dt)
    acfg = attn_cfg(cfg)

    ps0 = cache.get("probes", {})
    if ps0:
        n_pages = cache["k"].shape[1]
        ps0 = probes.bump(ps0, "page_oob", jnp.sum(
            (page_table < 0) | (page_table >= n_pages)).astype(jnp.float32))

    def body(carry, p_l):
        h, kc, vc, sc, l, ps = carry
        with probes.layer(ps, l) as pb:
            a, kc, vc, sc = A.attn_prefill_chunk(
                p_l["attn"], L.rms_norm(p_l["ln1"], h), acfg, pos=pos,
                page_table=page_table, write_pid=write_pid, past_len=start,
                k_pool=kc, v_pool=vc, layer=l, scales=sc, mesh=mesh, dp=dp)
            h = h + a
            with jax.named_scope("mlp"):
                if "moe" in p_l:
                    y = M.moe_apply(p_l["moe"], L.rms_norm(p_l["ln2"], h),
                                    moe_cfg(cfg), mesh)
                else:
                    y = L.swiglu(p_l["mlp"], L.rms_norm(p_l["ln2"], h),
                                 cfg.act_kind, cfg.act_levels, mesh)
        return (h + y, kc, vc, sc, l + 1, pb.state), None

    (x, nk, nv, nsc, _, ps1), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"], _paged_scales(cache),
               jnp.zeros((), jnp.int32), ps0),
        params["blocks"], unroll=_unroll(cfg))
    new_cache = {**cache, "k": nk, "v": nv}
    if nsc is not None:
        new_cache.update(k_scale=nsc[0], v_scale=nsc[1])
    if ps0:
        new_cache["probes"] = probes.bump(ps1, "tokens",
                                          length.astype(jnp.float32))
    with jax.named_scope("head"):
        x_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        x_last = L.rms_norm(params["final_norm"], x_last)
        logits = _logits(params, cfg, x_last)
    return logits, new_cache


@jax.named_scope("decode_step")
def _decode_step_paged(params, cfg, tokens, cache, mesh):
    """One paged decode step: per-slot page tables, (B,) positions.

    ``cache['pos']`` MUST be a (B,) vector (every batch row is a serving
    slot); logical position s of slot b lives at
    pool[page_table[b, s // page], s % page].  Retired slots carry an
    all-zero page-table row, so their lockstep writes land in the trash
    page and never touch pages reallocated to newcomers.
    """
    if cfg.family not in _PAGED_FAMILIES:
        raise NotImplementedError(cfg.family)
    dt = _dtype(cfg)
    dp = dp_axes(mesh) if mesh is not None else None
    pos = cache["pos"]
    pt = cache["page_table"]
    B = tokens.shape[0]
    page = cache["k"].shape[2]
    S_cap = pt.shape[1] * page
    ins = jnp.minimum(pos, S_cap - 1)
    vlen = jnp.minimum(pos, S_cap)          # fresh token enters via extra_kv
    write_pid = pt[jnp.arange(B), ins // page]
    write_off = ins % page
    x = L.embed_lookup(params["embed"], tokens).astype(dt)
    acfg = attn_cfg(cfg)

    ps0 = cache.get("probes", {})
    if ps0:
        n_pages = cache["k"].shape[1]
        ps0 = probes.bump(ps0, "page_oob", jnp.sum(
            (pt < 0) | (pt >= n_pages)).astype(jnp.float32))

    def body(carry, p_l):
        h, kc, vc, sc, l, ps = carry
        with probes.layer(ps, l) as pb:
            a, kc, vc, sc = A.attn_decode_paged(
                p_l["attn"], L.rms_norm(p_l["ln1"], h), acfg,
                pos=pos[:, None].astype(jnp.int32), page_table=pt,
                write_pid=write_pid, write_off=write_off, valid_len=vlen,
                k_pool=kc, v_pool=vc, layer=l, scales=sc, mesh=mesh, dp=dp)
            h = h + a
            with jax.named_scope("mlp"):
                if "moe" in p_l:
                    y = M.moe_apply(p_l["moe"], L.rms_norm(p_l["ln2"], h),
                                    moe_cfg(cfg), mesh)
                else:
                    y = L.swiglu(p_l["mlp"], L.rms_norm(p_l["ln2"], h),
                                 cfg.act_kind, cfg.act_levels, mesh)
        return (h + y, kc, vc, sc, l + 1, pb.state), None

    (x, nk, nv, nsc, _, ps1), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"], _paged_scales(cache),
               jnp.zeros((), jnp.int32), ps0),
        params["blocks"], unroll=_unroll(cfg))
    new_cache = {**cache, "k": nk, "v": nv, "pos": pos + 1}
    if nsc is not None:
        new_cache.update(k_scale=nsc[0], v_scale=nsc[1])
    if ps0:
        new_cache["probes"] = probes.bump(ps1, "tokens", float(B))
    with jax.named_scope("head"):
        x = L.rms_norm(params["final_norm"], x)
        logits = _logits(params, cfg, x)
    return logits, new_cache


def _verify_step_paged(params, cfg, tokens, cache, mesh):
    """Paged multi-token verify: K1 tokens per slot against gathered pages.

    Per-token write routing: logical position ``pos + i`` of slot b lives at
    page ``page_table[b, (pos+i) // page]``; positions past the slot's page
    span (speculative overshoot beyond the admission reservation — those
    tokens are guaranteed to be clamped away by the engine) and retired
    slots (all-zero page-table rows) route to the trash page 0.
    """
    if cfg.family not in _PAGED_FAMILIES:
        raise NotImplementedError(cfg.family)
    dt = _dtype(cfg)
    dp = dp_axes(mesh) if mesh is not None else None
    pos = cache["pos"]
    pt = cache["page_table"]
    B, K1 = tokens.shape
    page = cache["k"].shape[2]
    P = pt.shape[1]
    S_cap = P * page
    ppos = pos[:, None] + jnp.arange(K1)[None]                  # (B, K1)
    pidx = ppos // page
    write_pid = jnp.where(
        pidx < P, pt[jnp.arange(B)[:, None], jnp.minimum(pidx, P - 1)], 0)
    write_off = ppos % page
    vlen = jnp.minimum(pos, S_cap)
    x = L.embed_lookup(params["embed"], tokens).astype(dt)
    acfg = attn_cfg(cfg)

    ps0 = cache.get("probes", {})
    if ps0:
        n_pages = cache["k"].shape[1]
        ps0 = probes.bump(ps0, "page_oob", jnp.sum(
            (pt < 0) | (pt >= n_pages)).astype(jnp.float32))

    def body(carry, p_l):
        h, kc, vc, sc, l, ps = carry
        with probes.layer(ps, l) as pb:
            a, kc, vc, sc = A.attn_verify_paged(
                p_l["attn"], L.rms_norm(p_l["ln1"], h), acfg, pos=ppos,
                page_table=pt, write_pid=write_pid, write_off=write_off,
                valid_len=vlen, k_pool=kc, v_pool=vc, layer=l, scales=sc,
                mesh=mesh, dp=dp)
            h = h + a
            if "moe" in p_l:
                y = M.moe_apply(p_l["moe"], L.rms_norm(p_l["ln2"], h),
                                moe_cfg(cfg), mesh)
            else:
                y = L.swiglu(p_l["mlp"], L.rms_norm(p_l["ln2"], h),
                             cfg.act_kind, cfg.act_levels, mesh)
        return (h + y, kc, vc, sc, l + 1, pb.state), None

    (x, nk, nv, nsc, _, ps1), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"], _paged_scales(cache),
               jnp.zeros((), jnp.int32), ps0),
        params["blocks"], unroll=_unroll(cfg))
    new_cache = {**cache, "k": nk, "v": nv}
    if nsc is not None:
        new_cache.update(k_scale=nsc[0], v_scale=nsc[1])
    if ps0:
        new_cache["probes"] = probes.bump(ps1, "tokens", float(B * K1))
    x = L.rms_norm(params["final_norm"], x)
    return _logits(params, cfg, x), new_cache


def verify_step(params, cfg, tokens, cache, mesh=None):
    """Multi-token speculative verify (DESIGN.md §9).  tokens: (B, K1) — per
    slot, the pending last token followed by K draft proposals.  Returns
    (logits (B, K1, V) at EVERY position, new cache).

    ``logits[:, i]`` is the target distribution for the token *after*
    ``tokens[:, i]`` — one jitted forward replaces K1 sequential decode
    steps.  K/V for all K1 tokens are written at rows pos..pos+K1−1 per
    slot; ``cache['pos']`` comes back UNCHANGED — the caller advances it by
    however many tokens survive rejection sampling.  Rolling back after a
    rejection is therefore free: the rejected suffix is stale rows above
    ``pos``, fenced by every later step's valid-length mask exactly like a
    retired slot's tail.  ``cache['pos']`` must be the (B,) per-slot vector
    form (scalars are broadcast); a cache carrying a ``page_table`` takes
    the paged path.  KV-cache engine families only (dense/moe).
    """
    if "page_table" in cache:
        return _verify_step_paged(params, cfg, tokens, cache, mesh)
    if cfg.family not in _PAGED_FAMILIES:
        raise NotImplementedError(
            f"verify_step serves KV-cache families {_PAGED_FAMILIES}; got "
            f"{cfg.family!r}")
    dt = _dtype(cfg)
    B, K1 = tokens.shape
    pos_any = cache["pos"]
    pos_v = (pos_any if pos_any.ndim == 1
             else jnp.broadcast_to(pos_any, (B,))).astype(jnp.int32)
    S = cache["kv"]["k"].shape[2]
    ins = jnp.minimum(pos_v, S - K1)           # clamp: retired slots
    vlen = jnp.minimum(pos_v, S)
    ppos = pos_v[:, None] + jnp.arange(K1)[None]                # (B, K1) RoPE
    x = L.embed_lookup(params["embed"], tokens).astype(dt)
    acfg = attn_cfg(cfg)
    qkv = cfg.kv_quant

    ps0 = cache.get("probes", {})

    def body(carry, p_l):
        h, kc, vc, sc, l, ps = carry
        with probes.layer(ps, l) as pb:
            a, kc, vc, sc = A.attn_verify_cached(
                p_l["attn"], L.rms_norm(p_l["ln1"], h), acfg, pos=ppos,
                insert_at=ins, valid_len=vlen, k_all=kc, v_all=vc, layer=l,
                scales=sc, mesh=mesh,
                dp=dp_axes(mesh) if mesh is not None else None)
            h = h + a
            if "moe" in p_l:
                y = M.moe_apply(p_l["moe"], L.rms_norm(p_l["ln2"], h),
                                moe_cfg(cfg), mesh)
            else:
                y = L.swiglu(p_l["mlp"], L.rms_norm(p_l["ln2"], h),
                             cfg.act_kind, cfg.act_levels, mesh)
        return (h + y, kc, vc, sc, l + 1, pb.state), None

    sc0 = ((cache["kv"]["k_scale"], cache["kv"]["v_scale"]) if qkv else None)
    (x, nk, nv, nsc, _, ps1), _ = jax.lax.scan(
        body, (x, cache["kv"]["k"], cache["kv"]["v"], sc0,
               jnp.zeros((), jnp.int32), ps0),
        params["blocks"], unroll=_unroll(cfg))
    new_kv = {"k": nk, "v": nv}
    if qkv:
        new_kv.update(k_scale=nsc[0], v_scale=nsc[1])
    x = L.rms_norm(params["final_norm"], x)
    out_cache = {**cache, "kv": new_kv}
    if ps0:
        out_cache["probes"] = probes.bump(ps1, "tokens", float(B * K1))
    return _logits(params, cfg, x), out_cache


def decode_step(params, cfg, tokens, cache, mesh=None):
    """One decode step.  tokens: (B, 1) int32.  Returns (logits, new cache).

    ``cache['pos']`` is either a scalar (uniform batch: every row is at the
    same sequence offset — training smoke tests, dry-run cells) or a (B,)
    vector of per-slot positions (the ServeEngine's continuous batching,
    where batch rows are slots holding requests of different ages).  The
    vector form is supported for the KV-cache families (dense/vlm/moe/
    audio); recurrent-state families decode uniform batches only.

    A cache carrying a ``page_table`` is a paged page pool
    (``init_paged_cache``) and takes the paged path instead of the
    contiguous slab.
    """
    if "page_table" in cache:
        return _decode_step_paged(params, cfg, tokens, cache, mesh)
    dt = _dtype(cfg)
    pos_any = cache["pos"]
    B = tokens.shape[0]
    if pos_any.ndim == 1:
        if cfg.family not in ("dense", "vlm", "moe", "audio"):
            raise NotImplementedError(
                f"per-slot decode positions need a KV cache; family "
                f"{cfg.family!r} carries recurrent state")
        pos = pos_any[:, None].astype(jnp.int32)            # (B, 1) RoPE
    else:
        pos = jnp.broadcast_to(pos_any[None, None], (B, 1))
    pos_scalar = pos_any        # scalar in every branch below except dense kv
    x = L.embed_lookup(params["embed"], tokens).astype(dt)
    x = shard_act(x, mesh)

    if cfg.family in ("dense", "vlm", "moe", "audio"):
        # Cache is carried through the layer scan so XLA's while-loop buffer
        # assignment updates it in place (a scan-xs/ys cache would be
        # double-buffered: +1× full cache of temp memory).
        memory = cache.get("memory")
        S = cache["kv"]["k"].shape[2]
        if pos_any.ndim == 1:
            # clamp: retired slots keep lockstep-decoding garbage until the
            # engine reuses them — never past the cache end
            ins = jnp.minimum(pos_any, S - 1)
            vlen = jnp.minimum(pos_any + 1, S)
        else:
            ins, vlen = pos_any, pos_any + 1
        norm = L.layer_norm if cfg.family == "audio" else L.rms_norm
        acfg = attn_cfg(cfg)

        qkv = cfg.kv_quant
        ps0 = cache.get("probes", {})

        def body(carry, p_l):
            h, kc, vc, sc, l, ps = carry
            with probes.layer(ps, l) as pb:
                a, kc, vc, sc = A.attn_decode_cached(
                    p_l["attn"], norm(p_l["ln1"], h), acfg, pos=pos,
                    insert_at=ins, valid_len=vlen,
                    k_all=kc, v_all=vc, layer=l, scales=sc,
                    mesh=mesh, dp=dp_axes(mesh) if mesh is not None else None)
                h = shard_act(h + a, mesh)
                if cfg.family == "audio":
                    c, _ = A.attn_apply(p_l["xattn"],
                                        L.layer_norm(p_l["ln_x"], h),
                                        attn_cfg(cfg, causal=False),
                                        kv_override=memory)
                    h = shard_act(h + c, mesh)
                    y = L.mlp_block(p_l["mlp"], L.layer_norm(p_l["ln2"], h),
                                    cfg.act_kind, cfg.act_levels, mesh)
                elif "moe" in p_l:
                    y = M.moe_apply(p_l["moe"], L.rms_norm(p_l["ln2"], h),
                                    moe_cfg(cfg), mesh)
                else:
                    y = L.swiglu(p_l["mlp"], L.rms_norm(p_l["ln2"], h),
                                 cfg.act_kind, cfg.act_levels, mesh)
                h = shard_act(h + y, mesh)
            return (h, kc, vc, sc, l + 1, pb.state), None

        sc0 = (cache["kv"]["k_scale"], cache["kv"]["v_scale"]) if qkv else None
        (x, nk, nv, nsc, _, ps1), _ = jax.lax.scan(
            body, (x, cache["kv"]["k"], cache["kv"]["v"], sc0,
                   jnp.zeros((), jnp.int32), ps0),
            params["blocks"], unroll=_unroll(cfg))
        new_kv = {"k": nk, "v": nv}
        if qkv:
            new_kv.update(k_scale=nsc[0], v_scale=nsc[1])
        new_cache = {**cache, "kv": new_kv, "pos": pos_scalar + 1}
        if ps0:
            new_cache["probes"] = probes.bump(ps1, "tokens", float(B))

    elif cfg.family == "ssm_rwkv":
        def body(h, xs):
            p_l, s, xtm, xcm = xs
            st = {"s": s, "x_tm": xtm, "x_cm": xcm}
            h, st2 = _rwkv_block(p_l, h, cfg, mesh, st, decode=True)
            return h, (st2["s"], st2["x_tm"], st2["x_cm"])
        x, (s2, xtm2, xcm2) = jax.lax.scan(
            body, x, (params["blocks"], cache["s"], cache["x_tm"],
                      cache["x_cm"]), unroll=_unroll(cfg))
        new_cache = {"s": s2, "x_tm": xtm2, "x_cm": xcm2,
                     "pos": pos_scalar + 1}

    elif cfg.family == "hybrid":
        G = cfg.shared_attn_every
        n_groups = cfg.n_layers // G
        win = cache["shared_k"].shape[2]              # static ring size
        ins = pos_scalar % win                        # ring insert position
        vlen = jnp.minimum(pos_scalar + 1, win)
        x0 = x
        acfg = attn_cfg(cfg)
        shared = params["shared"]
        mb = jax.tree.map(lambda a: a.reshape((n_groups, G) + a.shape[1:]),
                          params["blocks"])

        def group(carry, xs):
            h, sk, sv, g = carry
            p_g, hg, cg = xs
            # shared block with carried ring KV cache (in-place DUS)
            hin = L.dense(shared["in_proj"], jnp.concatenate([h, x0], -1))
            a, sk, sv, _ = A.attn_decode_cached(
                shared["attn"], L.rms_norm(shared["ln1"], hin), acfg,
                pos=pos, insert_at=ins, valid_len=vlen,
                k_all=sk, v_all=sv, layer=g,
                mesh=mesh, dp=dp_axes(mesh) if mesh is not None else None)
            hin = hin + a
            hin = hin + L.swiglu(shared["mlp"], L.rms_norm(shared["ln2"], hin),
                                 cfg.act_kind, cfg.act_levels, mesh)
            h = shard_act(h + hin, mesh)

            def body(hh, xs2):
                p_l, ch, cc = xs2
                hh, c2 = _mamba_block(p_l, hh, cfg, mesh,
                                      {"h": ch, "conv": cc}, decode=True)
                return hh, (c2["h"], c2["conv"])
            h, (nh, nc) = jax.lax.scan(body, h, (p_g, hg, cg))
            return (h, sk, sv, g + 1), (nh, nc)

        hg = cache["h"].reshape((n_groups, G) + cache["h"].shape[1:])
        cg = cache["conv"].reshape((n_groups, G) + cache["conv"].shape[1:])
        (x, nsk, nsv, _), (nh, nc) = jax.lax.scan(
            group, (x, cache["shared_k"], cache["shared_v"],
                    jnp.zeros((), jnp.int32)),
            (mb, hg, cg), unroll=_unroll(cfg))
        new_cache = {"h": nh.reshape(cache["h"].shape),
                     "conv": nc.reshape(cache["conv"].shape),
                     "shared_k": nsk, "shared_v": nsv,
                     "pos": pos_scalar + 1}
    else:
        raise ValueError(cfg.family)

    norm = L.layer_norm if cfg.family == "audio" else L.rms_norm
    x = norm(params["final_norm"], x)
    logits = _logits(params, cfg, x)
    return logits, new_cache


def prefill(params, cfg, batch, mesh=None):
    """Prefill: forward over the prompt; returns (last-position logits, cache).

    The cache is *emitted* as scan outputs (per-layer K/V planes / final SSM
    states) rather than written into a preallocated zero cache — avoids a
    full extra cache of temp memory in the lowered step.

    ``batch['lengths']`` ((B,) int32, optional) marks right-padded prompts:
    row b's real tokens occupy positions [0, lengths[b]).  Causal masking
    already keeps real queries from seeing the padded tail, so no extra
    attention mask is needed; the returned logits are taken at each row's
    last *real* position and ``cache['pos']`` comes back as the (B,) length
    vector — the layout ServeEngine's batched prefill and per-slot decode
    consume.  KV-cache families only: a recurrent state would march through
    the padding and corrupt itself.
    """
    dt = _dtype(cfg)
    cdt = jnp.bfloat16 if cfg.dtype == "bfloat16" else dt
    tokens = batch["tokens"]
    lengths = batch.get("lengths")
    if lengths is not None and cfg.family not in ("dense", "vlm", "moe",
                                                  "audio"):
        raise NotImplementedError(
            f"per-request prompt lengths need a KV cache; family "
            f"{cfg.family!r} carries recurrent state through the padding")
    B, Sq = tokens.shape
    pos = None
    if cfg.family == "vlm":
        x = batch["embeds"].astype(dt)
        pos = batch.get("positions")
    else:
        x = L.embed_lookup(params["embed"], tokens).astype(dt)
    x = shard_act(x, mesh)

    if cfg.family in ("dense", "vlm", "moe", "audio"):
        memory = None
        if cfg.family == "audio":
            memory = _encoder(params, cfg, batch["frames"], mesh)

        def blk(p_l, h, _):
            if cfg.family == "audio":
                a, kv = A.attn_apply(p_l["attn"], L.layer_norm(p_l["ln1"], h),
                                     attn_cfg(cfg), pos=pos, return_kv=True,
                                     mesh=mesh)
                h = shard_act(h + a, mesh)
                c, _ = A.attn_apply(p_l["xattn"], L.layer_norm(p_l["ln_x"], h),
                                    attn_cfg(cfg, causal=False),
                                    kv_override=memory)
                h = shard_act(h + c, mesh)
                y = L.mlp_block(p_l["mlp"], L.layer_norm(p_l["ln2"], h),
                                cfg.act_kind, cfg.act_levels, mesh)
            else:
                a, kv = A.attn_apply(p_l["attn"], L.rms_norm(p_l["ln1"], h),
                                     attn_cfg(cfg), pos=pos, return_kv=True,
                                     mesh=mesh)
                h = shard_act(h + a, mesh)
                if "moe" in p_l:
                    y = M.moe_apply(p_l["moe"], L.rms_norm(p_l["ln2"], h),
                                    moe_cfg(cfg), mesh)
                else:
                    y = L.swiglu(p_l["mlp"], L.rms_norm(p_l["ln2"], h),
                                 cfg.act_kind, cfg.act_levels)
            h = shard_act(h + y, mesh)
            if cfg.kv_quant:
                kq, ksc = A.quantize_kv(kv["k"])
                vq, vsc = A.quantize_kv(kv["v"])
                return h, (kq, vq, ksc, vsc)
            return h, (kv["k"].astype(cdt), kv["v"].astype(cdt))

        ps0 = batch.get("probes") or {}
        if ps0:
            # Probe-instrumented body: same blk, carry extended with the
            # counters + a layer index (the plain prefill carry is just x, so
            # the off path below keeps its original, untouched trace).
            def bodyp(carry, p_l):
                h, ps, l = carry
                with probes.layer(ps, l) as pb:
                    h, plane = blk(p_l, h, None)
                return (h, pb.state, l + 1), plane
            (x, ps1, _), planes = jax.lax.scan(
                bodyp, (x, ps0, jnp.zeros((), jnp.int32)),
                params["blocks"], unroll=_unroll(cfg))
        else:
            def body(h, p_l):
                return blk(p_l, h, None)
            x, planes = jax.lax.scan(body, x, params["blocks"],
                                     unroll=_unroll(cfg))
            ps1 = {}
        if cfg.kv_quant:
            nk, nv, nks, nvs = planes
            new_kv = {"k": nk, "v": nv, "k_scale": nks, "v_scale": nvs}
        else:
            nk, nv = planes
            new_kv = {"k": nk, "v": nv}
        new_cache = {"kv": new_kv, "pos": jnp.asarray(Sq, jnp.int32)}
        if ps0:
            n_tok = (jnp.sum(lengths).astype(jnp.float32)
                     if lengths is not None else float(B * Sq))
            new_cache["probes"] = probes.bump(ps1, "tokens", n_tok)
        if memory is not None:
            new_cache["memory"] = memory.astype(cdt)

    elif cfg.family == "ssm_rwkv":
        def body(h, p_l):
            h, st2 = _rwkv_prefill_block(p_l, h, cfg, mesh)
            return h, (st2["s"], st2["x_tm"].astype(cdt),
                       st2["x_cm"].astype(cdt))
        x, (s2, xtm2, xcm2) = jax.lax.scan(body, x, params["blocks"],
                                           unroll=_unroll(cfg))
        new_cache = {"s": s2, "x_tm": xtm2, "x_cm": xcm2,
                     "pos": jnp.asarray(Sq, jnp.int32)}

    elif cfg.family == "hybrid":
        # prefill trunk == forward; the (small) SSM states + windowed shared
        # KV are re-derivable; the dry-run cell measures the trunk.
        logits = forward(params, cfg, batch, mesh)
        return logits[:, -1:], init_cache(cfg, B, Sq, cdt)
    else:
        raise ValueError(cfg.family)

    norm = L.layer_norm if cfg.family == "audio" else L.rms_norm
    if lengths is None:
        x_last = x[:, -1:]
    else:
        # per-row gather at the last real position; cache pos → (B,) vector
        lv = lengths.astype(jnp.int32)
        x_last = x[jnp.arange(B)[:, None], jnp.maximum(lv - 1, 0)[:, None]]
        new_cache["pos"] = lv
    x = norm(params["final_norm"], x_last)
    return _logits(params, cfg, x), new_cache


def _rwkv_prefill_block(p_l, h, cfg, mesh):
    rcfg = rwkv_cfg(cfg)
    spec = _act_spec(cfg, mesh, h)
    tm, st_tm = R.rwkv_tm_apply(p_l["tm"], L.rms_norm(p_l["ln1"], h), rcfg)
    h = shard_act(h + tm, mesh, spec)
    cm, st_cm = R.rwkv_cm_apply(p_l["cm"], L.rms_norm(p_l["ln2"], h), rcfg)
    h = shard_act(h + cm, mesh, spec)
    return h, {**st_tm, **st_cm}
