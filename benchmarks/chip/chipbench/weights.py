"""Index-form weights made on the device from the seed, with no clustering.

A served checkpoint is already compressed, so the benchmark makes the tree
that ``launch.serve.make_params(model, seed=..., n_weights=...)`` would
hold — int16 index planes over one |W|-entry f32 codebook, norm scales of
one — directly: every leaf is a pure function of (seed, its path, its
layer), built in one jitted call from one key.  The plain reference
rebuilds any single layer's plane the same way, so it takes nothing the
program has made.

The codebook holds |W| values of N(0, ``weight_std``) at jittered
quantiles: value i sits at quantile (i + 0.5 + 0.9 u_i) / |W|, with u_i in
[-0.5, 0.5) hashed from i and the seed.  It is sorted, random per seed,
and a function of the id alone, so ``codebook_value(key, ids)`` gives
``codebook[ids]`` element by element, with no gather (a TPU runs XLA
gathers from a table one element at a time).  One global codebook, as
``make_params``' global clustering gives, tiled per layer where the
program's tree tiles it.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

_BOOK_TAG = 0x7B00C
# top-level keys whose leaves stack one entry per layer on axis 0
STACKED = ("blocks", "enc_blocks")


def base_key(seed: int):
    """Key of a non-negative seed of any size (the low 31 bits seed it,
    the rest is folded in, so seeds past 2**31 stay distinct)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def index_plane(key, path: str, shape, n_weights: int, dtype, layer=None):
    """One plane of ids in [0, n_weights) — (K, N), or (E, K, N) for an
    expert leaf: the plane of ``layer`` for a stacked leaf, the whole leaf
    otherwise."""
    k = _path_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    wide = jnp.uint16 if n_weights <= 1 << 16 else jnp.uint32
    bits = jax.random.bits(k, tuple(shape), wide)
    return (bits % n_weights).astype(dtype)


def codebook_value(key, ids, n_weights: int, std: float):
    """``codebook[ids]`` computed from the ids themselves (f32)."""
    salt = jax.random.bits(jax.random.fold_in(key, _BOOK_TAG), (),
                           jnp.uint32)
    h = (ids.astype(jnp.uint32) ^ salt) * jnp.uint32(0x9E3779B1)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA77)
    h = h ^ (h >> 13)
    u = (h >> 8).astype(jnp.float32) / float(1 << 24) - 0.5
    q = (ids.astype(jnp.float32) + 0.5 + 0.9 * u) / n_weights
    return std * jnp.sqrt(2.0) * jax.lax.erf_inv(2.0 * q - 1.0)


def codebook(key, n_weights: int, std: float):
    return codebook_value(key, jnp.arange(n_weights), n_weights, std)


def serving_tree(model, n_weights: int, min_size: int = 4096,
                 stacked=STACKED):
    """Shapes and dtypes of ``make_params(model, n_weights=n_weights)``:
    the program's init, abstractly, with the rule of
    ``serving.to_codebook_params`` applied — every clustered matrix
    (``w`` or ``table``, at least 2-D and ``min_size`` values) becomes
    ``{'w_idx', 'codebook'}``, the codebook tiled per layer under the
    stacked prefixes."""
    from repro.core.quantizer import param_filter
    from repro.serving.compress import index_dtype_for

    keep = param_filter(model.cfg.quantized(n_weights=n_weights).wq)
    idt = index_dtype_for(n_weights)

    def walk(node, parts):
        for k, v in node.items():
            if isinstance(v, dict) or k not in ("w", "table"):
                continue
            path = "/".join(parts + [k])
            if v.ndim >= 2 and v.size >= min_size and keep(path):
                book = ((v.shape[0], n_weights) if parts[0] in stacked
                        else (n_weights,))
                rest = {kk: vv for kk, vv in node.items() if kk != k}
                return {**rest,
                        "w_idx": jax.ShapeDtypeStruct(v.shape, idt),
                        "codebook": jax.ShapeDtypeStruct(book, jnp.float32)}
        return {k: walk(v, parts + [k]) if isinstance(v, dict) else v
                for k, v in node.items()}

    return walk(jax.eval_shape(model.init, jax.random.PRNGKey(0)), [])


def leaf_path(key_path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in key_path)


def make_params(tree, seed: int, n_weights: int, std: float,
                shardings=None):
    """The weights of ``tree`` (from ``serving_tree``) for ``seed``, made
    in one jitted call: on the default device, or, given ``shardings`` (a
    tree of ``Sharding`` like ``tree``), as the jit's output shardings, so
    each device makes only its own shard.  The values do not depend on
    the shardings: a w_idx leaf under a stacked prefix is made plane by
    plane, each plane a function of (seed, path, layer) whatever the
    leaf's rank, and the PRNG is partitionable."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)

    def build(key):
        book = codebook(key, n_weights, std)
        out = []
        for kp, sds in flat:
            path, name = leaf_path(kp), leaf_path(kp[-1:])
            if name == "w_idx" and leaf_path(kp[:1]) in STACKED:
                out.append(jax.vmap(lambda layer, p=path, s=sds: index_plane(
                    key, p, s.shape[1:], n_weights, s.dtype, layer))(
                        jnp.arange(sds.shape[0])))
            elif name == "w_idx":
                out.append(index_plane(key, path, sds.shape, n_weights,
                                       sds.dtype))
            elif name == "codebook":
                out.append(jnp.broadcast_to(book, sds.shape).astype(
                    sds.dtype))
            elif name == "scale":
                out.append(jnp.ones(sds.shape, sds.dtype))
            elif name in ("b", "bias"):
                out.append(jnp.zeros(sds.shape, sds.dtype))
            else:
                raise ValueError(f"no seed rule for leaf {path}")
        return jax.tree_util.tree_unflatten(treedef, out)

    if shardings is None:
        return jax.jit(build)(base_key(seed))
    return jax.jit(build, out_shardings=shardings)(base_key(seed))
