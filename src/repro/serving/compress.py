"""Serving-side weight compression: dense params → codebook-index form.

This is the deployment artifact of the paper's §4 on TPU: every clustered
matrix is replaced in-place by ``{'w_idx': intN, 'codebook': f32[|W|]}``;
``models.layers.dense`` (and the embedding lookup) dispatch on that
structure, so the *same* model code serves both representations.  HBM
weight bytes drop by itemsize(f32→int8/int16) ≈ 2–4× (vs bf16), which is
the §Roofline memory-term win for decode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import clustering
from repro.core.quantizer import WeightQuantConfig, QuantizerState, param_filter

__all__ = ["to_codebook_params", "index_dtype_for"]


def index_dtype_for(n_weights: int):
    if n_weights <= 256:
        return jnp.int8
    if n_weights <= 65536:
        return jnp.int16
    return jnp.int32


def to_codebook_params(params, cfg: WeightQuantConfig, state: QuantizerState,
                       min_size: int = 4096,
                       stacked_prefixes=("blocks", "enc_blocks")):
    """Convert every clustered ≥2-D tensor to index form.

    Tensors below ``min_size`` (norm scales, biases, small vectors) stay
    dense — their indices would cost more than they save.  Requires a
    codebook (cluster_params must have run at least once).

    Leaves under ``stacked_prefixes`` carry a leading layer dim consumed by
    the lax.scan over layers; their codebook is tiled to (L, |W|) so the
    scan slices a (identical) per-layer copy — 4·L·|W| bytes of duplication,
    noise next to the index planes.
    """
    if not state.codebooks:
        raise ValueError("no codebook; run cluster_params first")
    keep = param_filter(cfg)
    idt = index_dtype_for(cfg.num_weights)

    def visit(path_parts, leaf):
        path = "/".join(path_parts)
        tail = path_parts[-1] if path_parts else ""
        if tail not in ("w", "table") or leaf.ndim < 2 or leaf.size < min_size \
                or not keep(path):
            return None  # unchanged
        book = state.codebooks.get("" if cfg.scope == "global" else path)
        if book is None:
            return None
        idx = clustering.assign_to_centers(leaf, book)
        book = jnp.asarray(book)
        if path_parts[0] in stacked_prefixes:
            book = jnp.broadcast_to(book[None], (leaf.shape[0],) + book.shape)
        return {"w_idx": idx.astype(idt), "codebook": book}

    def walk(node, parts):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if isinstance(v, dict):
                    out[k] = walk(v, parts + [k])
                else:
                    rep = visit(parts + [k], v)
                    if rep is not None and k == "w":
                        # replace the whole {'w': ...} entry with index form
                        return {**{kk: vv for kk, vv in node.items()
                                   if kk != "w"}, **rep}
                    if rep is not None and k == "table":
                        return {**{kk: vv for kk, vv in node.items()
                                   if kk != "table"}, **rep}
                    out[k] = v
            return out
        return node

    return walk(params, [])
