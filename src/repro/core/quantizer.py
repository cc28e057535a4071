"""Periodic adaptive weight clustering over parameter pytrees (paper §2.2).

The paper's procedure: every ``interval`` (=1000) training steps, cluster
*all* network weights and biases to ``|W|`` unique values and snap each
weight to its centroid; training then continues unmodified.  This module
implements that as a pure function over a parameter pytree plus a small
``QuantizerState`` so it drops into any training loop:

    wq = WeightQuantConfig(num_weights=1000, method="laplacian_l1")
    state = init_state(wq)
    ...
    if wq.due(step):
        params, state = cluster_params(params, wq, state, step, key)

Scopes: ``global`` (one codebook for the whole network — the paper's default,
enabling a single A×W multiplication table) or ``per_layer`` (paper §5 future
work bullet 1 — one codebook per parameter tensor).

|W| annealing (paper §5 future work bullet 2): start at ``anneal_from`` and
decay geometrically to ``num_weights`` over ``anneal_steps``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import clustering

__all__ = [
    "WeightQuantConfig",
    "QuantizerState",
    "init_state",
    "cluster_params",
    "codebook_indices",
    "num_weights_at",
    "param_filter",
]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class WeightQuantConfig:
    """Weight-clustering configuration.

    num_weights: |W| — number of unique weight values (0 disables).
    method:      'kmeans' | 'laplacian_l1' | 'uniform'.
    scope:       'global' (single codebook, paper default) | 'per_layer'.
    interval:    clustering cadence in steps (paper: 1000).
    subsample:   fraction of weights fed to k-means (paper §3.3: 0.02 for
                 AlexNet); 1.0 = use everything. Ignored by laplacian_l1,
                 which only needs mean/max statistics.
    kmeans_iters: Lloyd iterations per clustering event.
    anneal_from / anneal_steps: optional |W| annealing (§5 future work).
    exclude:     regex over param paths ('layer/w') exempt from clustering
                 (default none — the paper clusters everything incl. biases).
    """

    num_weights: int = 0
    method: str = "laplacian_l1"
    scope: str = "global"
    interval: int = 1000
    subsample: float = 1.0
    kmeans_iters: int = 25
    anneal_from: int = 0
    anneal_steps: int = 0
    exclude: str = ""

    def __post_init__(self):
        if self.num_weights and self.num_weights < 2:
            raise ValueError("num_weights must be >= 2 (or 0 to disable)")
        if self.method not in ("kmeans", "laplacian_l1", "uniform"):
            raise ValueError(f"unknown clustering method {self.method!r}")
        if self.scope not in ("global", "per_layer"):
            raise ValueError(f"unknown scope {self.scope!r}")

    @property
    def enabled(self) -> bool:
        return self.num_weights > 0

    def due(self, step: int) -> bool:
        """True on steps where the clustering event fires."""
        return self.enabled and step > 0 and step % self.interval == 0


@dataclasses.dataclass
class QuantizerState:
    """Codebook(s) from the most recent clustering event.

    codebooks: {path: centers} for per_layer scope, {'': centers} for global.
               Empty until the first clustering event.
    last_step: step of the most recent event (-1 = never).
    """

    codebooks: dict
    last_step: int = -1


def init_state(cfg: WeightQuantConfig) -> QuantizerState:
    del cfg
    return QuantizerState(codebooks={}, last_step=-1)


def num_weights_at(cfg: WeightQuantConfig, step: int) -> int:
    """|W| schedule: geometric decay anneal_from -> num_weights."""
    if not cfg.anneal_from or cfg.anneal_from <= cfg.num_weights:
        return cfg.num_weights
    if step >= cfg.anneal_steps:
        return cfg.num_weights
    frac = step / max(cfg.anneal_steps, 1)
    w = cfg.anneal_from * (cfg.num_weights / cfg.anneal_from) ** frac
    return max(cfg.num_weights, int(round(w)))


def param_filter(cfg: WeightQuantConfig):
    """Predicate(path) -> bool: True if this tensor is clustered."""
    if not cfg.exclude:
        return lambda path: True
    pat = re.compile(cfg.exclude)
    return lambda path: not pat.search(path)


def _flat_paths(params: PyTree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
             for kp, _ in leaves]
    values = [v for _, v in leaves]
    return paths, values, treedef


def _centers(values: jnp.ndarray, cfg: WeightQuantConfig, k: int,
             key: jax.Array) -> jnp.ndarray:
    if cfg.method == "laplacian_l1":
        return clustering.laplacian_l1_centers(values, k)
    if cfg.method == "uniform":
        return clustering.uniform_centers(values, k)
    v = values
    if cfg.subsample < 1.0 and v.size > 1_000_000:
        v = clustering.subsample(v.reshape(-1), cfg.subsample, key)
    return clustering.kmeans1d(v, k, iters=cfg.kmeans_iters)


@jax.jit
def _concat_f32(leaves):
    """Every leaf as one flat f32 vector.  Jitted so the f32 casts fuse
    into the concatenation: eagerly, each leaf's f32 copy would exist
    beside the result (2× the network in f32 at once)."""
    return jnp.concatenate([v.reshape(-1).astype(jnp.float32)
                            for v in leaves])


def cluster_params(params: PyTree, cfg: WeightQuantConfig,
                   state: QuantizerState, step: int,
                   key: jax.Array) -> tuple[PyTree, QuantizerState]:
    """One clustering event: snap every (included) weight to its centroid.

    Pure function; callers decide cadence via ``cfg.due(step)``.
    """
    if not cfg.enabled:
        return params, state
    k = num_weights_at(cfg, step)
    keep = param_filter(cfg)
    paths, values, treedef = _flat_paths(params)

    if cfg.scope == "global":
        included = [v for p, v in zip(paths, values) if keep(p)]
        flat = (_concat_f32(included) if included
                else jnp.zeros((1,), jnp.float32))
        centers = _centers(flat, cfg, k, key)
        del flat          # the f32 copy of the whole network: 4 B/weight
        new_values = [
            clustering.quantize_to_centers(v, centers) if keep(p) else v
            for p, v in zip(paths, values)
        ]
        books = {"": centers}
    else:
        books, new_values = {}, []
        for i, (p, v) in enumerate(zip(paths, values)):
            if keep(p) and v.size >= 2:
                c = _centers(v.reshape(-1), cfg, min(k, v.size),
                             jax.random.fold_in(key, i))
                books[p] = c
                new_values.append(clustering.quantize_to_centers(v, c))
            else:
                new_values.append(v)

    new_params = jax.tree_util.tree_unflatten(treedef, new_values)
    return new_params, QuantizerState(codebooks=books, last_step=step)


def codebook_indices(params: PyTree, cfg: WeightQuantConfig,
                     state: QuantizerState) -> tuple[PyTree, dict]:
    """Index representation of a clustered network (paper §4 deployment).

    Returns (pytree of int32 index arrays mirroring params, codebooks dict).
    Each index selects into the relevant codebook; this is the form whose
    memory footprint the §4 analysis (and our TPU codebook kernels) exploit.
    Raises if clustering never ran.
    """
    if not state.codebooks:
        raise ValueError("no codebook yet — run cluster_params first")
    keep = param_filter(cfg)
    paths, values, treedef = _flat_paths(params)
    idx_leaves = []
    for p, v in zip(paths, values):
        if cfg.scope == "global":
            book = state.codebooks[""]
        else:
            book = state.codebooks.get(p)
        if book is None or not keep(p):
            idx_leaves.append(v)  # unclustered tensor kept verbatim
            continue
        idx_leaves.append(clustering.assign_to_centers(v.astype(jnp.float32), book)
                          .reshape(v.shape))
    return jax.tree_util.tree_unflatten(treedef, idx_leaves), dict(state.codebooks)
