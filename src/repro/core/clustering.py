"""Weight clustering (paper §2.2, Figs. 3-5).

Three ways to pick the |W| cluster centers:

* ``kmeans1d``     — jitted 1-D Lloyd k-means (the paper's default; "all of the
                     clustering approaches we tried gave similar results").
* ``laplacian_l1`` — the paper's closed-form model-based quantizer: centers at
                     ``a ± b·L_i`` with ``L_i = L_{i-1} + Δ_i``,
                     ``Δ_i = −ln(1 − 2·exp(L_{i-1})/N)``, ``L_0 = 0``.
                     The recursion telescopes:  ``exp(−L_i) = 1 − 2i/N``  —
                     i.e. the tail mass drops linearly (paper Fig. 5, linear
                     occupancy), so we implement the stable closed form
                     ``L_i = −ln(1 − 2i/N)``.
* ``uniform``      — equally-spaced levels between min and max (the Lin et
                     al. 2015 baseline the paper argues against).

Everything here is pure-functional and jittable; the periodic-clustering
trainer hook lives in ``repro.core.quantizer``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "kmeans1d",
    "laplacian_l1_levels",
    "laplacian_l1_centers",
    "uniform_centers",
    "assign_to_centers",
    "quantize_to_centers",
    "subsample",
]


# ---------------------------------------------------------------------------
# assignment / replacement
# ---------------------------------------------------------------------------

_CHUNK = 1 << 22    # values per searchsorted call (see _per_chunk)


def _per_chunk(fn, values):
    """``fn`` over the flattened values in fixed chunks, reshaped back.

    ``searchsorted`` keeps several n-element int32 temporaries — ~10 GB
    for one stacked FFN matrix of a 1.7B model, more than a 16 GB chip
    holds beside the model — so large tensors are searched chunk by chunk.
    The chunks are a ``fori_loop`` of dynamic slices: a ``lax.map`` over a
    (chunks, C) reshape computes the same but took the TPU compiler over
    100 s per stacked weight shape (against ~2 s), which at full width
    alone overran a 20-minute run.
    """
    flat = values.reshape(-1)
    n = flat.shape[0]
    if n <= _CHUNK:
        return fn(flat).reshape(values.shape)
    flat = jnp.pad(flat, (0, -n % _CHUNK))
    out = jax.eval_shape(fn, jax.ShapeDtypeStruct((_CHUNK,), flat.dtype))

    def body(i, acc):
        v = jax.lax.dynamic_slice_in_dim(flat, i * _CHUNK, _CHUNK)
        return jax.lax.dynamic_update_slice_in_dim(acc, fn(v), i * _CHUNK, 0)

    acc = jax.lax.fori_loop(0, flat.shape[0] // _CHUNK, body,
                            jnp.zeros(flat.shape, out.dtype))
    return acc[:n].reshape(values.shape)


def _search_method() -> str:
    """``jnp.searchsorted``'s method for this backend.  ``scan`` is a chain
    of dependent gathers — the fastest on CPU; on an accelerator a scalar
    gather is slow, while comparing each value with all |W|−1 boundaries
    fuses into one pass of vector work with no temporaries.  Every method
    returns the same indices."""
    return "scan" if jax.default_backend() == "cpu" else "compare_all"


def _nearest(values, centers):
    # midpoint boundaries: in 1-D the nearest-center regions are the
    # intervals between adjacent-center midpoints
    boundaries = (centers[:-1] + centers[1:]) / 2.0
    return jnp.searchsorted(boundaries, values, side="right",
                            method=_search_method())


@jax.jit
def assign_to_centers(values: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """Nearest-center index for each value.  ``centers`` must be sorted.

    A ``searchsorted`` over the |W|−1 midpoints gives the argmin without an
    O(n·|W|) distance matrix.
    """
    return _per_chunk(lambda v: _nearest(v, centers).astype(jnp.int32),
                      values)


@jax.jit
def quantize_to_centers(values: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """Replace each value with its assigned (sorted) center's value."""
    return _per_chunk(lambda v: centers[_nearest(v, centers)].astype(v.dtype),
                      values)


def subsample(values: jnp.ndarray, fraction: float, key: jax.Array) -> jnp.ndarray:
    """Random subsample (paper §3.3: 2% of AlexNet's weights for k-means)."""
    n = values.shape[0]
    m = max(1, int(n * fraction))
    idx = jax.random.randint(key, (m,), 0, n)  # with replacement; fine for stats
    return values[idx]


# ---------------------------------------------------------------------------
# k-means (1-D Lloyd, jitted, fixed iteration count)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("k", "iters"))
def kmeans1d(values: jnp.ndarray, k: int, iters: int = 50,
             key: jax.Array | None = None) -> jnp.ndarray:
    """1-D k-means over ``values`` (flattened). Returns sorted centers (f32).

    Lloyd from TWO deterministic inits — data quantiles (equal-mass bins,
    best for heavy-tailed data at small k) and a uniform min..max grid
    (better basin at large k) — keeping whichever converges to lower MSE.
    Single-init 1-D Lloyd is notoriously slow out of a bad basin; the dual
    start fixes that at 2× a cost paid once per 1000 steps.
    Empty clusters keep their previous center.
    """
    v = values.reshape(-1).astype(jnp.float32)
    qs = (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
    init_q = jnp.quantile(v, qs)
    lo, hi = jnp.min(v), jnp.max(v)
    init_u = lo + (hi - lo) * qs

    def lloyd(centers):
        def body(centers, _):
            idx = assign_to_centers(v, centers)
            sums = jax.ops.segment_sum(v, idx, num_segments=k)
            counts = jax.ops.segment_sum(jnp.ones_like(v), idx,
                                         num_segments=k)
            new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0),
                            centers)
            return jnp.sort(new), None

        centers, _ = jax.lax.scan(body, centers, None, length=iters)
        mse = jnp.mean((centers[assign_to_centers(v, centers)] - v) ** 2)
        return centers, mse

    cands, mses = jax.vmap(lloyd)(jnp.stack([init_q, init_u]))
    return cands[jnp.argmin(mses)]


# ---------------------------------------------------------------------------
# Laplacian L1 closed form (paper §2.2)
# ---------------------------------------------------------------------------

def laplacian_l1_levels(n_centers: int) -> np.ndarray:
    """Normalized positive levels L_0..L_m for the L1-optimal Laplacian grid.

    Odd N:  centers at {0, ±L_1 .. ±L_m}, m=(N−1)/2, with exp(−L_i)=1−2i/N.
    Even N: centers at {±L_1 .. ±L_m}, m=N/2, with exp(−L_i)=1−(2i−1)/N
            (same linear-tail-mass construction, no zero center).
    Returned array is the positive half including L_0=0 for odd N.
    """
    if n_centers < 1:
        raise ValueError("need at least one center")
    n = n_centers
    if n % 2 == 1:
        i = np.arange(0, (n - 1) // 2 + 1, dtype=np.float64)
        tail = 1.0 - 2.0 * i / n
    else:
        i = np.arange(1, n // 2 + 1, dtype=np.float64)
        tail = 1.0 - (2.0 * i - 1.0) / n
    return -np.log(np.maximum(tail, 1e-300))


def laplacian_l1_centers(values: jnp.ndarray, n_centers: int,
                         nudge: bool = True) -> jnp.ndarray:
    """Closed-form centers ``a ± b·L_i`` fitted to ``values`` (paper §2.2).

    ``a`` is the mean; ``b`` starts at ``W_max / L_max`` (so the extreme level
    sits at the largest observed amplitude), then is "nudged" per the paper:

    * early training (``W_max < 0.5``): move the extreme level *outward* by
      ``b·Δ_max / (2·(1−W_max))`` — weights are still too tightly packed
      around the mean for a fair Laplacian sample;
    * late training (``W_max > 1.25``): move it slightly *inward* by
      ``b·Δ_max/4`` — retains the regularising pull-back of extreme weights.

    Jittable (n_centers static through the numpy level grid).
    """
    v = values.reshape(-1).astype(jnp.float32)
    levels = jnp.asarray(laplacian_l1_levels(n_centers), dtype=jnp.float32)
    l_max = float(levels[-1])
    # Δ_max = L_m − L_{m−1}: spacing of the outermost pair.
    d_max = float(levels[-1] - levels[-2]) if levels.shape[0] > 1 else 1.0

    a = jnp.mean(v)
    # max |v − a| from the two extremes: rounding is monotonic, so this is
    # the elementwise form bit for bit, without an n-element temporary
    # (6.9 GB at 1.7B weights — more than a 16 GB chip has to spare)
    w_max = jnp.maximum(jnp.max(v) - a, a - jnp.min(v))
    w_max = jnp.maximum(w_max, 1e-12)
    b = w_max / l_max
    if nudge:
        # outward nudge: extreme level b·L_max grows by b·Δ_max/(2(1−W_max))
        out = b * (1.0 + d_max / (2.0 * jnp.maximum(1.0 - w_max, 1e-6) * l_max))
        # inward nudge: extreme level shrinks by b·Δ_max/4
        inw = b * (1.0 - d_max / (4.0 * l_max))
        b = jnp.where(w_max < 0.5, out, jnp.where(w_max > 1.25, inw, b))

    pos = a + b * levels
    if n_centers % 2 == 1:
        neg = a - b * levels[1:]
    else:
        neg = a - b * levels
    return jnp.sort(jnp.concatenate([neg, pos]))


def uniform_centers(values: jnp.ndarray, n_centers: int) -> jnp.ndarray:
    """Equally-spaced centers between min and max (Lin et al. baseline)."""
    v = values.reshape(-1).astype(jnp.float32)
    lo, hi = jnp.min(v), jnp.max(v)
    t = jnp.linspace(0.0, 1.0, n_centers, dtype=jnp.float32)
    return lo + t * (hi - lo)
