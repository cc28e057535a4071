"""Paper §2.2: k-means / Laplacian-L1 weight clustering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -e '.[dev]')")
from hypothesis import given, settings, strategies as st

from repro.core import clustering as CL
from repro.core.quantizer import (WeightQuantConfig, cluster_params,
                                  init_state, num_weights_at, codebook_indices)


def test_laplacian_recursion_identity():
    """The paper's Δ_i = −ln(1 − 2·e^{L_{i−1}}/N) telescopes to
    e^{−L_i} = 1 − 2i/N (linear occupancy, Fig. 5)."""
    for n in (5, 11, 101, 999):
        L = CL.laplacian_l1_levels(n)
        i = np.arange(len(L))
        np.testing.assert_allclose(np.exp(-L), 1 - 2 * i / n, atol=1e-12)
        for j in range(1, len(L)):
            d = -np.log(1 - 2 * np.exp(L[j - 1]) / n)
            assert abs((L[j] - L[j - 1]) - d) < 1e-9


def test_laplacian_spacing_widens():
    """Fig. 5: wider spacing at large amplitudes."""
    L = CL.laplacian_l1_levels(101)
    d = np.diff(L)
    assert np.all(np.diff(d) > -1e-12)


@pytest.mark.parametrize("method", ["scan", "compare_all"])  # CPU, TPU
@pytest.mark.parametrize("n", [500, CL._CHUNK + 4321])   # + chunked path
def test_assign_matches_bruteforce(n, method, monkeypatch):
    monkeypatch.setattr(CL, "_search_method", lambda: method)
    for fn in (CL.assign_to_centers, CL.quantize_to_centers):
        fn.clear_cache()                  # the method is fixed at trace time
    rng = np.random.default_rng(1)
    centers = jnp.sort(jnp.asarray(rng.normal(size=37)))
    v = jnp.asarray(rng.normal(size=n) * 2)
    idx = np.asarray(CL.assign_to_centers(v, centers))
    vn, cn = np.asarray(v), np.asarray(centers)
    brute = np.concatenate([
        np.argmin(np.abs(vn[i:i + 500_000, None] - cn[None, :]), axis=1)
        for i in range(0, n, 500_000)])
    np.testing.assert_array_equal(idx, brute)
    np.testing.assert_array_equal(np.asarray(CL.quantize_to_centers(v, centers)),
                                  cn[brute])


def test_kmeans_beats_uniform_on_laplacian():
    key = jax.random.PRNGKey(0)
    v = jax.random.laplace(key, (50_000,))
    for k in (16, 64, 256):
        km = CL.quantize_to_centers(v, CL.kmeans1d(v, k))
        un = CL.quantize_to_centers(v, CL.uniform_centers(v, k))
        lap = CL.quantize_to_centers(v, CL.laplacian_l1_centers(v, k))
        mse = lambda q: float(jnp.mean((q - v) ** 2))
        assert mse(km) < mse(un), k          # paper's case against Lin et al.
        assert mse(lap) < mse(un), k


def test_kmeans_center_count_and_idempotence():
    v = jax.random.normal(jax.random.PRNGKey(2), (10_000,))
    c = CL.kmeans1d(v, 32)
    assert c.shape == (32,)
    q = CL.quantize_to_centers(v, c)
    q2 = CL.quantize_to_centers(q, c)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    assert len(np.unique(np.asarray(q))) <= 32


@settings(max_examples=25, deadline=None)
@given(st.integers(3, 200))
def test_laplacian_centers_symmetric(n):
    v = jax.random.laplace(jax.random.PRNGKey(4), (5000,)) * 0.3 + 0.1
    c = np.asarray(CL.laplacian_l1_centers(v, n, nudge=False))
    assert c.shape == (n,)
    a = float(jnp.mean(v))
    np.testing.assert_allclose(c + c[::-1], 2 * a, atol=1e-4)


def test_cluster_params_global_scope():
    key = jax.random.PRNGKey(0)
    params = {"a": {"w": jax.random.normal(key, (32, 64))},
              "b": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                           (16, 8)),
                    "bias": jax.random.normal(jax.random.fold_in(key, 2),
                                              (8,))}}
    wq = WeightQuantConfig(num_weights=17, method="kmeans", interval=10)
    newp, state = cluster_params(params, wq, init_state(wq), 10, key)
    allv = np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree.leaves(newp)])
    assert len(np.unique(allv)) <= 17          # ONE global codebook
    assert state.codebooks[""].shape == (17,)
    # biases clustered too (paper: "all of the weights ... including the
    # bias weights")
    assert set(np.unique(np.asarray(newp["b"]["bias"]))) <= \
        set(np.unique(allv))


def test_cluster_params_per_layer_scope():
    key = jax.random.PRNGKey(0)
    params = {"a": {"w": jax.random.normal(key, (64, 64))},
              "b": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                           (64, 64)) * 3}}
    wq = WeightQuantConfig(num_weights=9, method="kmeans", scope="per_layer")
    newp, state = cluster_params(params, wq, init_state(wq), 1000, key)
    ua = np.unique(np.asarray(newp["a"]["w"]))
    ub = np.unique(np.asarray(newp["b"]["w"]))
    assert len(ua) <= 9 and len(ub) <= 9
    assert len(state.codebooks) == 2


def test_exclude_filter():
    key = jax.random.PRNGKey(0)
    params = {"mlp": {"w": jax.random.normal(key, (64, 64))},
              "norm": {"scale": jnp.ones((64,)) * 1.2345}}
    wq = WeightQuantConfig(num_weights=4, method="kmeans", exclude="norm")
    newp, _ = cluster_params(params, wq, init_state(wq), 1000, key)
    np.testing.assert_array_equal(np.asarray(newp["norm"]["scale"]),
                                  np.asarray(params["norm"]["scale"]))
    assert len(np.unique(np.asarray(newp["mlp"]["w"]))) <= 4


def test_wq_schedule_and_due():
    wq = WeightQuantConfig(num_weights=100, anneal_from=1000,
                           anneal_steps=100, interval=10)
    assert num_weights_at(wq, 0) == 1000
    assert num_weights_at(wq, 100) == 100
    assert num_weights_at(wq, 50) < 1000
    assert not wq.due(0) and wq.due(10) and not wq.due(11)


def test_codebook_indices_roundtrip():
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (128, 32))}
    wq = WeightQuantConfig(num_weights=50, method="laplacian_l1")
    newp, state = cluster_params(params, wq, init_state(wq), 1000, key)
    idx_tree, books = codebook_indices(newp, wq, state)
    rec = books[""][np.asarray(idx_tree["w"])]
    np.testing.assert_allclose(np.asarray(newp["w"]), np.asarray(rec),
                               atol=1e-6)
