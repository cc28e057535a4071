"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("m,k,n", [(4, 8, 4), (5, 37, 9), (128, 128, 128),
                                   (130, 200, 260), (1, 512, 7)])
@pytest.mark.parametrize("idt", [jnp.int8, jnp.int16, jnp.int32])
@pytest.mark.parametrize("xdt", [jnp.float32, jnp.bfloat16])
def test_codebook_matmul_sweep(m, k, n, idt, xdt):
    W = min(int(jnp.iinfo(idt).max), 1000)
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (m, k), xdt)
    wi = jax.random.randint(ks[1], (k, n), 0, W).astype(idt)
    book = jax.random.normal(ks[2], (W,), jnp.float32)
    out = ops.codebook_matmul(x, wi, book)
    exp = ref.codebook_matmul_ref(x, wi, book)
    tol = 2e-2 if xdt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=tol, atol=tol * k)


def test_codebook_matmul_grads():
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (8, 16))
    wi = jax.random.randint(ks[1], (16, 12), 0, 32)
    book = jax.random.normal(ks[2], (32,))
    g = jax.grad(lambda x, b: jnp.sum(ops.codebook_matmul(x, wi, b) ** 2),
                 argnums=(0, 1))(x, book)
    gr = jax.grad(lambda x, b: jnp.sum(ref.codebook_matmul_ref(x, wi, b) ** 2),
                  argnums=(0, 1))(x, book)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n,R,C", [(4, 10, 6, 17, 33),
                                       (129, 257, 131, 33, 1001),
                                       (8, 128, 128, 9, 257)])
def test_lut_matmul_bit_exact(m, k, n, R, C):
    a = jax.random.randint(KEY, (m, k), 0, R)
    w = jax.random.randint(jax.random.fold_in(KEY, 1), (k, n), 0, C)
    t = jax.random.randint(jax.random.fold_in(KEY, 2), (R, C), -1000, 1000)
    np.testing.assert_array_equal(np.asarray(ops.lut_matmul(a, w, t)),
                                  np.asarray(ref.lut_matmul_ref(a, w, t)))


@pytest.mark.parametrize("kind", ["tanh", "relu6", "sigmoid", "rtanh"])
@pytest.mark.parametrize("levels", [2, 16, 256])
@pytest.mark.parametrize("shape", [(7,), (3, 5), (2, 130, 9)])
def test_act_quant_sweep(kind, levels, shape):
    x = jax.random.normal(KEY, shape) * 3
    y = ops.act_quant(x, kind, levels)
    yr = ref.act_quant_ref(x, kind, levels)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-6)
    g = jax.grad(lambda v: jnp.sum(ops.act_quant(v, kind, levels)))(x)
    gr = jax.grad(lambda v: jnp.sum(ref.act_quant_ref(v, kind, levels)))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5)


@pytest.mark.parametrize("n,k", [(100, 3), (10_001, 100), (5000, 257)])
def test_kmeans_assign_sweep(n, k):
    v = jax.random.laplace(KEY, (n,))
    c = jnp.sort(jax.random.normal(jax.random.fold_in(KEY, 3), (k,)))
    idx, sums, counts = ops.kmeans_assign(v, c)
    idr, sr, cr = ref.kmeans_assign_ref(v, c)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idr))
    # sums differ only by f32 accumulation order (chunked matmul vs
    # segment_sum); bound relative to the magnitude of what was summed
    scale = np.abs(np.asarray(v)).sum() / max(len(np.asarray(c)), 1)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sr),
                               rtol=1e-3, atol=1e-4 * scale + 1e-3)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(cr), atol=0.5)


def test_kmeans_assign_full_lloyd_step():
    """One Lloyd update from kernel partials == segment_sum update."""
    v = jax.random.laplace(KEY, (20_000,)) * 0.5
    c = jnp.sort(jax.random.normal(jax.random.fold_in(KEY, 9), (64,)))
    _, sums, counts = ops.kmeans_assign(v, c)
    new = np.where(np.asarray(counts) > 0,
                   np.asarray(sums) / np.maximum(np.asarray(counts), 1), c)
    idr, sr, cr = ref.kmeans_assign_ref(v, c)
    exp = np.where(np.asarray(cr) > 0,
                   np.asarray(sr) / np.maximum(np.asarray(cr), 1), c)
    np.testing.assert_allclose(new, exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bm,bn,bk", [(8, 32, 16), (32, 8, 64), (64, 64, 32)])
def test_codebook_matmul_block_shapes(bm, bn, bk):
    """BlockSpec tiling sweep: results must be block-shape invariant."""
    from repro.kernels.codebook_matmul import codebook_matmul_pallas
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (70, 90))
    wi = jax.random.randint(ks[1], (90, 50), 0, 128).astype(jnp.int16)
    book = jax.random.normal(ks[2], (128,))
    out = codebook_matmul_pallas(x, wi, book, bm=bm, bn=bn, bk=bk,
                                 interpret=True)
    exp = ref.codebook_matmul_ref(x, wi, book)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("bm,bn,bk", [(8, 16, 8), (16, 64, 32)])
def test_lut_matmul_block_shapes(bm, bn, bk):
    from repro.kernels.lut_matmul import lut_matmul_pallas
    a = jax.random.randint(KEY, (33, 49), 0, 9)
    w = jax.random.randint(jax.random.fold_in(KEY, 1), (49, 21), 0, 65)
    t = jax.random.randint(jax.random.fold_in(KEY, 2), (9, 65), -500, 500)
    out = lut_matmul_pallas(a, w, t, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.lut_matmul_ref(a, w, t)))


@pytest.mark.parametrize("m,bm,id_dtype", [(2, 2, jnp.int16),
                                            (11, 8, jnp.int16),
                                            (5, 8, jnp.int32)])
def test_lut_kernel_paper_table(m, bm, id_dtype):
    """The lut kernel at the paper's 4096 × 1000 table (8 lane segments
    per lookup) with ragged M, N and K edges: bit-exact."""
    from repro.kernels.lut_matmul import lut_matmul_pallas
    a = jax.random.randint(KEY, (m, 150), 0, 4096)
    w = jax.random.randint(jax.random.fold_in(KEY, 1), (150, 200), 0, 1000)
    t = jax.random.randint(jax.random.fold_in(KEY, 2), (4096, 1000),
                           -(1 << 20), 1 << 20)
    out = lut_matmul_pallas(a.astype(id_dtype), w.astype(id_dtype), t,
                            bm=bm, bn=128, bk=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.lut_matmul_ref(a, w, t)))


@pytest.mark.parametrize("k,n,W,id_dtype,dtype", [
    (300, 700, 1000, jnp.int16, jnp.float32),   # paper |W|, ragged tiles
    (33, 129, 256, jnp.int8, jnp.bfloat16),     # negative narrow ids
    (5, 3, 100, jnp.int32, jnp.float32)])       # dims below one tile
def test_dequantize_matches_gather(k, n, W, id_dtype, dtype):
    """The Pallas dequantize returns exactly the XLA gather's weights."""
    from repro.kernels.codebook_matmul import dequantize_pallas
    book = jax.random.normal(KEY, (W,))
    idx = jax.random.randint(jax.random.fold_in(KEY, 1), (k, n), 0, W)
    want = np.asarray(ops.dequantize(idx.astype(id_dtype), book, dtype))
    got = dequantize_pallas(idx.astype(id_dtype), book, dtype=dtype, bk=64,
                            bn=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        want, np.asarray(book[idx].astype(dtype)))


def test_kmeans_assign_block_sizes():
    from repro.kernels.kmeans1d import kmeans_assign_pallas
    v = jax.random.laplace(KEY, (3000,))
    c = jnp.sort(jax.random.normal(jax.random.fold_in(KEY, 7), (65,)))
    ref_idx, ref_s, ref_c = ref.kmeans_assign_ref(v, c)
    for bv in (256, 1024, 4096):
        idx, s, cnt = kmeans_assign_pallas(v, c, bv=bv, interpret=True)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        np.testing.assert_allclose(np.asarray(s), np.asarray(ref_s),
                                   rtol=1e-3, atol=1e-2)


# --- property-based parity (ISSUE 6): randomized ragged shapes ---------------
# conftest installs tests/_hypothesis_fallback.py as `hypothesis` when the
# real package is absent, so these properties always run, deterministically.
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.codebook_matmul import (codebook_matmul_pallas,  # noqa: E402
                                           codebook_matmul_xla)
from repro.kernels.lut_matmul import (lut_matmul_pallas,  # noqa: E402
                                      lut_matmul_xla)


def _lut_case(seed, m, k, n, R, C, mag):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, R, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(0, C, (k, n)), jnp.int32)
    t = jnp.asarray(rng.integers(-mag, mag, (R, C)), jnp.int32)
    return a, w, t


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 33), st.integers(1, 40), st.integers(1, 33),
       st.sampled_from([3, 9, 257]), st.sampled_from([5, 65, 256]),
       # 1 << 25 is overflow-adjacent: k*mag approaches but stays inside
       # int32, so any double-count or dropped mask term wraps visibly
       st.sampled_from([1000, 1 << 25]),
       st.integers(0, 10_000))
def test_lut_parity_property(m, k, n, R, C, mag, seed):
    """Every route — XLA rows/flat at several chunk sizes, Pallas interpret
    with blocks larger AND smaller than the dims — must equal the pure-jnp
    oracle bit-for-bit on ragged shapes (integer accumulators: no
    tolerance, exact)."""
    a, w, t = _lut_case(seed, m, k, n, R, C, mag)
    want = np.asarray(ref.lut_matmul_ref(a, w, t))
    for variant in ("rows", "flat"):
        got = lut_matmul_xla(a, w, t, kc=32, variant=variant)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=variant)
    got = lut_matmul_pallas(a, w, t, bm=8, bn=16, bk=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want, err_msg="pallas")


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 33), st.integers(1, 40), st.integers(1, 33),
       st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.integers(0, 10_000))
def test_codebook_parity_property(m, k, n, xdt, seed):
    """XLA fallback and Pallas interpret vs oracle on ragged shapes."""
    rng = np.random.default_rng(seed)
    W = 64
    x = jnp.asarray(rng.standard_normal((m, k)), xdt)
    wi = jnp.asarray(rng.integers(0, W, (k, n)), jnp.int32)
    book = jnp.asarray(rng.standard_normal((W,)), jnp.float32)
    want = np.asarray(ref.codebook_matmul_ref(x, wi, book), np.float32)
    tol = 2e-2 if xdt == jnp.bfloat16 else 2e-5
    for got in (codebook_matmul_xla(x, wi, book),
                codebook_matmul_pallas(x, wi, book, bm=8, bn=16, bk=16,
                                       interpret=True)):
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=tol, atol=tol * k)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 20), st.integers(1, 24), st.integers(1, 20),
       st.integers(0, 10_000))
def test_lut_negative_ids_canonicalize(m, k, n, seed):
    """Narrow signed ids are unsigned-intended: every kernel route must
    treat a negative id as id + table_dim.  The oracle is ref on the
    explicitly canonicalized indices (ref itself does raw flat addressing
    and is NOT the contract for negative inputs)."""
    rng = np.random.default_rng(seed)
    R, C = 300, 256
    a8 = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w8 = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    t = jnp.asarray(rng.integers(-1000, 1000, (R, C)), jnp.int32)
    a_can = jnp.where(a8 < 0, a8.astype(jnp.int32) + R, a8).astype(jnp.int32)
    w_can = jnp.where(w8 < 0, w8.astype(jnp.int32) + C, w8).astype(jnp.int32)
    want = np.asarray(ref.lut_matmul_ref(a_can, w_can, t))
    for variant in ("rows", "flat"):
        got = lut_matmul_xla(a8, w8, t, kc=16, variant=variant)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=variant)
    got = lut_matmul_pallas(a8, w8, t, bm=8, bn=16, bk=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want, err_msg="pallas")


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 20), st.integers(1, 24), st.integers(1, 20),
       st.integers(0, 10_000))
def test_codebook_negative_ids_canonicalize(m, k, n, seed):
    """int8 codebook ids with |W| = 256: -1 must address entry 255 on
    every route (two's-complement reinterpretation, DESIGN.md §12)."""
    rng = np.random.default_rng(seed)
    W = 256
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    wi8 = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    book = jnp.asarray(rng.standard_normal((W,)), jnp.float32)
    wi_can = jnp.where(wi8 < 0, wi8.astype(jnp.int32) + W, wi8)
    want = np.asarray(ref.codebook_matmul_ref(x, wi_can.astype(jnp.int32),
                                              book))
    for got in (codebook_matmul_xla(x, wi8, book),
                codebook_matmul_pallas(x, wi8, book, bm=8, bn=16, bk=16,
                                       interpret=True)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-3)


# --- edge shapes + masking explicitness --------------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 3, 7), (1, 1, 1), (2, 5, 128),
                                   (7, 200, 3), (128, 1, 5)])
def test_lut_edge_shapes_exact(m, k, n):
    """M=1, K < bk, N odd, degenerate dims — through the routed op AND
    every explicit route."""
    a, w, t = _lut_case(99, m, k, n, 17, 33, 1000)
    want = np.asarray(ref.lut_matmul_ref(a, w, t))
    np.testing.assert_array_equal(np.asarray(ops.lut_matmul(a, w, t)), want)
    for kc in (1, 64, 128):
        for variant in ("rows", "flat"):
            got = lut_matmul_xla(a, w, t, kc=kc, variant=variant)
            np.testing.assert_array_equal(np.asarray(got), want)
    got = lut_matmul_pallas(a, w, t, bm=128, bn=128, bk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("m,k,n", [(1, 3, 7), (2, 5, 128), (7, 200, 3)])
def test_codebook_edge_shapes(m, k, n):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    wi = jnp.asarray(rng.integers(0, 16, (k, n)), jnp.int32)
    book = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    want = np.asarray(ref.codebook_matmul_ref(x, wi, book))
    np.testing.assert_allclose(
        np.asarray(ops.codebook_matmul(x, wi, book)), want, rtol=2e-5,
        atol=2e-4)
    got = codebook_matmul_pallas(x, wi, book, bm=128, bn=128, bk=128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-4)


def test_lut_ragged_k_masking_is_explicit():
    """K ragged vs bk, with table[0, 0] deliberately nonzero and all ids 0:
    any unmasked tail contribution adds a multiple of table[0, 0] — exact
    equality proves the tail handling is explicit masking, not an
    assumption that padded/OOB gathers read zeros."""
    R, C = 4, 4
    t = jnp.full((R, C), 7, jnp.int32)         # every entry visible
    for (m, k, n, bk) in [(3, 5, 4, 16), (1, 1, 1, 8), (4, 37, 3, 16)]:
        a = jnp.zeros((m, k), jnp.int32)
        w = jnp.zeros((k, n), jnp.int32)
        want = np.full((m, n), 7 * k, np.int64).astype(np.int32)
        got = lut_matmul_pallas(a, w, t, bm=8, bn=8, bk=bk, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want)
        for variant in ("rows", "flat"):
            got = lut_matmul_xla(a, w, t, kc=bk, variant=variant)
            np.testing.assert_array_equal(np.asarray(got), want)


def test_codebook_ragged_k_masking_is_explicit():
    """Same masking probe for the float kernel: book[0] nonzero, plus
    non-finite activations — the kernel masks BOTH operands on the ragged
    tail (an unmasked NaN times a masked-to-zero weight would still
    poison the accumulator)."""
    book = jnp.asarray([5.0, -1.0], jnp.float32)
    m, k, n = 3, 5, 4
    x = jnp.ones((m, k), jnp.float32)
    wi = jnp.zeros((k, n), jnp.int32)
    want = np.full((m, n), 5.0 * k, np.float32)
    got = codebook_matmul_pallas(x, wi, book, bm=8, bn=8, bk=16,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


# --- page gather: ragged tables + id clamping --------------------------------

@pytest.mark.parametrize("B,P,pages,rest", [(1, 1, 2, (4, 2, 3)),
                                            (3, 5, 7, (4, 2)),
                                            (2, 9, 16, (8,))])
def test_page_gather_parity(B, P, pages, rest):
    from repro.kernels.page_gather import page_gather_pallas
    rng = np.random.default_rng(11)
    pool = jnp.asarray(rng.standard_normal((pages,) + rest), jnp.float32)
    pt = jnp.asarray(rng.integers(0, pages, (B, P)), jnp.int32)
    got = page_gather_pallas(pool, pt, interpret=True)
    want = np.asarray(pool)[np.asarray(pt)]
    np.testing.assert_array_equal(np.asarray(got), want)
    # the ops-level CPU fallback must agree with the kernel
    np.testing.assert_array_equal(np.asarray(ops.gather_pages(pool, pt)),
                                  want)


def test_page_gather_oob_ids_clamp():
    """Out-of-range page ids (negative or >= n_pages) clamp into the pool
    on BOTH routes — the bounded-garbage contract: a bad id degrades to a
    valid page read, never UB / NaN / INT_MIN fill."""
    from repro.kernels.page_gather import page_gather_pallas
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.standard_normal((4, 2, 3)), jnp.float32)
    pt = jnp.asarray([[-3, 0], [2, 9]], jnp.int32)
    clamped = np.clip(np.asarray(pt), 0, 3)
    want = np.asarray(pool)[clamped]
    got = page_gather_pallas(pool, pt, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(ops.gather_pages(pool, pt)),
                                  want)
