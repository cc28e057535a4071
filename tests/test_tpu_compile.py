"""Compile-only rehearsal of the serving kernels for a TPU v5e.

Each test lowers and compiles one kernel with Mosaic for a *described*
``v5e:2x2`` topology at the widths of ``qwen3-1.7b`` (d_model 2048, d_ff
6144, vocab 151936, 8 KV heads of 128, int8 KV pages, int16 codebook
ids).  Nothing
runs: this catches what Pallas interpret mode cannot — unsupported
primitives, tiling violations, VMEM overruns, removed compiler APIs — at
no chip time.  The topology is described inside a module-scoped fixture
so that importing this file never loads the TPU compiler; the fixture
skips where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest

D_MODEL, D_FF, N_KV, HEAD_DIM, PAGE = 2048, 6144, 8, 128, 16
N_WEIGHTS = 1000                    # the paper's |W| working point


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,k,n", [(8, D_MODEL, D_FF), (8, D_FF, D_MODEL),
                                   (128, D_MODEL, D_FF)])
def test_codebook_matmul_compiles(one_chip, m, k, n):
    from repro.kernels import autotune
    from repro.kernels.codebook_matmul import codebook_matmul_pallas

    cfg = autotune.kernel_config("codebook", m, k, n, dtype="bfloat16",
                                 plat="tpu", table_shape=(N_WEIGHTS,))
    assert cfg["impl"] == "pallas"

    def f(x, idx, book):
        return codebook_matmul_pallas(x, idx, book, bm=cfg["bm"],
                                      bn=cfg["bn"], bk=cfg["bk"],
                                      interpret=False)

    _compile(f, _spec(one_chip, (m, k), jnp.bfloat16),
             _spec(one_chip, (k, n), jnp.int16),
             _spec(one_chip, (N_WEIGHTS,), jnp.float32))


@pytest.mark.parametrize("k,n,dtype", [
    (151936, D_MODEL, jnp.float32),            # the tied lm-head
    (D_MODEL, D_FF, jnp.bfloat16)])            # a dense-backend FFN weight
def test_dequantize_compiles(one_chip, k, n, dtype):
    from repro.kernels.codebook_matmul import dequantize_pallas

    _compile(lambda idx, book: dequantize_pallas(idx, book, dtype=dtype,
                                                 interpret=False),
             _spec(one_chip, (k, n), jnp.int16),
             _spec(one_chip, (N_WEIGHTS,), jnp.float32))


@pytest.mark.parametrize("page_shape,dtype", [
    ((PAGE, N_KV, HEAD_DIM), jnp.int8),        # K / V pages
    ((PAGE, N_KV), jnp.float32),               # per-token-per-head scales
])
def test_page_gather_compiles(one_chip, page_shape, dtype):
    from repro.kernels.page_gather import page_gather_pallas

    _compile(lambda pool, pt: page_gather_pallas(pool, pt, interpret=False),
             _spec(one_chip, (64,) + page_shape, dtype),
             _spec(one_chip, (4, 8), jnp.int32))


def test_act_quant_compiles(one_chip):
    from repro.kernels.act_quant import act_quant_pallas

    _compile(lambda x: act_quant_pallas(x, kind="relu6", levels=32,
                                        interpret=False),
             _spec(one_chip, (8, D_FF), jnp.float32))


def test_kmeans_assign_compiles(one_chip):
    from repro.kernels.kmeans1d import kmeans_assign_pallas

    _compile(lambda v, c: kmeans_assign_pallas(v, c, interpret=False),
             _spec(one_chip, (1 << 16,), jnp.float32),
             _spec(one_chip, (N_WEIGHTS,), jnp.float32))


@pytest.mark.parametrize("m,k,n", [(2, D_MODEL, D_FF), (16, D_FF, D_MODEL)])
def test_lut_rows_kernel_compiles(one_chip, m, k, n):
    """The lut engine's TPU route — the row-gather kernel, with the tiles
    autotune picks — at the paper's 4096 × 1000 table, for a decode step
    (M = 2) and a 16-token prefill chunk."""
    from repro.kernels import autotune
    from repro.kernels.lut_matmul import lut_matmul_pallas

    cfg = autotune.kernel_config("lut", m, k, n, dtype="int32",
                                 plat="tpu", table_shape=(4096, N_WEIGHTS))
    assert cfg["impl"] == "pallas"
    _compile(lambda a, w, t: lut_matmul_pallas(
        a, w, t, bm=cfg["bm"], bn=cfg["bn"], bk=cfg["bk"], interpret=False),
        _spec(one_chip, (m, k), jnp.int16),
        _spec(one_chip, (k, n), jnp.int16),
        _spec(one_chip, (4096, N_WEIGHTS), jnp.int32))
