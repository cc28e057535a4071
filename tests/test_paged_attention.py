"""The paged-attention kernel (DESIGN.md §8) against the read it replaces.

``paged_attention_pallas`` reads each slot's live int8 pages in place from
the stacked pool and returns partial softmax statistics; ``_join_fresh``
folds the fresh tokens in.  In Pallas interpret mode that must equal the
XLA read — page gather, dequantize, ``decode_attention`` (Lq = 1) or
``chunk_attention`` (a 16-token prefill chunk) — over a stacked pool of 3
layers read at layers 0 and 2, with slots at valid length 0, mid-page, an
exact page multiple and across two compute blocks, a retired all-zero
table row, and a page id shared by two slots.  Off TPU the serving path
keeps the XLA read, and the route counter says so.  The region args that
count the pages read come from the same rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import attention as A
from repro.serving.engine import kv_page_counts

L, N_PAGES, PAGE, KV, HD, P = 3, 40, 16, 2, 128, 12
# valid length per slot: none, mid-page, two exact pages, two 128-token
# blocks (the second partial), and a retired slot whose stale pos is 40
LENS = np.array([0, 7, 32, 150, 40], np.int32)


def _table():
    pt = np.zeros((len(LENS), P), np.int32)
    pt[0, :3] = [1, 2, 3]
    pt[1, :1] = [5]
    pt[2, :2] = [6, 7]
    pt[3, :10] = np.arange(10, 20)
    pt[3, 2] = 6                      # shares slot 2's page 6
    return pt                         # slot 4: retired, all zero


def _pool(rng):
    kq, ks = A.quantize_kv(jnp.asarray(
        rng.normal(size=(L, N_PAGES, PAGE, KV, HD)), jnp.float32))
    vq, vs = A.quantize_kv(jnp.asarray(
        rng.normal(size=(L, N_PAGES, PAGE, KV, HD)), jnp.float32))
    return kq, vq, (ks, vs)


@pytest.mark.parametrize("layer,dtype", [(0, jnp.float32),
                                         (2, jnp.bfloat16)])
@pytest.mark.parametrize("lq,groups", [(1, 2), (1, 12), (16, 2), (16, 12)])
def test_kernel_matches_xla_read(lq, groups, layer, dtype):
    rng = np.random.default_rng(lq * 100 + groups * 10 + layer)
    kq, vq, scales = _pool(rng)
    assert kq.dtype == jnp.int8 and scales[0].dtype == jnp.bfloat16
    pt = _table()
    B = len(LENS)
    q = jnp.asarray(rng.normal(size=(B, lq, KV, groups, HD)), dtype)
    k_new = jnp.asarray(rng.normal(size=(B, lq, KV, HD)), dtype)
    v_new = jnp.asarray(rng.normal(size=(B, lq, KV, HD)), dtype)

    qr = q.transpose(0, 2, 3, 1, 4).reshape(B, KV, groups * lq, HD)
    num, denom, m = paged_attention_pallas(
        qr, kq, vq, scales[0], scales[1], layer, jnp.asarray(pt),
        jnp.asarray(LENS), interpret=True)
    got = A._join_fresh(q, k_new, v_new,
                        num.reshape(B, KV, groups, lq, HD),
                        denom.reshape(B, KV, groups, lq),
                        m.reshape(B, KV, groups, lq))

    # the XLA read; a retired slot reads no page, so it attends only to
    # its fresh tokens (the gather would show it the trash page)
    ref_len = jnp.asarray(np.where(pt[:, 0] == 0, 0, LENS))
    k_l, v_l = A._gather_paged_kv(kq, vq, jnp.asarray(pt), layer, scales)
    if lq == 1:
        want = A.decode_attention(q, k_l, v_l, ref_len,
                                  extra_kv=(k_new, v_new))
    else:
        want = A.chunk_attention(q, k_l, v_l, ref_len, k_new, v_new)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    # the retired slot's statistics are empty
    assert float(jnp.abs(num[4]).max()) == 0.0
    assert float(jnp.abs(denom[4]).max()) == 0.0


def test_off_tpu_the_xla_read_runs():
    rng = np.random.default_rng(7)
    kq, vq, scales = _pool(rng)
    q = jnp.asarray(rng.normal(size=(len(LENS), 1, KV, 2, HD)), jnp.float32)
    ops.reset_route_counts()
    assert ops.paged_attention(q, kq, vq, scales, 0, jnp.asarray(_table()),
                               jnp.asarray(LENS)) is None
    assert ops.route_counts() == {"paged_attention.xla": 1}


def test_kv_page_counts():
    pt = np.zeros((4, 6), np.int32)
    pt[1, :3] = [9, 3, 4]             # pos 17 with 16-token pages: 2 pages
    pt[2, :2] = [5, 6]                # pos 40 reaches a third page, id 0
    pt[3, :1] = [8]                   # pos 0 reads nothing
    got = kv_page_counts(np.array([33, 17, 40, 0]), pt, 16)
    # slot 0 is retired: its stale pos 33 reads nothing
    assert got == {"kv_pages_read": 2 + 2, "kv_pages_table": 24}
