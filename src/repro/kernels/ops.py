"""Public ops for the kernels package: jit'd wrappers + gradients.

The matmul ops are *routed* per platform (DESIGN.md §12): on TPU the
codebook and lut contractions run compiled tiled Pallas kernels with block
sizes from the autotune cache (``kernels.autotune``); every op off-TPU
takes the semantically identical XLA route (``*_xla``) — interpret-mode
Pallas re-enters the grid per block at HLO level and is orders of
magnitude slower, so it is a *testing* vehicle (tests/test_kernels.py runs
it for block-walk parity), never a serving path.

* ``codebook_matmul(x, w_idx, codebook)`` — differentiable w.r.t. x and the
  codebook (d codebook = scatter-add of x^T·g over indices), NOT w.r.t. the
  integer indices.  This is exactly the gradient structure the paper's
  training uses between clustering events (weights move freely in float;
  here the codebook is the float degree of freedom).
* ``lut_matmul(a_idx, w_idx, table)`` — integer-only, no gradient; the
  row-gather Pallas kernel on TPU, the XLA gather-accumulate elsewhere —
  bit-identical int32 accumulators.
* ``dequantize(w_idx, codebook, dtype)`` — the weights of an index-form
  tensor, for contractions that dequantize before an XLA dot; serving-only.
* ``act_quant(x, kind, levels)`` — paper §2.1 backward: derivative of the
  *underlying* function, ignoring quantization.
* ``kmeans_assign(values, centers)`` — no gradient (clustering is a
  training-loop event, not part of the differentiated graph).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import act_quant as _aq
from repro.kernels import codebook_matmul as _cm
from repro.kernels import kmeans1d as _km
from repro.kernels import lut_matmul as _lm
from repro.kernels import page_gather as _pg

__all__ = ["codebook_matmul", "dequantize", "lut_matmul", "act_quant",
           "kmeans_assign", "gather_pages", "on_tpu",
           "supports_compiled_pallas", "route_counts", "reset_route_counts"]

# Trace-time platform-route counters: "{kernel}.{impl}" -> times that impl
# was selected while tracing (pallas | pallas_interpret | xla).  Read as
# deltas by serving/telemetry.py; kernels/ never imports serving/.
ROUTE_CALLS: dict = {}


def _count_route(kernel: str, impl: str) -> None:
    key = f"{kernel}.{impl}"
    ROUTE_CALLS[key] = ROUTE_CALLS.get(key, 0) + 1


def route_counts() -> dict:
    return dict(ROUTE_CALLS)


def reset_route_counts() -> None:
    ROUTE_CALLS.clear()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def supports_compiled_pallas() -> bool:
    """True when the platform can run these kernels compiled (Mosaic).

    The kernels are written against the TPU memory hierarchy (VMEM-resident
    tables, MXU accumulation); everywhere else they execute in Pallas
    interpret mode — same numerics, HLO-level speed — so the serving
    backends stay usable on CPU dev boxes and in CI.
    """
    return on_tpu()


def _interp() -> bool:
    return not supports_compiled_pallas()


def _tuned(kernel: str, m: int, k: int, n: int, dtype, table_shape):
    from repro.kernels import autotune

    plat = "tpu" if supports_compiled_pallas() else "xla"
    return autotune.kernel_config(kernel, int(m), int(k), int(n),
                                  dtype=jnp.dtype(dtype).name, plat=plat,
                                  table_shape=tuple(int(d)
                                                    for d in table_shape))


# --- codebook matmul ---------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=())
def codebook_matmul(x, w_idx, codebook):
    m, k = x.shape
    n = w_idx.shape[1]
    cfg = _tuned("codebook", m, k, n, x.dtype, codebook.shape)
    if cfg.get("impl") == "xla":
        _count_route("codebook", "xla")
        return _cm.codebook_matmul_xla(x, w_idx, codebook)
    _count_route("codebook", "pallas_interpret" if _interp() else "pallas")
    return _cm.codebook_matmul_pallas(x, w_idx, codebook, bm=cfg["bm"],
                                      bn=cfg["bn"], bk=cfg["bk"],
                                      interpret=_interp())


def _cm_fwd(x, w_idx, codebook):
    return codebook_matmul(x, w_idx, codebook), (x, w_idx, codebook)


def _cm_bwd(res, g):
    x, w_idx, codebook = res
    w = codebook[w_idx.astype(jnp.int32)].astype(g.dtype)        # (K, N)
    dx = jnp.dot(g, w.T).astype(x.dtype)
    # d codebook: scatter-add (x^T g) over the index map
    xtg = jnp.dot(x.astype(jnp.float32).T, g)                    # (K, N)
    dbook = jax.ops.segment_sum(xtg.reshape(-1),
                                w_idx.astype(jnp.int32).reshape(-1),
                                num_segments=codebook.shape[0])
    return dx, None, dbook.astype(codebook.dtype)


codebook_matmul.defvjp(_cm_fwd, _cm_bwd)


def dequantize(w_idx, codebook, dtype):
    """``codebook[w_idx]`` as ``dtype`` — identical values on every route:
    the Pallas dequantize on TPU (lane gathers, a vreg at a time), the XLA
    gather elsewhere.  No gradient (serving-only)."""
    if supports_compiled_pallas():
        _count_route("dequantize", "pallas")
        return _cm.dequantize_pallas(w_idx, codebook, dtype=dtype,
                                     interpret=False)
    _count_route("dequantize", "xla")
    return codebook[w_idx.astype(jnp.int32)].astype(dtype)


# --- faithful integer engine -------------------------------------------------

def lut_matmul(a_idx, w_idx, table):
    """Integer accumulators of the §4 engine (no gradient, by construction).

    On TPU the Pallas kernel (``lut_matmul_pallas``: whole table rows
    gathered per activation, column lookups as in-kernel lane gathers);
    elsewhere the XLA gather-accumulate, whose chunking/variant
    autotune picks.  Any route is bit-identical — integer addition is
    associative.
    """
    m, k = a_idx.shape
    n = w_idx.shape[1]
    cfg = _tuned("lut", m, k, n, a_idx.dtype, table.shape)
    if cfg.get("impl") == "xla":
        _count_route("lut", "xla")
        return _lm.lut_matmul_xla(a_idx, w_idx, table, kc=cfg["kc"],
                                  variant=cfg["variant"])
    _count_route("lut", "pallas_interpret" if _interp() else "pallas")
    return _lm.lut_matmul_pallas(a_idx, w_idx, table, bm=cfg["bm"],
                                 bn=cfg["bn"], bk=cfg["bk"],
                                 interpret=_interp())


# --- paged KV cache: page-table gather ---------------------------------------

def gather_pages(pool, page_table):
    """out[b, p] = pool[page_table[b, p]] — the paged-decode gather.

    pool: (n_pages, page, *rest); page_table: (B, P) int32.  Returns
    (B, P, page, *rest).  On TPU this is the compiled Pallas scalar-prefetch
    kernel (one DMA per page, no index expansion); elsewhere the identical
    gather is left to XLA — ``jnp.take`` fuses on CPU whereas interpret-mode
    Pallas would re-enter Python inside every decode step.  No gradient
    (serving-only, like ``lut_matmul``).
    """
    if supports_compiled_pallas():
        _count_route("page_gather", "pallas")
        return _pg.page_gather_pallas(pool, page_table, interpret=False)
    _count_route("page_gather", "xla")
    # mode='clip' matches the Pallas kernel's explicit page-id clamp (the
    # jnp.take default is 'fill', which would turn an OOB id into NaN/INT_MIN
    # rather than the bounded-garbage contract both paths promise)
    return jnp.take(pool, page_table.astype(jnp.int32), axis=0, mode="clip")


# --- fused activation quantization ------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def act_quant(x, kind: str, levels: int):
    y, _ = _aq.act_quant_pallas(x, kind=kind, levels=levels,
                                interpret=_interp())
    return y


def _aq_fwd(x, kind, levels):
    return act_quant(x, kind, levels), x


def _aq_bwd(kind, levels, x, g):
    # derivative of the underlying (un-quantized) nonlinearity — paper §2.1
    if kind == "tanh":
        d = 1.0 - jnp.tanh(x) ** 2
    elif kind == "relu6":
        d = ((x > 0.0) & (x < 6.0)).astype(g.dtype)
    elif kind == "sigmoid":
        s = jax.nn.sigmoid(x)
        d = s * (1.0 - s)
    elif kind == "rtanh":
        d = jnp.where(x > 0.0, 1.0 - jnp.tanh(x) ** 2, 0.0)
    else:
        raise ValueError(kind)
    return ((g * d).astype(x.dtype),)


act_quant.defvjp(_aq_fwd, _aq_bwd)


def act_quant_index(x, kind: str, levels: int):
    """Level indices only (int32; no gradient path)."""
    _, idx = _aq.act_quant_pallas(x, kind=kind, levels=levels,
                                  interpret=_interp())
    return idx


# --- k-means streaming assignment -------------------------------------------

def kmeans_assign(values, centers):
    """(assignment idx, per-center sums, per-center counts)."""
    return _km.kmeans_assign_pallas(values, centers, interpret=_interp())
