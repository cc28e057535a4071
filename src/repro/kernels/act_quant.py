"""Fused quantized-activation kernel (paper §2.1 forward semantics).

Computes ``q(f(x))`` — the underlying bounded nonlinearity followed by
round-to-level in output space — in one VMEM pass, optionally also emitting
the int32 level index (the row index for the §4 LUT engine).  Elementwise,
so the only tiling concern is lane alignment; blocks default to (256, 256).

The backward pass (underlying-function derivative) is attached in ``ops.py``
via ``jax.custom_vjp`` — the kernel itself is forward-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["act_quant_kernel", "act_quant_pallas"]


def _base(kind: str, x):
    if kind == "tanh":
        return jnp.tanh(x)
    if kind == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    if kind == "sigmoid":
        return 1.0 / (1.0 + jnp.exp(-x))
    if kind == "rtanh":
        return jnp.maximum(jnp.tanh(x), 0.0)
    raise ValueError(kind)


def act_quant_kernel(x_ref, y_ref, idx_ref, *, kind: str, levels: int,
                     lo: float, step: float):
    y = _base(kind, x_ref[...].astype(jnp.float32))
    q = jnp.round((y - lo) / step)
    q = jnp.clip(q, 0.0, levels - 1)
    y_ref[...] = (lo + q * step).astype(y_ref.dtype)
    idx_ref[...] = q.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("kind", "levels", "bm", "bn", "interpret"))
def act_quant_pallas(x: jnp.ndarray, *, kind: str, levels: int,
                     bm: int = 256, bn: int = 256,
                     interpret: bool = True):
    """Returns (quantized values, level indices); forward-only semantics.

    x is flattened to 2-D, padded to block multiples, and restored.
    """
    from repro.core.activations import ACT_RANGES
    lo, hi = ACT_RANGES[kind]
    step = (hi - lo) / (levels - 1)

    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = bn
    rows = -(-n // cols)
    pad = rows * cols - n
    x2 = jnp.pad(flat, (0, pad)).reshape(rows, cols)
    rp = (-rows) % bm
    if rp:
        x2 = jnp.pad(x2, ((0, rp), (0, 0)))

    grid = (x2.shape[0] // bm, x2.shape[1] // bn)
    y2, idx2 = pl.pallas_call(
        functools.partial(act_quant_kernel, kind=kind, levels=levels,
                          lo=lo, step=step),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                   pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(x2.shape, jnp.int32)],
        interpret=interpret,
        name="act_quant_pallas",
    )(x2)
    y = y2.reshape(-1)[:n].reshape(shape)
    idx = idx2.reshape(-1)[:n].reshape(shape)
    return y, idx
