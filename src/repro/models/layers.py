"""Shared layers: quant-aware dense, norms, embeddings, FFN variants."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.activations import ActQuantConfig, act_apply
from repro.kernels import dispatch, probes

__all__ = [
    "dense_init", "dense", "rms_norm_init", "rms_norm", "layer_norm_init",
    "layer_norm", "embed_init", "embed_lookup", "ffn_act", "swiglu_init",
    "swiglu", "mlp_init", "mlp_block",
]


# --- dense (the quantization-aware workhorse) --------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32,
               bias: bool = False, std: float | None = None):
    std = (d_in ** -0.5) if std is None else std
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x, kind: str | None = None):
    """x @ W (+ b).  W is dense ('w') or codebook-indexed ('w_idx'+'codebook').

    The index form is the deployment representation from the paper's §4: the
    full weight matrix never exists in HBM — only narrow indices plus the
    |W|-entry codebook.  How the contraction runs is decided by the serving
    backend switch (``kernels.dispatch``, DESIGN.md §3):

    * ``dense`` (default) — gather the codebook, then a plain XLA dot;
      training and every non-serving path take this branch.
    * ``codebook`` — the Pallas ``codebook_matmul`` (dequantize-in-VMEM
      gather feeding the MXU; compiled on TPU, interpret elsewhere).
    * ``lut`` — the faithful §4 integer engine ``lut_matmul``: activations
      snapped to a level grid, int32 table-gather accumulation, no
      multiplications in the contraction.

    ``kind`` ('col' | 'row' | None) names the layer's tensor-parallel role
    per ``distributed.sharding.param_specs`` — consulted only when the
    active backend carries a mesh (DESIGN.md §10), where it decides whether
    the index matrix shards its output axis (col: no collective) or its
    reduction axis (row: one output psum).
    """
    if "w_idx" in p:
        if dispatch.matmul_backend() != "dense" and p["w_idx"].ndim == 2:
            # lut_table: optional precomputed §4 table attached by
            # dispatch.attach_lut_tables (ServeEngine does this at init)
            y = dispatch.backend_matmul(x, p["w_idx"], p["codebook"], kind,
                                        table=p.get("lut_table"))
            if "b" in p:
                y = y + p["b"].astype(x.dtype)
            return y
        w = dispatch.dequantize(p["w_idx"], p["codebook"], x.dtype)
    else:
        w = p["w"].astype(x.dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def kernel_of(p):
    """Materialized weight matrix of a dense param dict (for tests)."""
    if "w_idx" in p:
        return p["codebook"][p["w_idx"].astype(jnp.int32)]
    return p["w"]


# --- norms -------------------------------------------------------------------

def rms_norm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layer_norm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# --- embeddings --------------------------------------------------------------

def embed_init(key, vocab: int, d: int, dtype=jnp.float32, std: float = 0.02):
    return {"table": (jax.random.normal(key, (vocab, d)) * std).astype(dtype)}


def embed_lookup(p, ids):
    if "w_idx" in p:  # codebook-compressed embedding table
        return p["codebook"][p["w_idx"][ids].astype(jnp.int32)]
    return p["table"][ids]


def embed_logits(p, x):
    """Tied-softmax logits x @ E^T (f32 for a stable CE)."""
    t = p["codebook"][p["w_idx"].astype(jnp.int32)] if "w_idx" in p else p["table"]
    return jnp.dot(x.astype(jnp.float32), t.astype(jnp.float32).T)


# --- FFN ---------------------------------------------------------------------

def ffn_act(x, kind: str, levels: int):
    """The paper's activation-quantization site.

    levels == 0: continuous nonlinearity (baseline).
    levels  > 0: quantized (`act_apply`) — requires a bounded kind; unbounded
                 kinds are swapped for relu6 exactly as the paper swaps
                 AlexNet's ReLU for ReLU6 (§3.3).
    """
    if levels <= 0:
        if kind == "silu":
            return jax.nn.silu(x)
        if kind == "gelu":
            return jax.nn.gelu(x)
        if kind == "relu":
            return jax.nn.relu(x)
        if kind == "relu6":
            return jnp.clip(x, 0.0, 6.0)
        if kind == "tanh":
            return jnp.tanh(x)
        raise ValueError(kind)
    bounded = {"silu": "relu6", "gelu": "relu6", "relu": "relu6"}.get(kind, kind)
    if bounded == "relu6":
        # Saturation probe: inputs outside the hard rails get pinned to an
        # endpoint level by the quantized nonlinearity.  Only relu6 has true
        # rail clipping (tanh/sigmoid saturate asymptotically, no clip).
        probes.tap_act(x, 0.0, 6.0)
    return act_apply(ActQuantConfig(bounded, levels), x)


def swiglu_init(key, d: int, ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w1": dense_init(k1, d, ff, dtype),
            "w3": dense_init(k2, d, ff, dtype),
            "w2": dense_init(k3, ff, d, dtype)}


def _ffn_hidden_constraint(h, mesh):
    """(B, S, ff) intermediate: ff over `model`, S gathered.  Without this,
    a sequence-sharded residual meeting a model-sharded w1 leaves XLA with
    conflicting layouts and it replicates the (B, S, ff) tensor — the
    largest activation in the network (≈5 GB/device at mistral dims)."""
    if mesh is None or h.shape[-1] % mesh.shape["model"] != 0:
        return h
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import dp_axes
    spec = P(dp_axes(mesh), *([None] * (h.ndim - 2)), "model")
    return jax.lax.with_sharding_constraint(h, NamedSharding(mesh, spec))


def swiglu(p, x, act_kind: str = "silu", act_levels: int = 0, mesh=None):
    h = (ffn_act(dense(p["w1"], x, kind="col"), act_kind, act_levels)
         * dense(p["w3"], x, kind="col"))
    h = _ffn_hidden_constraint(h, mesh)
    return dense(p["w2"], h, kind="row")


def mlp_init(key, d: int, ff: int, dtype=jnp.float32, bias: bool = True):
    k1, k2 = jax.random.split(key)
    return {"w1": dense_init(k1, d, ff, dtype, bias=bias),
            "w2": dense_init(k2, ff, d, dtype, bias=bias)}


def mlp_block(p, x, act_kind: str = "gelu", act_levels: int = 0, mesh=None):
    h = ffn_act(dense(p["w1"], x, kind="col"), act_kind, act_levels)
    return dense(p["w2"], _ffn_hidden_constraint(h, mesh), kind="row")
