"""Serving-time matmul backend switch: ``dense`` | ``codebook`` | ``lut``.

``models.layers.dense`` consults this module whenever its params are in
codebook-index form (``{'w_idx', 'codebook'}``, see serving/compress.py) and
routes the contraction accordingly (DESIGN.md §2–§3):

* ``dense``    — gather the codebook then a plain XLA dot (default; what
                 training and the seed serving path always did).
* ``codebook`` — ``kernels.codebook_matmul``: indices stay narrow in HBM,
                 dequantize-in-VMEM gather feeding the MXU.  The TPU
                 deployment artifact; compiled Pallas on TPU/GPU, interpret
                 fallback elsewhere (see ``kernels.ops``).
* ``lut``      — ``kernels.lut_matmul``: the paper's faithful §4 engine.
                 Activations are snapped to a uniform level grid, the
                 multiplication table M[a, w] = rint(a·w·2^s/Δa) is gathered
                 and accumulated in int32 — the contraction itself performs
                 no multiplications and no floating-point ops.

The backend is *trace-time* state: ``ServeEngine`` (and anything else) wraps
its jitted calls in ``use_backend(...)`` so the choice is baked into each
trace; already-compiled executables are unaffected by later switches.
Backend selection is process-global, not thread-local — concurrent tracing
under different backends is not supported.

**Tensor parallelism** (DESIGN.md §10): ``use_backend(..., mesh=...)``
additionally routes every index-form contraction through ``shard_map`` over
the mesh's ``model`` axis.  The *weights never rematerialize*: only the
narrow integer indices are sharded — column-parallel mats
(``kind='col'``: wq/wk/wv/w1/w3/lm_head) split the output axis, N/tp
indices per shard, no collective; row-parallel mats (``kind='row'``:
wo/w2) split the reduction axis, K/tp indices per shard, one psum of the
(…, N) output.  The codebook (and the lut backend's A×W table, built from
it) replicates — it is tiny by construction.  The ``lut`` row-parallel
psum happens on the **int32 accumulator** (exact: integer addition is
associative), so a TP-sharded lut contraction is bit-identical to the
single-device one; the scale chosen for the full fan-in stays safe for
every K/tp sub-reduction.  Layers whose sharded axis does not divide the
TP degree fall back to replicated compute inside an all-replicated
shard_map (correct, no savings).
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import probes

__all__ = ["BACKENDS", "LutSpec", "BackendSpec", "make_lut_spec",
           "use_backend", "matmul_backend", "matmul_mesh", "dequantize",
           "backend_matmul",
           "bind_backend", "build_lut_table", "attach_lut_tables",
           "kernel_config", "autotune_shapes", "matmul_call_counts",
           "reset_matmul_call_counts"]

BACKENDS = ("dense", "codebook", "lut")


@dataclasses.dataclass(frozen=True)
class LutSpec:
    """Static description of the §4 integer emulation grid.

    a_min/a_max: activation clip range covered by the level grid.  RMS-normed
                 transformer activations live well inside ±16; anything
                 outside saturates (same posture as the paper's bounded
                 activations).
    levels:      |A| — number of activation levels (grid resolution Δa).
    s:           fixed-point scale exponent, chosen by ``make_lut_spec`` so
                 ``fan_in · max|M|`` statically fits an int32 accumulator
                 (the paper's no-overflow guarantee, core/lut.choose_scale).
    """

    a_min: float
    a_max: float
    levels: int
    s: int

    @property
    def da(self) -> float:
        return (self.a_max - self.a_min) / (self.levels - 1)


def make_lut_spec(codebook, fan_in: int, *, levels: int = 4096,
                  a_range: tuple[float, float] = (-16.0, 16.0),
                  acc_bits: int = 32) -> LutSpec:
    """Pick the largest scale s with a static no-overflow guarantee.

    max|M| = max|a|·max|w|·2^s/Δa and we need fan_in·max|M| < 2^(acc_bits−1)
    — the same bound as ``core.lut.choose_scale`` with the activation grid
    standing in for the level set.
    """
    a_min, a_max = a_range
    da = (a_max - a_min) / (levels - 1)
    wmax = float(np.max(np.abs(np.asarray(codebook, np.float64))))
    amax = max(abs(a_min), abs(a_max))
    headroom = 2.0 ** (acc_bits - 1) - 1
    s = int(np.floor(np.log2(headroom * da / max(fan_in * wmax * amax, 1e-30))))
    if s < 1:
        raise ValueError(
            f"no int{acc_bits} scale fits fan_in={fan_in}, max|w|={wmax:.3g}, "
            f"grid ±{amax}: coarsen the grid or widen the accumulator")
    return LutSpec(a_min=a_min, a_max=a_max, levels=levels, s=s)


def build_lut_table(codebook, spec: LutSpec):
    """The §4 multiplication table M[a, w] = rint(a·w·2^s/Δa) as int32.

    ONE recipe shared by every consumer (engine-time precompute, the TP
    psum path, the trace-time fallback below) — parity across them depends
    on the rounding being identical.  Accepts a single (|W|,) codebook or
    a layer-stacked (L, |W|) one; the activation-grid axis is appended
    second-to-last either way → (|A|, |W|) or (L, |A|, |W|).
    """
    da, s = spec.da, spec.s
    avals = spec.a_min + jnp.arange(spec.levels, dtype=jnp.float32) * da
    scale = (2.0 ** s) / da
    prod = avals[:, None] * codebook.astype(jnp.float32)[..., None, :]
    return jnp.rint(prod * scale).astype(jnp.int32)


def attach_lut_tables(params, spec: LutSpec):
    """Precompute a ``lut_table`` leaf next to every routed index-form dict.

    The table is a pure function of (codebook, grid) but building it inside
    the per-layer ``lax.scan`` body cannot be hoisted by XLA (the scanned
    codebook leaf is a per-iteration slice) — so the lut backend used to
    re-rint the whole |A|×|W| table every layer, every step.  Attaching it
    as a param leaf turns that into a plain HBM operand: stacked (L, |W|)
    codebooks get a stacked (L, |A|, |W|) table the scan slices alongside
    the indices, and ``distributed.sharding.serve_param_specs`` replicates
    any non-w/w_idx leaf, so the table rides through TP untouched (the §10
    psum contract needs every shard to see the identical table).

    The embedding's index form is skipped: its lookup (and the tied
    lm-head) dequantize via the codebook directly, never through
    ``backend_matmul``.
    """
    def walk(node, parts):
        if not isinstance(node, dict):
            return node
        if "w_idx" in node and "codebook" in node \
                and "embed" not in parts and node["w_idx"].ndim >= 2:
            return {**node, "lut_table": build_lut_table(node["codebook"],
                                                         spec)}
        return {k: walk(v, parts + [k]) for k, v in node.items()}

    return walk(params, [])


def kernel_config(kernel: str, m: int, k: int, n: int, *, dtype: str,
                  table_shape: tuple, plat: str | None = None, **kw):
    """Launch config for one contraction site — see ``kernels.autotune``.

    ``plat`` defaults to the live platform class: 'tpu' (compiled Pallas)
    when Mosaic is available, 'xla' (fallback kernels) otherwise.
    """
    from repro.kernels import autotune, ops

    if plat is None:
        plat = "tpu" if ops.supports_compiled_pallas() else "xla"
    return autotune.kernel_config(kernel, m, k, n, dtype=dtype, plat=plat,
                                  table_shape=table_shape, **kw)


def autotune_shapes(shapes, **kw):
    """Batch-tune + persist the cache JSON — see ``kernels.autotune``."""
    from repro.kernels import autotune

    return autotune.autotune_shapes(shapes, **kw)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """A (backend, lut grid, mesh) triple naming how ONE model's matmuls run.

    Speculative decoding traces TWO models inside one jitted step — e.g. a
    coarse-grid ``lut``-tier draft proposing tokens that a ``codebook``-tier
    target verifies (serving/spec.py).  The ambient backend state is scoped
    and re-entrant, so per-model overrides nest freely within a single
    trace:

        with target.scope():            # e.g. codebook
            ... trace the verify forward ...
            with draft.scope():         # e.g. lut, its own (coarser) grid
                ... trace the draft proposal loop ...

    Each scope applies only to the ops traced under it; the executable that
    comes out runs both models' contractions through their own kernels.
    The one-backend-per-jitted-function rule of ``bind_backend`` still
    holds at the *outer* level: a function whose trace mixes scopes must
    itself be jitted once per (target, draft) pairing.
    """

    name: str = "dense"
    lut_spec: LutSpec | None = None
    mesh: object = None            # None = single-device (draft models)

    def scope(self):
        return use_backend(self.name, self.lut_spec, self.mesh)


class _State:
    backend: str = "dense"
    lut_spec: LutSpec | None = None
    mesh: object = None


_STATE = _State()

# Trace-time dispatch counters: "{backend}.{route}" -> number of
# backend_matmul sites traced through that route (local | col | row |
# replicated).  Process-global like the backend state itself; the serving
# telemetry registry (serving/telemetry.py) reads these as deltas — this
# module must never import serving/.
MATMUL_CALLS: dict = {}


def _count_route(route: str) -> None:
    key = f"{_STATE.backend}.{route}"
    MATMUL_CALLS[key] = MATMUL_CALLS.get(key, 0) + 1


def matmul_call_counts() -> dict:
    """Snapshot of the trace-time route counters."""
    return dict(MATMUL_CALLS)


def reset_matmul_call_counts() -> None:
    MATMUL_CALLS.clear()


def matmul_backend() -> str:
    """The backend active for traces happening right now."""
    return _STATE.backend


def bind_backend(fn, name: str, lut_spec: LutSpec | None = None, mesh=None):
    """A *new* callable running ``fn`` under ``use_backend(name, ...)``.

    jax.jit keys its executable cache on function identity, NOT on this
    module's ambient backend — jitting the same function object under two
    backends would silently reuse the first trace.  Each ``bind_backend``
    call returns a distinct closure, so ``jax.jit(bind_backend(f, b))``
    gets its own cache per backend.  ``ServeEngine`` builds its jitted
    steps this way.
    """
    def bound(*args, **kwargs):
        with use_backend(name, lut_spec, mesh):
            return fn(*args, **kwargs)
    bound.__name__ = f"{getattr(fn, '__name__', 'fn')}[{name}]"
    return bound


@contextlib.contextmanager
def use_backend(name: str, lut_spec: LutSpec | None = None, mesh=None):
    """Route index-form ``dense`` layers through ``name`` while tracing.

    Trace-time state: enter this context around the *tracing* of a jitted
    function (or wrap the function with ``bind_backend`` so every trace is
    covered).  Never jit one function object under two different backends —
    see ``bind_backend``.  ``mesh`` additionally shard-maps every routed
    contraction over the mesh's ``model`` axis (see module docstring).
    """
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    if name == "lut" and lut_spec is None:
        raise ValueError("backend 'lut' needs a LutSpec (make_lut_spec)")
    prev = _STATE.backend, _STATE.lut_spec, _STATE.mesh
    _STATE.backend, _STATE.lut_spec, _STATE.mesh = name, lut_spec, mesh
    try:
        yield
    finally:
        _STATE.backend, _STATE.lut_spec, _STATE.mesh = prev


def matmul_mesh():
    """The mesh index-form contractions are being sharded over (or None)."""
    return _STATE.mesh


def dequantize(w_idx, codebook, dtype):
    """``codebook[w_idx]`` as ``dtype``: the weights of an index-form tensor
    for the contractions that dequantize before an XLA dot (the ``dense``
    backend, the tied lm-head).  ``ops.dequantize`` where one device holds
    the operands; under a mesh XLA's gather, which the SPMD partitioner
    splits (a Pallas call it would all-gather, see ``_sharded_matmul``)."""
    from repro.kernels import ops  # lazy: keep pallas off the import path

    if _STATE.mesh is None:
        return ops.dequantize(w_idx, codebook, dtype)
    return codebook[w_idx.astype(jnp.int32)].astype(dtype)


def backend_matmul(x, w_idx, codebook, kind: str | None = None, table=None):
    """``x @ codebook[w_idx]`` through the active non-dense backend.

    x: (..., K) float; w_idx: (K, N) integer indices; codebook: (|W|,).
    kind: 'col' | 'row' | None — the layer's TP role per
    ``distributed.sharding.param_specs`` (only consulted when a mesh is
    active; None = replicated compute).  table: optional precomputed
    (|A|, |W|) int32 §4 table (``attach_lut_tables``) — the lut backend
    rebuilds it from the codebook when absent, which is correct but
    re-derives the table inside every layer of a scanned stack.
    Returns (..., N) in x.dtype.  Callers guarantee ``matmul_backend()``
    is not 'dense' (the plain gather+dot lives in models.layers.dense).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _STATE.mesh is not None and "model" in _STATE.mesh.axis_names \
            and _STATE.mesh.shape["model"] > 1:
        y = _sharded_matmul(x2, w_idx, codebook, kind, _STATE.mesh, table)
    else:
        _count_route("local")
        y = _local_matmul(x2, w_idx, codebook, table)
    # Numerics taps sit here, on the full (pre-shard_map) activations and
    # the decoded output — a no-op unless a probes.layer frame is open.
    probes.tap_matmul(x2, y, _STATE.backend, _STATE.lut_spec)
    return y.reshape(*lead, -1).astype(x.dtype)


def _local_matmul(x2, w_idx, codebook, table=None):
    from repro.kernels import ops  # lazy: keep pallas off the import path

    if _STATE.backend == "codebook":
        return ops.codebook_matmul(x2, w_idx, codebook)
    if _STATE.backend == "lut":
        return _lut_matmul(x2, w_idx, codebook, _STATE.lut_spec, table)
    raise ValueError(f"backend_matmul called with {_STATE.backend!r}")


def _sharded_matmul(x2, w_idx, codebook, kind, mesh, table=None):
    """shard_map the contraction over `model` (Pallas kernels have no SPMD
    partitioning rule, so left to XLA they would replicate and all-gather
    their operands — this keeps only int indices moving, never weights).

    col:  x replicated, w_idx (K, N/tp) → local kernel, output N-sharded.
    row:  x (…, K/tp), w_idx (K/tp, N) → local kernel + one (…, N) psum
          (the lut backend psums the int32 accumulator — exact).
    else: all-replicated shard_map (every shard computes the full product).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compat import shard_map

    backend, spec = _STATE.backend, _STATE.lut_spec
    tp = mesh.shape["model"]
    K, N = w_idx.shape

    def kernel(xl, wl):
        from repro.kernels import ops

        if backend == "codebook":
            return ops.codebook_matmul(xl, wl, codebook)
        return _lut_matmul(xl, wl, codebook, spec, table)

    if kind == "col" and N % tp == 0:
        _count_route("col")
        f = shard_map(kernel, mesh=mesh,
                      in_specs=(P(None, None), P(None, "model")),
                      out_specs=P(None, "model"), check_vma=False)
        return f(x2, w_idx)

    if kind == "row" and K % tp == 0:
        _count_route("row")
        if backend == "lut":
            def body(xl, wl):
                # psum the int32 accumulator, decode the scale once after:
                # integer addition is associative, so the sharded reduction
                # is bit-identical to the single-device contraction (the
                # replicated table means every shard gathers identical
                # entries; the full-fan-in scale stays safe per K/tp slice)
                acc = jax.lax.psum(_lut_acc(xl, wl, codebook, spec, table),
                                   "model")
                return acc.astype(jnp.float32) * (spec.da / (2.0 ** spec.s))
        else:
            def body(xl, wl):
                return jax.lax.psum(kernel(xl, wl), "model")
        f = shard_map(body, mesh=mesh,
                      in_specs=(P(None, "model"), P("model", None)),
                      out_specs=P(None, None), check_vma=False)
        return f(x2, w_idx)

    # replicated fallback (axis does not divide tp, or unannotated site)
    _count_route("replicated")
    f = shard_map(kernel, mesh=mesh,
                  in_specs=(P(None, None), P(None, None)),
                  out_specs=P(None, None), check_vma=False)
    return f(x2, w_idx)


def _lut_acc(x2, w_idx, codebook, spec: LutSpec, table=None):
    """The §4 integer accumulator: snap activations to the level grid,
    gather M[a_idx·C + w_idx], sum in int32 (no decode).

    ``table`` is the precomputed constant of a real deployment
    (``attach_lut_tables`` hangs it off the params).  When absent it is
    rebuilt here from the codebook — same ``build_lut_table`` recipe, so
    the accumulators are bit-identical either way, but the rebuild sits
    inside the layer scan and costs |A|·|W| rints per layer per step.
    """
    from repro.kernels import ops

    da = spec.da
    a_idx = jnp.clip(jnp.round((x2.astype(jnp.float32) - spec.a_min) / da),
                     0, spec.levels - 1).astype(jnp.int32)
    if table is None:
        table = build_lut_table(codebook, spec)              # (|A|, |W|)
    return ops.lut_matmul(a_idx, w_idx, table)


def _lut_matmul(x2, w_idx, codebook, spec: LutSpec, table=None):
    """Faithful §4 contraction: int32 accumulate, decode once at the end."""
    acc = _lut_acc(x2, w_idx, codebook, spec, table)
    return acc.astype(jnp.float32) * (spec.da / (2.0 ** spec.s))
