"""Batched serving engine: jitted prefill, jitted decode loop, continuous
batching, and the multiply-free matmul backends (DESIGN.md §3).

The three pieces the seed engine lacked, now the hot path:

* **Prefill** consumes the whole (right-padded) prompt batch in ONE jitted
  call — ``transformer.prefill`` with ``batch['lengths']`` returns each
  row's logits at its last real position and a (B,) ``cache['pos']``
  vector.  Prompt lengths are bucketed to powers of two to bound
  recompiles.
* **Decode** is a ``lax.while_loop`` over ``decode_step`` with greedy /
  temperature sampling *inside* the loop: steady-state decode never
  re-enters Python per token and never syncs to the host.  Per-request
  stop lengths retire rows in place (retired rows lockstep-decode into
  their own clamped cache slot until the loop exits — wasted FLOPs, zero
  correctness impact, no recompile).
* **Continuous batching** (``serve``): the batch dimension is a pool of
  ``max_batch`` slots.  Each request prefills alone (per-bucket compile),
  is spliced into a free slot's cache rows at its own position offset, and
  decodes in lockstep with whatever else is in flight.  The decode loop
  runs with ``stop_on_event=True`` — it exits exactly when some request
  hits its stop length, Python harvests the finished slot, admits the next
  queued request into it (slot reuse == cache eviction: the newcomer's
  prefill overwrites the retiree's rows, and the per-slot ``pos``/valid
  length guarantee no cross-request attention leakage), and re-enters the
  loop.  Python runs O(#requests) times, not O(#tokens).

Backends (``backend=``, routed through ``kernels.dispatch`` at trace time):
``dense`` — gather + XLA dot (default); ``codebook`` — Pallas
``codebook_matmul`` (narrow indices in HBM, dequantize-in-VMEM); ``lut`` —
the paper's faithful §4 integer engine (``lut_matmul``; no multiplications
in the contraction).  ``codebook``/``lut`` require index-form params
(``serving.to_codebook_params``).  Engine families: KV-cache token LMs
(``dense``/``moe``); recurrent-state families would march their state
through the padding.

**Paged mode** (``paged=True``, DESIGN.md §8): ``serve`` swaps the dense
slab for a page pool (``serving.kvcache.PagePool``) — prompts stream
through page-sized prefill chunks (one compile shape, no bucket ladder),
decode runs against per-slot page tables, pages store int8 + scales
(``kv_dtype='int8'``), identical prompt prefixes share refcounted pages
(``prefix_cache``), and admission waits on free *pages* instead of free
slots.  ``generate`` stays contiguous — it is the equivalence reference
the paged path is tested against.

**Speculative mode** (``spec=SpecConfig(...)``, DESIGN.md §9): each decode
iteration becomes a *round* — a draft (parameter-free n-gram self-draft,
or a second model at a lower discretization tier under its own matmul
backend) proposes ``k`` tokens, the target scores all ``k+1`` positions in
one ``verify_step`` forward, and rejection sampling keeps the accepted
prefix plus one corrected/bonus token.  temperature=0 output is
token-for-token identical to baseline decode; temperature>0 output is
distributionally unbiased (and composes with ``top_k``/``top_p``).  The
contiguous spec loop is still a single ``lax.while_loop`` (the n-gram
draft is device-side); the paged spec path steps rounds from Python and
rolls rejected pages back through ``PagePool.truncate``/``extend``.

**Step-level scheduling API** (DESIGN.md §11): ``serve()`` owns its whole
request list; the ``sched_*`` / ``serve_step`` surface hands that control
flow to an external scheduler (``serving.scheduler.AsyncScheduler``)
instead — ``sched_state`` allocates the slot-pool state, ``sched_admit``
prefills one request into one slot, ``serve_step`` decodes a bounded
*quantum* of tokens per round (the SAME jitted while_loop, with per-round
stop lengths), and ``sched_swap_out``/``sched_swap_in`` move a preempted
request's KV state (contiguous slot rows, or its pool pages) to a
host-side ``SwapBlob`` and back, bit-exactly.  Requests arrive, wait,
stream, preempt, and resume — without this engine ever reading a clock.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import dispatch
from repro.kernels.paged_attention import pages_to_read
from repro.models.model_zoo import Model
from repro.serving import probes as nprobes
from repro.serving.kvcache import PagePool
from repro.serving.telemetry import NULL_TELEMETRY
from repro.serving.spec import (SpecConfig, SpecStats, filter_logits,
                                ngram_propose, ngram_propose_host,
                                spec_accept)

__all__ = ["ServeEngine", "SchedState", "SwapBlob"]

_ENGINE_FAMILIES = ("dense", "moe")


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def kv_page_counts(pos, page_table, page_size: int) -> dict:
    """Region args of one paged forward: ``kv_pages_read``, the pages the
    paged attention reads (each slot's pages below its ``pos``, cut at its
    first trash id, so none for a retired all-zero row), against
    ``kv_pages_table``, the B·P pages of the whole table."""
    pt = np.asarray(page_table)
    read = pages_to_read(pt, np.asarray(pos), page_size)
    return {"kv_pages_read": int(read.sum()), "kv_pages_table": int(pt.size)}


def _splice_rows(buf, toks, start, m):
    """buf[b, start[b] + i] = toks[b, i] for i < m[b] — per-row variable-
    length append, expressed as a full-row select (no scatter: clipped
    duplicate column indices would have undefined write order)."""
    B, W = buf.shape
    rel = jnp.arange(W)[None] - start[:, None]               # (B, W)
    pick = (rel >= 0) & (rel < m[:, None])
    vals = jnp.take_along_axis(toks, jnp.clip(rel, 0, toks.shape[1] - 1),
                               axis=1)
    return jnp.where(pick, vals, buf)


def _index_form_stats(params):
    """(found_any, max fan-in over w_idx leaves, concatenated codebooks).

    Every codebook leaf is gathered (per_layer scope has one per tensor) so
    the LUT scale is chosen against the global max|w| — the no-overflow
    guarantee must hold for the worst layer, not the first one visited.
    """
    fan_in, books = 0, []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name == "w_idx" and leaf.ndim >= 2:
            fan_in = max(fan_in, int(leaf.shape[-2]))
        if name == "codebook":
            books.append(np.asarray(leaf[0] if leaf.ndim == 2 else leaf))
    book = np.concatenate(books) if books else None
    return fan_in > 0, fan_in, book


@dataclasses.dataclass
class SchedState:
    """Mutable slot-pool state for the step-level scheduling API
    (DESIGN.md §11).  One per scheduler session; every field is reassigned
    by the engine's ``sched_*`` calls — callers treat it as opaque.

    The per-slot vectors are HOST numpy mirrors of the ``serve()`` loop's
    carry: admissions/releases/swaps touch one slot at a time, and plain
    indexed writes are free where eager device scatters cost a dispatch
    each (the fleet path admits 100k+ requests per trace).  The jitted
    decode loop converts them on entry; ``serve_step`` writes the round's
    results back.  Contiguous engines own ``cache`` (device KV slab +
    per-slot pos); paged engines own the page-table mirror ``pt_np``, the
    per-slot ``pos`` vector, and the per-slot ``Admission`` handles (the
    pool itself lives on the engine)."""

    live: object                     # (B,) np.bool_ — slot occupied
    last: object                     # (B,) int32 — last sampled token
    n_gen: object                    # (B,) int32 — tokens emitted
    stops: object                    # (B,) int32 — per-slot stop length
    out: object                      # (B, max_len) int32 — emission buffer
    key: object                      # PRNG carry (temperature > 0)
    cache: dict | None = None        # contiguous KV cache (with (B,) pos)
    pt_np: object | None = None      # paged (B, P) page-table host mirror
    pos: object | None = None        # paged per-slot positions (host)
    adm: list | None = None          # paged per-slot Admission handles


@dataclasses.dataclass
class SwapBlob:
    """Host-side image of one preempted request's serving state — what
    ``sched_swap_out`` extracts and ``sched_swap_in`` restores, verbatim
    (restoration is bit-exact: no recompute, no re-quantization).

    ``data`` maps cache plane names to host arrays: the request's live
    pages ``(L, n_pages, page, ...)`` in paged mode, its slot's cache rows
    ``(L, pos, ...)`` in contiguous mode.  ``reserve`` is the paged
    admission-time page reservation ``swap_in`` must re-claim."""

    paged: bool
    pos: int                         # tokens whose K/V are written
    stop: int                        # request stop length
    n_gen: int                       # tokens emitted so far
    last: int                        # last sampled token
    reserve: int                     # paged page reservation to re-claim
    n_pages: int                     # pages of real data (swap-cost unit)
    out_row: object                  # emitted tokens (out-buffer prefix)
    data: dict                       # plane name -> host array


@dataclasses.dataclass
class ServeEngine:
    """Continuous-batching inference engine over one model + param set.

    max_batch:   slot-pool width for ``serve`` (``generate`` sizes its own
                 batch).
    max_len:     cache capacity; prompt_len + max_new must fit.
    temperature: 0 = greedy argmax; >0 = categorical sampling.
    mesh:        a ``('data', 'model')`` mesh turns the engine tensor-
                 parallel (DESIGN.md §10): weights column/row-shard over
                 `model` (index-form params shard only their integer
                 indices), the KV cache — contiguous slab or page pool —
                 shards its sequence/in-page axis, and prefill, the decode
                 while_loop, and spec verify rounds all stay jitted under
                 the mesh.  Requires ``max_len % tp == 0`` (paged:
                 ``page_size % tp == 0``).  tp=N output is token-for-token
                 identical to the mesh-less engine (tests/test_tp_serve.py).
    backend:     'dense' | 'codebook' | 'lut' (see module docstring).
    lut_levels / lut_range: activation grid of the 'lut' backend's
                 multiplication table (|A| entries over [a_min, a_max]).
    paged:       serve() through the paged KV cache (DESIGN.md §8): chunked
                 prefill, per-slot page tables, admission gated on free
                 *pages* rather than free slots.  generate() stays on the
                 contiguous slab (the paged-equivalence reference).
    page_size:   tokens per page (paged mode).
    kv_dtype:    'bf16' — pages in the model's cache float dtype (f32 for
                 f32 models, matching the contiguous slab); 'int8' —
                 quantized pages + per-token-per-head scales.
    prefix_cache: content-addressed sharing of full prompt pages across
                 requests (and serve() calls — the pool persists on the
                 engine).
    n_pages:     global pool size; 0 → 1 trash page + max_batch × ⌈max_len /
                 page_size⌉ (capacity parity with the contiguous slab).
    top_k/top_p: sampling filters (temperature > 0 only): keep the k
                 highest logits / the smallest nucleus whose mass reaches
                 p.  Rejection sampling in spec mode composes with the
                 SAME filtered distribution, so speculation stays unbiased.
    spec:        a ``serving.spec.SpecConfig`` enables speculative decoding
                 for ``serve()`` (DESIGN.md §9); ``generate()`` stays
                 baseline — it is the parity reference spec mode is tested
                 against.  ``spec_stats`` accumulates acceptance counters.
    """

    model: Model
    params: object
    max_len: int = 256
    temperature: float = 0.0
    mesh: object = None
    backend: str = "dense"
    max_batch: int = 8
    lut_levels: int = 4096
    lut_range: tuple = (-16.0, 16.0)
    paged: bool = False
    page_size: int = 16
    kv_dtype: str = "bf16"
    prefix_cache: bool = True
    n_pages: int = 0
    top_k: int = 0                 # 0 = off; sampling only (greedy is argmax)
    top_p: float = 1.0             # 1.0 = off; nucleus filtering
    spec: SpecConfig | None = None  # speculative decoding (DESIGN.md §9)
    telemetry: object = None       # serving.telemetry registry (§13); None
    #                                normalizes to the zero-cost null object
    probes: bool = False           # in-graph numerics probes (§14): thread
    #                                per-layer discretization-health counters
    #                                through prefill + the decode while_loop

    def __post_init__(self):
        if self.telemetry is None:
            self.telemetry = NULL_TELEMETRY
        cfg = self.model.cfg
        if cfg.family not in _ENGINE_FAMILIES:
            raise NotImplementedError(
                f"ServeEngine serves KV-cache token LMs {_ENGINE_FAMILIES}; "
                f"got family {cfg.family!r}")
        if self.backend not in dispatch.BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in "
                             f"{dispatch.BACKENDS}")
        has_idx, fan_in, book = _index_form_stats(self.params)
        self._lut_spec = None
        if self.backend != "dense":
            if not has_idx:
                raise ValueError(
                    f"backend {self.backend!r} needs codebook-index params "
                    "(run serving.to_codebook_params first)")
            if self.backend == "lut":
                self._lut_spec = dispatch.make_lut_spec(
                    book, fan_in, levels=self.lut_levels,
                    a_range=self.lut_range)
                # precompute the §4 tables once (DESIGN.md §12): without
                # this every scanned layer re-derives the |A|×|W| table
                # inside every decode step; with it the table is a plain
                # (replicated) param leaf the kernels gather from
                self.params = dispatch.attach_lut_tables(self.params,
                                                         self._lut_spec)
        self._cache_dtype = (jnp.float32 if cfg.dtype == "float32"
                             else jnp.bfloat16)

        if self.mesh is not None:
            if "model" not in self.mesh.axis_names:
                raise ValueError("ServeEngine mesh needs a 'model' axis "
                                 "(launch.mesh.make_local_mesh)")
            tp = self.mesh.shape["model"]
            if self.max_len % tp:
                raise ValueError(
                    f"max_len {self.max_len} must be a multiple of the TP "
                    f"degree {tp} (the cache shards its sequence axis over "
                    "`model`)")
            if self.paged and self.page_size % tp:
                raise ValueError(
                    f"page_size {self.page_size} must be a multiple of the "
                    f"TP degree {tp} (each shard owns an S-slice of every "
                    "page)")
            if self.spec is not None and self.max_len // tp < self.spec.k + 1:
                raise ValueError(
                    f"max_len/tp = {self.max_len // tp} cannot hold the "
                    f"k+1 = {self.spec.k + 1} verify rows of one shard")
            self.params = self._shard_params(self.params)

        bb = partial(dispatch.bind_backend, name=self.backend,
                     lut_spec=self._lut_spec, mesh=self.mesh)
        self._prefill = jax.jit(bb(self._prefill_fn))
        # the cache operand is donated everywhere it is threaded through:
        # callers always reassign from the result, and without donation XLA
        # copies the full pool/slab per call (per 16-token prefill chunk in
        # paged mode — O(pool) bandwidth for a one-page update)
        self._decode_loop = jax.jit(bb(self._loop_fn),
                                    static_argnames=("stop_on_event",),
                                    donate_argnums=(1,))
        self._admit = jax.jit(self._admit_fn,       # pure memory traffic
                              donate_argnums=(0,))
        self._grow = jax.jit(self._grow_fn)
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype {self.kv_dtype!r} not in "
                             "('bf16', 'int8')")
        self._prefill_chunk = jax.jit(bb(self._prefill_chunk_fn),
                                      donate_argnums=(1,))
        self._pool: PagePool | None = None
        # step-level scheduling API (DESIGN.md §11): fixed-shape swap
        # movers — page-axis gather/scatter for the pool, a whole-slot
        # row splice for the contiguous slab — so preemption never grows
        # the compile cache past one program each
        self._gather_pages = jax.jit(lambda cache, pids: {
            k: jnp.take(v, pids, axis=1) for k, v in cache.items()})
        self._scatter_pages = jax.jit(self._scatter_pages_fn,
                                      donate_argnums=(0,))
        self._gather_rows = jax.jit(lambda kv, slot: {
            k: jax.lax.dynamic_index_in_dim(v, slot, axis=1, keepdims=False)
            for k, v in kv.items()})
        self._restore_slot = jax.jit(self._splice, donate_argnums=(0,))
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")

        # --- numerics probes (DESIGN.md §14) ---------------------------------
        self._ps = {}
        self._probe_audit = {}
        if self.probes:
            if self.spec is not None:
                raise NotImplementedError(
                    "numerics probes instrument the plain decode loops; "
                    "speculative serve() is not instrumented — build the "
                    "engine with probes=False or spec=None")
            self._ps = nprobes.init_state(cfg.n_layers)
            # w_idx is immutable at runtime: audit the clip-canonicalized
            # index ids once on the host instead of per decode step
            self._probe_audit = nprobes.static_index_audit(self.params)

        # --- speculative decoding (DESIGN.md §9) -----------------------------
        self.spec_stats = SpecStats()
        self._draft_bs = None
        if self.spec is not None:
            sp = self.spec
            if sp.draft not in ("ngram", "model"):
                raise ValueError(f"spec.draft {sp.draft!r} not in "
                                 "('ngram', 'model')")
            if sp.k < 1:
                raise ValueError(f"spec.k must be >= 1, got {sp.k}")
            if sp.draft == "model":
                if sp.draft_params is None:
                    raise ValueError("spec.draft='model' needs "
                                     "spec.draft_params")
                dhas, dfan, dbook = _index_form_stats(sp.draft_params)
                dlut = None
                if sp.draft_backend not in dispatch.BACKENDS:
                    raise ValueError(f"draft backend {sp.draft_backend!r} "
                                     f"not in {dispatch.BACKENDS}")
                if sp.draft_backend != "dense":
                    if not dhas:
                        raise ValueError(
                            f"draft backend {sp.draft_backend!r} needs "
                            "codebook-index draft_params")
                    if sp.draft_backend == "lut":
                        dlut = dispatch.make_lut_spec(
                            dbook, dfan, levels=sp.lut_levels,
                            a_range=sp.lut_range)
                        # same table precompute for the draft tier
                        self.spec = sp = dataclasses.replace(
                            sp, draft_params=dispatch.attach_lut_tables(
                                sp.draft_params, dlut))
                self._draft_bs = dispatch.BackendSpec(sp.draft_backend, dlut)
                self._draft_prefill = jax.jit(dispatch.bind_backend(
                    self._prefill_fn, name=sp.draft_backend, lut_spec=dlut))
                self._draft_propose_j = jax.jit(self._draft_propose,
                                                donate_argnums=(1,))
            # contiguous spec decode: one while_loop, k+1 tokens per round
            self._spec_loop = jax.jit(bb(self._spec_loop_fn),
                                      static_argnames=("stop_on_event",),
                                      donate_argnums=(2, 3, 4))
            self._admit_kv = jax.jit(self._admit_kv_fn, donate_argnums=(0,))
            # paged spec decode: Python-stepped rounds
            self._verify = jax.jit(bb(self._verify_fn), donate_argnums=(1,))
            self._accept = jax.jit(self._accept_fn)

    # --- tensor parallelism (DESIGN.md §10) ----------------------------------

    def _shard_params(self, params):
        """Place params per the serving TP policy: block matmuls ('w' or the
        integer 'w_idx') column/row-sharded over `model`, everything else —
        embeddings, norms, codebooks, LUT inputs — replicated."""
        from repro.distributed import sharding as SH

        specs = SH.serve_param_specs(params)
        sh = jax.tree_util.tree_map(
            lambda s: SH.named(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(params, sh)

    def _place_kv(self, cache):
        """Shard a contiguous cache's KV planes (L, B, S, KV[, hd]):
        sequence over `model`, batch over data when it divides (§5)."""
        if self.mesh is None:
            return cache
        from repro.distributed.sharding import dp_axes, named

        dp = dp_axes(self.mesh)
        dsz = 1
        for a in dp:
            dsz *= self.mesh.shape[a]
        kv = {}
        for name, arr in cache["kv"].items():
            b_ax = dp if arr.shape[1] % dsz == 0 else None
            spec = P(None, b_ax, "model", *([None] * (arr.ndim - 3)))
            kv[name] = jax.device_put(arr, named(self.mesh, spec))
        return {**cache, "kv": kv}

    def _place_pool(self, cache):
        """Shard page-pool arrays (L, n_pages, page, KV[, hd]): the in-page
        token axis over `model` — every shard owns an S-slice of every
        page, so page tables and allocator decisions stay shard-invariant
        (DESIGN.md §10)."""
        if self.mesh is None:
            return cache
        from repro.distributed.sharding import named

        return {name: jax.device_put(
                    arr, named(self.mesh,
                               P(None, None, "model",
                                 *([None] * (arr.ndim - 3)))))
                for name, arr in cache.items()}

    # --- jitted bodies -------------------------------------------------------

    def _prefill_fn(self, params, tokens, lengths, ps=None):
        batch = {"tokens": tokens, "lengths": lengths}
        if ps:
            batch["probes"] = ps     # probe counters ride the batch pytree
        return self.model.prefill(params, batch, self.mesh)

    # --- numerics probes (DESIGN.md §14) -------------------------------------
    #
    # The engine owns ONE accumulated probe state (`self._ps`).  Every jitted
    # call that should collect gets the state injected into its cache operand
    # immediately before the call and harvested immediately after — no pool /
    # slot cache ever *persists* a "probes" key, so swap blobs, prefix pages,
    # and admission splices are untouched.  The decode loops donate their
    # cache operand, hence the strict reassign-from-result discipline.

    def _ps_inject(self, cache):
        if self.probes:
            cache = {**cache, "probes": self._ps}
        return cache

    def _ps_extract(self, cache):
        if self.probes and "probes" in cache:
            self._ps = cache.pop("probes")
        return cache

    def numerics(self) -> dict:
        """Canonical numerics snapshot: per-layer saturation/headroom/KV
        error + the static index audit (empty when probes are off).  This
        is the telemetry 'numerics' provider."""
        if not self.probes:
            return {}
        return nprobes.summarize(self._ps, audit=self._probe_audit,
                                 backend=self.backend)

    def reset_probes(self) -> None:
        """Zero the accumulated counters (fresh measurement window)."""
        if self.probes:
            self._ps = nprobes.init_state(self.model.cfg.n_layers)

    def _sample(self, logits, key):
        """Greedy argmax, or temperature sampling through the top-k / top-p
        filters (filtering is a no-op for argmax: the max always survives).
        """
        with jax.named_scope("sample"):
            lg = logits[:, -1, :self.model.cfg.vocab].astype(jnp.float32)
            if self.temperature > 0:
                lg = filter_logits(lg / self.temperature, self.top_k,
                                   self.top_p)
                return jax.random.categorical(key, lg).astype(jnp.int32)
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def _grow_fn(self, cache):
        """Pad prefill-emitted KV planes (S = prompt bucket) to max_len."""
        kv = {k: jnp.pad(v, [(0, 0), (0, 0), (0, self.max_len - v.shape[2])]
                         + [(0, 0)] * (v.ndim - 3))
              for k, v in cache["kv"].items()}
        return {**cache, "kv": kv}

    def _loop_fn(self, params, cache, last, active, n_gen, stops, out, key,
                 *, stop_on_event: bool):
        """while_loop decode: one iteration == one token for every slot.

        Exits when all slots are retired, the out-buffer width is exhausted,
        or (stop_on_event) the first time any slot hits its stop length —
        the continuous-batching admission point.
        """
        B, cap = out.shape

        def cond(c):
            _, _, active, _, _, _, _, steps, event = c
            go = jnp.any(active) & (steps < cap)
            if stop_on_event:
                go = go & ~event
            return go

        def body(c):
            cache, last, active, n_gen, stops, out, key, steps, _ = c
            logits, cache = self.model.decode(params, last[:, None], cache,
                                              self.mesh)
            key, sub = jax.random.split(key)
            nxt = jnp.where(active, self._sample(logits, sub), last)
            col = jnp.clip(n_gen, 0, cap - 1)
            cur = out[jnp.arange(B), col]
            out = out.at[jnp.arange(B), col].set(jnp.where(active, nxt, cur))
            n_gen = n_gen + active.astype(jnp.int32)
            newly = active & (n_gen >= stops)
            return (cache, nxt, active & ~newly, n_gen, stops, out, key,
                    steps + 1, jnp.any(newly))

        c = (cache, last, active, n_gen, stops, out, key,
             jnp.zeros((), jnp.int32), jnp.asarray(False))
        c = jax.lax.while_loop(cond, body, c)
        return c[0], c[1], c[2], c[3], c[5], c[6]   # cache,last,active,n_gen,out,key

    @staticmethod
    def _splice(cache, c1, slot):
        """Copy a batch-1 prefill cache into slot ``slot`` of a pooled
        contiguous cache (KV planes + per-slot pos)."""
        kv = dict(cache["kv"])
        for k, src in c1["kv"].items():
            start = (0, slot) + (0,) * (src.ndim - 2)
            kv[k] = jax.lax.dynamic_update_slice(
                cache["kv"][k], src.astype(cache["kv"][k].dtype), start)
        pos = cache["pos"].at[slot].set(c1["pos"][0])
        return {**cache, "kv": kv, "pos": pos}

    def _admit_fn(self, cache, c1, slot, first_tok, stop,
                  last, active, n_gen, stops, out):
        """Splice a freshly prefilled request (batch 1) into slot ``slot``.

        The newcomer's KV rows overwrite the retired occupant's prefix; its
        (smaller) ``pos`` plus the decode-time valid-length mask evict
        whatever stale suffix remains without touching it.
        """
        cache = self._splice(cache, c1, slot)
        row = jnp.zeros((out.shape[1],), out.dtype).at[0].set(first_tok)
        return (cache,
                last.at[slot].set(first_tok),
                # the prefill sample already produced token #1: a stop of 1
                # is done on arrival
                active.at[slot].set(stop > 1),
                n_gen.at[slot].set(1),
                stops.at[slot].set(stop),
                out.at[slot].set(row))

    def _admit_kv_fn(self, cache, c1, slot):
        """KV-only admission splice (the draft model's cache in spec mode —
        the engine-side state updates already happened on the target)."""
        return self._splice(cache, c1, slot)

    # --- speculative decoding (DESIGN.md §9) ---------------------------------

    def _draft_propose(self, dparams, dcache, last, key):
        """k autoregressive draft steps under the draft's OWN backend scope
        (it nests inside the target's — dispatch.BackendSpec).

        Returns (proposals (B, k), q_dist (B, k, V) | None, dcache).  The
        scan runs k+1 steps — the extra step writes the LAST proposal's K/V
        (its own sampled token is discarded), so after a fully-accepted
        round the draft cache is valid for every emitted token and the
        caller's rollback (``dcache['pos'] = accepted length``) never
        exposes an unwritten row.  Rejected draft rows become stale tail
        entries fenced by the valid-length mask, exactly like the target's.
        """
        sp = self.spec
        vocab = self.model.cfg.vocab
        with self._draft_bs.scope():
            def step(carry, k_i):
                dc, tok = carry
                logits, dc = self.model.decode(dparams, tok[:, None], dc,
                                               None)
                lg = logits[:, -1, :vocab].astype(jnp.float32)
                if self.temperature > 0:
                    lg = filter_logits(lg / self.temperature, self.top_k,
                                       self.top_p)
                    nxt = jax.random.categorical(k_i, lg).astype(jnp.int32)
                    dist = jax.nn.softmax(lg, axis=-1)
                else:
                    nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    dist = jnp.zeros((), jnp.float32)   # unused at T=0
                return (dc, nxt), (nxt, dist)

            keys = jax.random.split(key, sp.k + 1)
            (dcache, _), (toks, dists) = jax.lax.scan(
                step, (dcache, last), keys)
        q_dist = (dists[:sp.k].transpose(1, 0, 2)
                  if self.temperature > 0 else None)
        return toks[:sp.k].T, q_dist, dcache

    def _accept_fn(self, logits, d_toks, q_dist, key):
        lg = logits[..., :self.model.cfg.vocab].astype(jnp.float32)
        return spec_accept(lg, d_toks, q_dist, key,
                           temperature=self.temperature,
                           top_k=self.top_k, top_p=self.top_p)

    def _verify_fn(self, params, cache, tokens):
        return self.model.verify(params, tokens, cache, self.mesh)

    def _spec_loop_fn(self, params, dparams, cache, dcache, ctx, ctx_len,
                      last, active, n_gen, stops, out, key, *,
                      stop_on_event: bool):
        """while_loop speculative decode: one iteration == one ROUND — k
        draft proposals, one k+1-token verify forward, rejection sampling —
        emitting 1..k+1 tokens per active slot.

        ctx (B, C) / ctx_len (B,) hold each slot's full token history
        (prompt + emitted): the n-gram self-draft reads it ON DEVICE, so
        Python is still re-entered only O(#requests) times.  Rollback is
        ``pos += emitted`` (< k+1 on rejection): the rejected suffix stays
        as stale cache rows above pos, fenced by the next round's
        valid-length mask.
        """
        sp = self.spec
        K, K1 = sp.k, sp.k + 1
        B, cap = out.shape

        def cond(c):
            active, steps, event = c[7], c[10], c[11]
            go = jnp.any(active) & (steps < cap)
            if stop_on_event:
                go = go & ~event
            return go

        def body(c):
            (cache, dcache, ctx, ctx_len, last, n_gen, stops, active, out,
             key, steps, _ev, stt) = c
            key, kd, ka = jax.random.split(key, 3)
            if sp.draft == "ngram":
                d_toks, q_dist = ngram_propose(
                    ctx, ctx_len, k=K, n=sp.ngram), None
            else:
                d_toks, q_dist, dcache = self._draft_propose(
                    dparams, dcache, last, kd)
            tokens = jnp.concatenate([last[:, None], d_toks], axis=1)
            logits, cache = self.model.verify(params, tokens, cache,
                                              self.mesh)
            n_acc, toks = self._accept_fn(logits, d_toks, q_dist, ka)
            remaining = jnp.maximum(stops - n_gen, 0)
            m = jnp.where(active, jnp.minimum(n_acc + 1, remaining), 0)
            # full-row emission splice (a scatter at clipped columns would
            # collide at the buffer edge; duplicate-index order is undefined)
            out = _splice_rows(out, toks, n_gen, m)
            ctx = _splice_rows(ctx, toks, ctx_len, m)
            last = jnp.where(
                active, toks[jnp.arange(B), jnp.maximum(m - 1, 0)], last)
            cache = {**cache, "pos": cache["pos"] + m}
            ctx_len = ctx_len + m
            n_gen = n_gen + m
            if sp.draft == "model":
                # draft rollback: its cache is valid for every emitted token
                # except the pending last (which it has not seen)
                dcache = {**dcache, "pos": jnp.maximum(ctx_len - 1, 0)}
            newly = active & (n_gen >= stops)
            n_act = jnp.any(active).astype(jnp.int32)
            stt = (stt[0] + n_act,                              # rounds
                   stt[1] + jnp.sum(jnp.where(active, K, 0)),   # proposed
                   stt[2] + jnp.sum(jnp.minimum(n_acc, m)),     # accepted
                   stt[3] + jnp.sum(m))                         # emitted
            return (cache, dcache, ctx, ctx_len, last, n_gen, stops,
                    active & ~newly, out, key, steps + 1, jnp.any(newly),
                    stt)

        z = jnp.zeros((), jnp.int32)
        c = (cache, dcache, ctx, ctx_len, last, n_gen, stops, active, out,
             key, z, jnp.asarray(False), (z, z, z, z))
        c = jax.lax.while_loop(cond, body, c)
        # cache,dcache,ctx,ctx_len,last,n_gen,active,out,key,stats
        return (c[0], c[1], c[2], c[3], c[4], c[5], c[7], c[8], c[9], c[12])

    def _serve_spec(self, prompts, stops_req, key):
        """Continuous batching with speculative rounds (contiguous cache):
        the baseline serve() skeleton, with the while_loop swapped for
        ``_spec_loop`` and a per-slot context buffer feeding the draft."""
        sp = self.spec
        n = len(prompts)
        B, cap, C = self.max_batch, max(stops_req), self.max_len

        cache = self._place_kv(self.model.init_cache(
            B, self.max_len, dtype=self._cache_dtype))
        cache = {**cache, "pos": jnp.zeros((B,), jnp.int32)}
        if sp.draft == "model":
            dparams = sp.draft_params
            dcache = self.model.init_cache(B, self.max_len,
                                           dtype=self._cache_dtype)
            dcache = {**dcache, "pos": jnp.zeros((B,), jnp.int32)}
        else:
            dparams = dcache = jnp.zeros((), jnp.int32)
        ctx = jnp.zeros((B, C), jnp.int32)
        ctx_len = jnp.zeros((B,), jnp.int32)
        last = jnp.zeros((B,), jnp.int32)
        active = jnp.zeros((B,), bool)
        n_gen = jnp.zeros((B,), jnp.int32)
        stops = jnp.ones((B,), jnp.int32)
        out = jnp.zeros((B, cap), jnp.int32)

        queue = deque(range(n))
        slot_rid: list[int | None] = [None] * B
        results: dict[int, list[int]] = {}

        while queue or any(r is not None for r in slot_rid):
            for b in [b for b in range(B) if slot_rid[b] is None]:
                if not queue:
                    break
                rid = queue.popleft()
                plen = len(prompts[rid])
                toks1, len1 = self._pad_prompts([prompts[rid]])
                lg1, c1 = self._prefill(self.params, toks1, len1)
                key, sub = jax.random.split(key)
                first = self._sample(lg1, sub)
                cache, last, active, n_gen, stops, out = self._admit(
                    cache, c1, b, first[0], stops_req[rid],
                    last, active, n_gen, stops, out)
                if sp.draft == "model":
                    _, dc1 = self._draft_prefill(dparams, toks1, len1)
                    dcache = self._admit_kv(dcache, dc1, b)
                row = np.zeros((C,), np.int32)
                row[:plen] = prompts[rid]
                row[plen] = int(first[0])
                ctx = ctx.at[b].set(jnp.asarray(row))
                ctx_len = ctx_len.at[b].set(plen + 1)
                slot_rid[b] = rid
            (cache, dcache, ctx, ctx_len, last, n_gen, active, out, key,
             stt) = self._spec_loop(
                self.params, dparams, cache, dcache, ctx, ctx_len, last,
                active, n_gen, stops, out, key, stop_on_event=True)
            self.spec_stats.add(*(int(s) for s in stt))
            act, gen = np.asarray(active), np.asarray(n_gen)
            out_np = np.asarray(out)
            for b in range(B):
                rid = slot_rid[b]
                if rid is not None and not act[b]:
                    results[rid] = (list(prompts[rid])
                                    + out_np[b, :gen[b]].tolist())
                    slot_rid[b] = None
        return [results[i] for i in range(n)]

    # --- paged path (DESIGN.md §8) -------------------------------------------

    def _prefill_chunk_fn(self, params, cache, tokens, page_row, start,
                          length, write_pid):
        return self.model.prefill_chunk(
            params, {"tokens": tokens, "start": start, "length": length,
                     "page_row": page_row, "write_pid": write_pid},
            cache, self.mesh)

    @property
    def pool(self) -> PagePool:
        """The engine's page pool (created lazily; persists across serve()
        calls so the prefix cache keeps earning hits)."""
        if self._pool is None:
            pps = -(-self.max_len // self.page_size)
            n_pages = self.n_pages or 1 + self.max_batch * pps
            dtype = (jnp.int8 if self.kv_dtype == "int8"
                     else self._cache_dtype)
            self._pool = PagePool(
                self.model, n_pages=n_pages, page_size=self.page_size,
                pages_per_slot=pps, kv_dtype=dtype,
                prefix_cache=self.prefix_cache)
            self._pool.cache = self._place_pool(self._pool.cache)
        return self._pool

    def dense_cache_bytes(self) -> int:
        """HBM bytes of the PR 1 contiguous slab at this engine's shape —
        the baseline the paged pool is compared against."""
        cache = jax.eval_shape(lambda: self.model.init_cache(
            self.max_batch, self.max_len, dtype=self._cache_dtype))
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(cache["kv"]))

    def _chunked_prefill(self, pool, prompt, adm):
        """Stream one admitted prompt through page-sized chunks; returns the
        logits of the last real position (chunk compiles once: every call
        is the same (1, page) shape)."""
        page = self.page_size
        plen = len(prompt)
        row_np = np.asarray(adm.pids + [0] * (pool.pages_per_slot
                                              - len(adm.pids)), np.int32)
        row = jnp.asarray(row_np)
        logits = None
        cache = self._ps_inject(pool.cache)
        for ci, c in enumerate(range(adm.compute_from, adm.n_chunks)):
            args = (kv_page_counts([c * page], row_np[None], page)
                    if self.telemetry.enabled else {})
            with self.telemetry.region("engine.prefill_chunk", **args):
                toks = np.zeros((1, page), np.int32)
                chunk = prompt[c * page:(c + 1) * page]
                toks[0, :len(chunk)] = chunk
                logits, cache = self._prefill_chunk(
                    self.params, cache, jnp.asarray(toks), row,
                    np.int32(c * page), np.int32(len(chunk)),
                    np.int32(adm.write_pids[ci]))
        pool.cache = self._ps_extract(cache)
        return logits

    def _paged_admit(self, prompt, stop, key):
        """One request's paged admission sequence — pool admission,
        chunked prefill, prefix registration, CoW split, first-token
        sample — shared verbatim by serve() and the scheduler API so the
        two paths cannot drift.  Returns (adm, first_token, key), with
        adm None (key untouched) when the pool cannot admit yet."""
        pool = self.pool
        adm = pool.admit(list(prompt), stop)
        if adm is None:
            return None, None, key
        logits = self._chunked_prefill(pool, list(prompt), adm)
        pool.register_prefill(adm)
        pool.cow(adm)     # shared tail page → private before decode writes
        with self.telemetry.region("engine.first_token"):
            key, sub = jax.random.split(key)
            first = int(self._sample(logits, sub)[0])
        return adm, first, key

    def _serve_paged(self, prompts, stops_req, key):
        pool = self.pool
        page = self.page_size
        for p, s in zip(prompts, stops_req):
            if pool.pages_needed(len(p), s) > pool.usable_pages:
                raise ValueError(
                    f"request (prompt {len(p)} + {s} new) can never fit the "
                    f"{pool.usable_pages}-page pool")
        n = len(prompts)
        B, cap, P = self.max_batch, max(stops_req), pool.pages_per_slot

        pt_np = np.zeros((B, P), np.int32)            # all-trash rows
        pos = jnp.zeros((B,), jnp.int32)
        last = jnp.zeros((B,), jnp.int32)
        active = jnp.zeros((B,), bool)
        n_gen = jnp.zeros((B,), jnp.int32)
        stops = jnp.ones((B,), jnp.int32)
        out = jnp.zeros((B, cap), jnp.int32)

        queue = deque(range(n))
        slot_rid: list[int | None] = [None] * B
        slot_adm: list = [None] * B
        results: dict[int, list[int]] = {}

        while queue or any(r is not None for r in slot_rid):
            # admission: gated on free PAGES (a free slot with an
            # under-provisioned pool waits; retirement frees pages)
            for b in [b for b in range(B) if slot_rid[b] is None]:
                if not queue:
                    break
                rid = queue[0]
                adm, first, key = self._paged_admit(prompts[rid],
                                                    stops_req[rid], key)
                if adm is None:
                    break                              # wait for pages
                queue.popleft()
                pt_np[b] = 0
                pt_np[b, :len(adm.pids)] = adm.pids
                stop = stops_req[rid]
                pos = pos.at[b].set(len(prompts[rid]))
                last = last.at[b].set(first)
                active = active.at[b].set(stop > 1)
                n_gen = n_gen.at[b].set(1)
                stops = stops.at[b].set(stop)
                out = out.at[b].set(
                    jnp.zeros((cap,), out.dtype).at[0].set(first))
                slot_rid[b], slot_adm[b] = rid, adm
            if queue and all(r is None for r in slot_rid):
                raise RuntimeError(
                    "paged admission deadlock: no request in flight and the "
                    "pool cannot admit the next one")
            cache = self._ps_inject({**pool.cache,
                                     "page_table": jnp.asarray(pt_np),
                                     "pos": pos})
            cache, last, active, n_gen, out, key = self._decode_loop(
                self.params, cache, last, active, n_gen, stops, out, key,
                stop_on_event=True)
            pos = cache["pos"]
            cache = self._ps_extract(cache)
            pool.cache = {k: v for k, v in cache.items()
                          if k not in ("page_table", "pos")}
            act, gen = np.asarray(active), np.asarray(n_gen)
            out_np = np.asarray(out)
            for b in range(B):
                rid = slot_rid[b]
                if rid is not None and not act[b]:
                    results[rid] = (list(prompts[rid])
                                    + out_np[b, :gen[b]].tolist())
                    pool.retire(slot_adm[b])
                    pt_np[b] = 0                      # retired → trash page
                    pos = pos.at[b].set(0)
                    slot_rid[b], slot_adm[b] = None, None
        return [results[i] for i in range(n)]

    def _serve_paged_spec(self, prompts, stops_req, key):
        """Paged continuous batching with speculative rounds, stepped from
        Python: before each round every active slot ``extend``s its live
        pages to cover the speculative span (pos + k + 1), and after
        rejection sampling ``truncate`` returns the emptied tail pages to
        the pool — rejected speculation is not just masked out (the
        contiguous rollback), its pages stop existing.  The freed pages
        stay reserved for the request, so the next extend cannot deadlock
        (serving/kvcache.py).
        """
        sp = self.spec
        pool = self.pool
        for p, s in zip(prompts, stops_req):
            if pool.pages_needed(len(p), s) > pool.usable_pages:
                raise ValueError(
                    f"request (prompt {len(p)} + {s} new) can never fit the "
                    f"{pool.usable_pages}-page pool")
        n = len(prompts)
        B, cap, P = self.max_batch, max(stops_req), pool.pages_per_slot
        K, K1 = sp.k, sp.k + 1

        pt_np = np.zeros((B, P), np.int32)
        pos_np = np.zeros((B,), np.int64)
        last_np = np.zeros((B,), np.int64)
        act_np = np.zeros((B,), bool)
        gen_np = np.zeros((B,), np.int64)
        stop_np = np.ones((B,), np.int64)
        out_np = np.zeros((B, cap), np.int64)
        slot_ctx: list[list | None] = [None] * B
        if sp.draft == "model":
            dparams = sp.draft_params
            dcache = self.model.init_cache(B, self.max_len,
                                           dtype=self._cache_dtype)
            dcache = {**dcache, "pos": jnp.zeros((B,), jnp.int32)}

        queue = deque(range(n))
        slot_rid: list[int | None] = [None] * B
        slot_adm: list = [None] * B
        results: dict[int, list[int]] = {}

        def set_row(b):
            pt_np[b] = 0
            pids = slot_adm[b].pids
            pt_np[b, :len(pids)] = pids

        while queue or any(r is not None for r in slot_rid):
            for b in [b for b in range(B) if slot_rid[b] is None]:
                if not queue:
                    break
                rid = queue[0]
                adm, first, key = self._paged_admit(prompts[rid],
                                                    stops_req[rid], key)
                if adm is None:
                    break
                queue.popleft()
                plen = len(prompts[rid])
                slot_rid[b], slot_adm[b] = rid, adm
                # release the worst-case tail: rounds extend() it back
                # page-by-page as speculation actually needs it
                pool.truncate(adm, plen)
                set_row(b)
                pos_np[b], last_np[b] = plen, first
                act_np[b] = stops_req[rid] > 1
                gen_np[b], stop_np[b] = 1, stops_req[rid]
                out_np[b] = 0
                out_np[b, 0] = first
                slot_ctx[b] = list(prompts[rid]) + [first]
                if sp.draft == "model":
                    toks1, len1 = self._pad_prompts([prompts[rid]])
                    _, dc1 = self._draft_prefill(dparams, toks1, len1)
                    dcache = self._admit_kv(dcache, dc1, b)
            if queue and all(r is None for r in slot_rid):
                raise RuntimeError(
                    "paged admission deadlock: no request in flight and the "
                    "pool cannot admit the next one")

            if any(act_np[b] for b in range(B) if slot_rid[b] is not None):
                # --- one speculative round over the in-flight slots ----------
                for b in range(B):
                    if slot_rid[b] is not None and act_np[b]:
                        pool.extend(slot_adm[b], int(pos_np[b]) + K1)
                        set_row(b)
                last_dev = jnp.asarray(last_np, jnp.int32)
                if sp.draft == "ngram":
                    d_np = np.zeros((B, K), np.int64)
                    for b in range(B):
                        if slot_rid[b] is not None and act_np[b]:
                            d_np[b] = ngram_propose_host(
                                slot_ctx[b], k=K, n=sp.ngram)
                    d_toks, q_dist = jnp.asarray(d_np, jnp.int32), None
                else:
                    key, kd = jax.random.split(key)
                    d_toks, q_dist, dcache = self._draft_propose_j(
                        dparams, dcache, last_dev, kd)
                tokens = jnp.concatenate([last_dev[:, None], d_toks], axis=1)
                cache = {**pool.cache, "page_table": jnp.asarray(pt_np),
                         "pos": jnp.asarray(pos_np, jnp.int32)}
                logits, cache = self._verify(self.params, cache, tokens)
                pool.cache = {k: v for k, v in cache.items()
                              if k not in ("page_table", "pos")}
                key, ka = jax.random.split(key)
                n_acc, toks = self._accept(logits, d_toks, q_dist, ka)
                n_acc, toks = np.asarray(n_acc), np.asarray(toks)
                proposed = accepted = emitted = 0
                for b in range(B):
                    if slot_rid[b] is None or not act_np[b]:
                        continue
                    m = int(min(n_acc[b] + 1, stop_np[b] - gen_np[b]))
                    emit = toks[b, :m].tolist()
                    out_np[b, gen_np[b]:gen_np[b] + m] = emit
                    slot_ctx[b].extend(int(t) for t in emit)
                    pos_np[b] += m
                    gen_np[b] += m
                    last_np[b] = emit[-1]
                    proposed += K
                    accepted += min(int(n_acc[b]), m)
                    emitted += m
                    # rollback: emptied speculative tail pages go home
                    pool.truncate(slot_adm[b], int(pos_np[b]))
                    set_row(b)
                    if gen_np[b] >= stop_np[b]:
                        act_np[b] = False
                self.spec_stats.add(1, proposed, accepted, emitted)
                if sp.draft == "model":
                    dpos = np.array(
                        [len(slot_ctx[b]) - 1 if slot_ctx[b] else 0
                         for b in range(B)], np.int32)
                    dcache = {**dcache, "pos": jnp.asarray(dpos)}

            for b in range(B):
                rid = slot_rid[b]
                if rid is not None and not act_np[b]:
                    results[rid] = (list(prompts[rid])
                                    + out_np[b, :gen_np[b]].tolist())
                    pool.retire(slot_adm[b])
                    pt_np[b] = 0
                    pos_np[b] = 0
                    slot_ctx[b] = None
                    slot_rid[b], slot_adm[b] = None, None
        return [results[i] for i in range(n)]

    # --- step-level scheduling API (DESIGN.md §11) ---------------------------

    def _scatter_pages_fn(self, cache, pids, pages):
        """cache[:, pids[i]] = pages[:, i] for every pool plane.  Padding
        entries of ``pids`` point at trash page 0 (duplicate writes of the
        same zero page — content is never read un-fenced)."""
        return {k: v.at[:, pids].set(pages[k].astype(v.dtype))
                for k, v in cache.items()}

    def sched_check(self, prompt, stop: int) -> None:
        """Validate one request against this engine's capacity; raises for
        a request that could NEVER be admitted (schedulers call this at
        submit time so impossible requests fail fast, not in the queue)."""
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if stop < 1:
            raise ValueError("max_new must be >= 1")
        if len(prompt) + stop > self.max_len:
            raise ValueError("prompt + max_new exceeds max_len")
        if self.paged:
            pool = self.pool
            needed = pool.pages_needed(len(prompt), stop)
            if needed > min(pool.pages_per_slot, pool.usable_pages):
                raise ValueError(
                    f"request needs {needed} pages but the slot holds "
                    f"{pool.pages_per_slot} and the pool "
                    f"{pool.usable_pages}")

    def sched_state(self, key=None) -> SchedState:
        """Allocate one scheduler session's slot-pool state.  The paged
        pool itself lives on the engine (prefix cache persists across
        sessions, exactly like ``serve()`` calls)."""
        if self.spec is not None:
            raise NotImplementedError(
                "the step-level API drives plain decode rounds; "
                "speculative serve() remains a batch mode")
        B = self.max_batch
        # per-slot vectors live as HOST mirrors: sched_admit/release/swap
        # touch one slot at a time, and eager device scatters would cost a
        # dispatch each (the fleet admits 100k+ requests per trace).  The
        # jitted decode loop converts them on entry; serve_step writes the
        # round's results back (it already syncs them for harvesting).
        st = SchedState(
            live=np.zeros((B,), bool), last=np.zeros((B,), np.int32),
            n_gen=np.zeros((B,), np.int32),
            stops=np.ones((B,), np.int32),
            out=np.zeros((B, self.max_len), np.int32),
            key=jax.random.PRNGKey(0) if key is None else key)
        if self.paged:
            st.pt_np = np.zeros((B, self.pool.pages_per_slot), np.int32)
            st.pos = np.zeros((B,), np.int32)
            st.adm = [None] * B
        else:
            cache = self._place_kv(self.model.init_cache(
                B, self.max_len, dtype=self._cache_dtype))
            st.cache = {**cache, "pos": jnp.zeros((B,), jnp.int32)}
        return st

    def sched_admit(self, st: SchedState, slot: int, prompt,
                    stop: int) -> int | None:
        """Prefill one request into free slot ``slot``.  Returns the first
        sampled token, or None when the paged pool cannot supply its page
        reservation yet (the admission gate — nothing is allocated)."""
        with self.telemetry.region("engine.admit"):
            return self._sched_admit(st, slot, prompt, stop)

    def _sched_admit(self, st: SchedState, slot: int, prompt,
                     stop: int) -> int | None:
        if self.paged:
            adm, first, st.key = self._paged_admit(prompt, stop, st.key)
            if adm is None:
                self.telemetry.count("engine.admit_blocked")
                return None
            st.adm[slot] = adm
            st.pt_np[slot] = 0
            st.pt_np[slot, :len(adm.pids)] = adm.pids
            st.pos[slot] = len(prompt)
            st.last[slot] = first
            st.n_gen[slot] = 1
            st.stops[slot] = stop
            st.out[slot] = 0
            st.out[slot, 0] = first
        else:
            toks1, len1 = self._pad_prompts([list(prompt)])
            lg1, c1 = self._prefill(self.params, toks1, len1,
                                    self._ps if self.probes else None)
            c1 = self._ps_extract(c1)
            st.key, sub = jax.random.split(st.key)
            firstd = self._sample(lg1, sub)
            act = st.live & (st.n_gen < st.stops)
            st.cache, last, _, n_gen, stops, out = self._admit(
                st.cache, c1, slot, firstd[0], stop,
                st.last, act, st.n_gen, st.stops, st.out)
            st.last, st.n_gen = np.array(last), np.array(n_gen)
            st.stops, st.out = np.array(stops), np.array(out)
            first = int(firstd[0])
        st.live[slot] = True
        return first

    def serve_step(self, st: SchedState, quantum: int = 1):
        """One bounded decode round: every live, unfinished slot emits up
        to ``quantum`` tokens in lockstep (the serve() while_loop with
        per-round stop lengths — same jitted program, same numerics).

        Returns ``(tokens, finished)``: the new tokens per slot this
        round, and the slots whose requests hit their true stop (the
        caller must harvest and ``sched_release`` them)."""
        act = st.live & (st.n_gen < st.stops)
        if not act.any():
            return {}, []
        args = (kv_page_counts(st.pos, st.pt_np, self.page_size)
                if self.paged and self.telemetry.enabled else {})
        with self.telemetry.region("engine.decode_round", **args):
            return self._decode_round(st, act, quantum)

    def _decode_round(self, st: SchedState, act, quantum: int):
        tel = self.telemetry
        prev = st.n_gen.copy()
        round_stops = np.minimum(st.stops, st.n_gen + quantum)
        with tel.region("engine.decode.stage"):
            if self.paged:
                cache = {**self.pool.cache,
                         "page_table": jnp.asarray(st.pt_np),
                         "pos": jnp.asarray(st.pos)}
            else:
                cache = st.cache
            cache = self._ps_inject(cache)
        with tel.region("engine.decode.launch"):
            cache, last, _, n_gen, out, st.key = self._decode_loop(
                self.params, cache, st.last, act, st.n_gen, round_stops,
                st.out, st.key, stop_on_event=False)
        # np.asarray over a device array is a read-only view — copy so the
        # slot-wise sched_* writes stay plain numpy assignments
        with tel.region("engine.decode.wait"):
            # the first copy back is queued behind the loop and waits for
            # it, so the device's time ends here; an explicit
            # block_until_ready first measured ~0.3 % slower a round
            st.last = np.array(last)
        with tel.region("engine.decode.fetch"):
            cache = self._ps_extract(cache)
            st.n_gen = np.array(n_gen)
            st.out = np.array(out)
            if self.paged:
                st.pos = np.array(cache["pos"])
                self.pool.cache = {k: v for k, v in cache.items()
                                   if k not in ("page_table", "pos")}
            else:
                st.cache = cache
        with tel.region("engine.decode.collect"):
            gen, stops, out_np = st.n_gen, st.stops, st.out
            toks, done = {}, []
            for b in range(len(st.live)):
                if not st.live[b]:
                    continue
                if gen[b] > prev[b]:
                    toks[b] = out_np[b, prev[b]:gen[b]].tolist()
                if gen[b] >= stops[b]:
                    done.append(b)
            if tel.enabled and toks:
                tel.count("engine.steps")
                tel.count("engine.tokens",
                          sum(len(t) for t in toks.values()))
                tel.observe("engine.batch_occupancy", len(toks))
                tel.count("engine.stops_finished", len(done))
                tel.count("engine.stops_quantum", len(toks) - len(done))
        return toks, done

    def sched_release(self, st: SchedState, slot: int) -> None:
        """Retire a finished slot.  Paged: the request's pages go back to
        the pool (prefix registration included, like serve()); contiguous:
        the next admission's splice evicts the stale rows."""
        if self.paged:
            self.pool.retire(st.adm[slot])
            st.adm[slot] = None
            st.pt_np[slot] = 0
            st.pos[slot] = 0
        else:
            st.cache = {**st.cache,
                        "pos": st.cache["pos"].at[slot].set(0)}
        st.live[slot] = False

    def sched_swap_out(self, st: SchedState, slot: int) -> SwapBlob:
        """Preempt slot ``slot``: copy its KV state to a host-side blob,
        then release its device resources (paged: page refcounts drop,
        prefix-cache hashes survive — ``PagePool.swap_out``).  The copy
        happens strictly before the release: a released page can be
        re-allocated and overwritten immediately."""
        gen = int(st.n_gen[slot])
        stop = int(st.stops[slot])
        last = int(st.last[slot])
        out_row = st.out[slot, :gen].copy()
        if self.paged:
            pool, adm = self.pool, st.adm[slot]
            pos = int(st.pos[slot])
            n_data = -(-pos // self.page_size)
            reserve = adm.reserve
            pids = np.zeros((pool.pages_per_slot,), np.int32)
            pids[:adm.n_live] = adm.pids[:adm.n_live]
            pages = self._gather_pages(pool.cache, jnp.asarray(pids))
            data = {k: np.asarray(v[:, :n_data]) for k, v in pages.items()}
            pool.swap_out(adm)
            st.adm[slot] = None
            st.pt_np[slot] = 0
            st.pos[slot] = 0
            blob = SwapBlob(paged=True, pos=pos, stop=stop, n_gen=gen,
                            last=last, reserve=reserve, n_pages=n_data,
                            out_row=out_row, data=data)
        else:
            pos = int(np.asarray(st.cache["pos"])[slot])
            rows = self._gather_rows(st.cache["kv"], slot)
            data = {k: np.asarray(v)[:, :pos] for k, v in rows.items()}
            st.cache = {**st.cache,
                        "pos": st.cache["pos"].at[slot].set(0)}
            blob = SwapBlob(paged=False, pos=pos, stop=stop, n_gen=gen,
                            last=last, reserve=0,
                            n_pages=-(-pos // self.page_size),
                            out_row=out_row, data=data)
        st.live[slot] = False
        return blob

    def sched_swap_in(self, st: SchedState, slot: int,
                      blob: SwapBlob) -> bool:
        """Restore a swapped-out request into free slot ``slot`` —
        bit-exact (pages/rows written back verbatim), so a preempted
        request's continuation is token-identical to never having been
        preempted.  Returns False when the paged pool cannot supply the
        request's reservation yet.

        The blob need not come from THIS engine: fleet drain-time
        migration (DESIGN.md §15) restores a drained replica's blob on a
        survivor.  That only works between identically-shaped caches, so
        plane layout mismatches (a heterogeneous fleet) fail loudly here
        instead of scattering garbage."""
        if self.paged:
            pool = self.pool
            for k, v in pool.cache.items():
                d = blob.data.get(k)
                if d is None or tuple(d.shape[2:]) != tuple(v.shape[2:]) \
                        or d.shape[0] != v.shape[0]:
                    raise ValueError(
                        f"swap-in blob plane {k!r} does not match this "
                        f"engine's cache layout — migration requires "
                        f"identically-shaped replicas")
            adm = pool.swap_in(blob.reserve)
            if adm is None:
                self.telemetry.count("engine.swap_in_blocked")
                return False
            P = pool.pages_per_slot
            pids = np.zeros((P,), np.int32)
            pids[:blob.n_pages] = adm.pids[:blob.n_pages]
            pages = {}
            for k, v in pool.cache.items():
                pad = np.zeros((v.shape[0], P) + tuple(v.shape[2:]),
                               np.asarray(blob.data[k]).dtype)
                pad[:, :blob.n_pages] = blob.data[k]
                pages[k] = jnp.asarray(pad)
            pool.cache = self._scatter_pages(pool.cache, jnp.asarray(pids),
                                             pages)
            st.adm[slot] = adm
            st.pt_np[slot] = 0
            st.pt_np[slot, :len(adm.pids)] = adm.pids
            st.pos[slot] = blob.pos
        else:
            kv = {}
            for k, v in st.cache["kv"].items():
                pad = np.zeros((v.shape[0], 1) + tuple(v.shape[2:]),
                               np.asarray(blob.data[k]).dtype)
                pad[:, 0, :blob.pos] = blob.data[k]
                kv[k] = jnp.asarray(pad)
            c1 = {"kv": kv, "pos": jnp.asarray([blob.pos], jnp.int32)}
            st.cache = self._restore_slot(st.cache, c1, slot)
        st.out[slot] = 0
        st.out[slot, :blob.n_gen] = blob.out_row
        st.last[slot] = blob.last
        st.n_gen[slot] = blob.n_gen
        st.stops[slot] = blob.stop
        st.live[slot] = True
        return True

    # --- prompt plumbing -----------------------------------------------------

    def _pad_prompts(self, prompts):
        lens = [len(p) for p in prompts]
        if min(lens) < 1:
            raise ValueError("empty prompt")
        pb = _bucket(max(lens))
        if pb > self.max_len:
            raise ValueError(f"prompt bucket {pb} exceeds max_len "
                             f"{self.max_len}")
        toks = np.zeros((len(prompts), pb), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        return jnp.asarray(toks), jnp.asarray(lens, jnp.int32)

    # --- public API ----------------------------------------------------------

    def prefill_logits(self, prompt) -> jnp.ndarray:
        """(vocab,) logits at the last position of ``prompt`` — what the
        first generated token is sampled from — through the prefill program
        ``serve()`` runs: paged engines stream the prompt through the
        chunked prefill into freshly admitted pool pages, which are
        released again; contiguous engines run the bucketed prefill."""
        if self.paged:
            pool = self.pool
            adm = pool.admit(list(prompt), 1)
            if adm is None:
                raise RuntimeError("the page pool cannot admit the prompt")
            try:
                logits = self._chunked_prefill(pool, list(prompt), adm)
            finally:
                pool.retire(adm)
        else:
            toks, lens = self._pad_prompts([list(prompt)])
            logits, _ = self._prefill(self.params, toks, lens)
        return logits[0, -1, :self.model.cfg.vocab]

    def generate(self, prompts: list[list[int]], max_new: int = 32,
                 key=None) -> list[list[int]]:
        """Greedy (or sampled) continuation for a fixed batch of prompts.

        One jitted prefill + one jitted decode loop; Python is re-entered
        exactly once, at the end.
        """
        B = len(prompts)
        toks, lengths = self._pad_prompts(prompts)
        if int(jnp.max(lengths)) + max_new > self.max_len:
            raise ValueError("prompt + max_new exceeds max_len")
        key = jax.random.PRNGKey(0) if key is None else key
        logits, cache = self._prefill(self.params, toks, lengths,
                                      self._ps if self.probes else None)
        cache = self._place_kv(self._grow(cache))
        key, sub = jax.random.split(key)
        first = self._sample(logits, sub)
        stops = jnp.full((B,), max_new, jnp.int32)
        n_gen = jnp.ones((B,), jnp.int32)
        active = n_gen < stops
        out = jnp.zeros((B, max_new), jnp.int32).at[:, 0].set(first)
        cache, _, _, n_gen, out, _ = self._decode_loop(
            self.params, cache, first, active, n_gen, stops, out, key,
            stop_on_event=False)
        self._ps_extract(cache)
        out = np.asarray(out)
        return [list(p) + out[i, :max_new].tolist()
                for i, p in enumerate(prompts)]

    def serve(self, prompts: list[list[int]], max_new=32,
              key=None) -> list[list[int]]:
        """Continuous batching over a queue of requests.

        ``max_new`` may be an int or a per-request list.  Requests beyond
        ``max_batch`` wait; every time one in flight finishes, its slot is
        harvested and the next queued request joins *between* decode steps.
        With ``paged=True`` admission additionally waits on free cache
        pages (the real capacity resource) and prompts stream through
        page-sized prefill chunks.  With ``spec`` set, decode runs in
        speculative rounds (k drafted tokens verified per forward,
        DESIGN.md §9) — temperature=0 output is identical to non-spec
        serve, token for token.  Returns prompt + continuation per request,
        in submission order.
        """
        n = len(prompts)
        stops_req = ([max_new] * n if isinstance(max_new, int)
                     else list(max_new))
        for p, s in zip(prompts, stops_req):
            if len(p) < 1:
                raise ValueError("empty prompt")
            if len(p) + s > self.max_len:
                raise ValueError("prompt + max_new exceeds max_len")
            if s < 1:
                raise ValueError("max_new must be >= 1")
            if self.spec is not None and len(p) + s + self.spec.k > self.max_len:
                raise ValueError(
                    "prompt + max_new + spec.k exceeds max_len (the verify "
                    "forward needs k rows of speculative headroom)")
        key = jax.random.PRNGKey(0) if key is None else key
        if self.paged:
            if self.spec is not None:
                return self._serve_paged_spec(prompts, stops_req, key)
            return self._serve_paged(prompts, stops_req, key)
        if self.spec is not None:
            return self._serve_spec(prompts, stops_req, key)
        B, cap = self.max_batch, max(stops_req)

        cache = self._place_kv(self.model.init_cache(
            B, self.max_len, dtype=self._cache_dtype))
        cache = {**cache, "pos": jnp.zeros((B,), jnp.int32)}
        last = jnp.zeros((B,), jnp.int32)
        active = jnp.zeros((B,), bool)
        n_gen = jnp.zeros((B,), jnp.int32)
        stops = jnp.ones((B,), jnp.int32)
        out = jnp.zeros((B, cap), jnp.int32)

        queue = deque(range(n))
        slot_rid: list[int | None] = [None] * B
        results: dict[int, list[int]] = {}

        while queue or any(r is not None for r in slot_rid):
            # admit into every free slot (join happens between decode steps)
            free = [b for b in range(B) if slot_rid[b] is None]
            for b in free:
                if not queue:
                    break
                rid = queue.popleft()
                toks1, len1 = self._pad_prompts([prompts[rid]])
                lg1, c1 = self._prefill(self.params, toks1, len1,
                                        self._ps if self.probes else None)
                c1 = self._ps_extract(c1)
                key, sub = jax.random.split(key)
                first = self._sample(lg1, sub)
                cache, last, active, n_gen, stops, out = self._admit(
                    cache, c1, b, first[0], stops_req[rid],
                    last, active, n_gen, stops, out)
                slot_rid[b] = rid
            # decode in lockstep until some request finishes (the event)
            cache = self._ps_inject(cache)
            cache, last, active, n_gen, out, key = self._decode_loop(
                self.params, cache, last, active, n_gen, stops, out, key,
                stop_on_event=True)
            cache = self._ps_extract(cache)
            # harvest retired slots (leave happens between decode steps)
            act = np.asarray(active)
            gen = np.asarray(n_gen)
            out_np = np.asarray(out)
            for b in range(B):
                rid = slot_rid[b]
                if rid is not None and not act[b]:
                    results[rid] = (list(prompts[rid])
                                    + out_np[b, :gen[b]].tolist())
                    slot_rid[b] = None
        return [results[i] for i in range(n)]
