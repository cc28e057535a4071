"""Bring-up smoke test: the serving stack at full width on one TPU.

Drives the main path once through the launcher's own building blocks
(``launch/serve.py``): ``qwen3-1.7b`` at its published widths and depth
(28 layers, d_model 2048, 16 heads / 8 KV heads of 128, d_ff 6144, vocab
151936, bf16), random weights from ``model.init(PRNGKey(--seed))``
clustered to |W| = 1000 and served from codebook-index weights with a
paged int8 KV cache.

Phases, in order; the first that raises ends the run:

1. ``kernels``  — the routed codebook contraction at the QKV/O and FFN
   sites (M = 8 and 128, the real layer-0 index planes) and the routed
   lut contraction at the FFN sites (M = 2 and 16, exact) against
   ``kernels/ref.py``, the dequantize of those planes against
   ``codebook[w_idx]`` (exact), and the page gather on int8 K/V and f32
   scale pages against ``jnp.take``.
2. ``dense``    — ``ServeEngine.serve``, backend ``dense``, on the
   index-form params.
3. ``codebook`` — the same through the Pallas codebook kernel.
4. ``server``   — a short Poisson trace drained through the scheduler's
   step API on the codebook engine.
5. ``lut``      — the same serving through the §4 integer engine (2
   requests, 4 tokens).

``--four-chips`` runs only the tensor-parallel check instead: the
codebook backend at tp=4 over four chips against tp=1 on the first chip.

Compile seconds are JAX's lowering and XLA-compile events of the phase.
Tok/s lines are host-clock timings of one call for a handful of requests
less those seconds (tracing stays in) — smoke timings, not benchmarks.
Greedy-token agreement with ``dense`` is reported, not asserted: on random weights a rounding difference flips
argmax ties.  The last stdout line is ``{"ok": true, "device": ...}``,
printed only when every phase passed; without a TPU the script fails.
Each phase's start goes to stderr with its second, and every thread's
stack is dumped there if the run is still going at ``WATCHDOG_S``.

    python chip_smoke.py [--seed N] [--four-chips]
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "qwen3-1.7b"
N_WEIGHTS = 1000          # the paper's |W| working point
PAGE = 16
PROMPT_LEN = 8
MAX_NEW = 8
N_REQUESTS = 4
# Kernel parity tolerance: kernel and oracle multiply the SAME bf16
# operands (bf16(codebook[id]) × bf16 x — exact products in f32) and only
# accumulate them in different orders.  One bf16 ulp (2^-8) of Σ|x·w|
# bounds any such reordering by orders of magnitude while still failing a
# systematically wrong dequantize; single-entry exactness is probed
# separately with one-hot rows, which must match bit for bit.
KERNEL_RTOL = 2.0 ** -8
LUT_K = 500               # K of the lut parity slice: ragged against 64
# tp=4 vs tp=1 first-step logits: the row-parallel psum and the sharded
# attention reassociate f32 sums; where that moves a bf16 activation by an
# ulp, the int8 KV quantizer and 28 layers carry the difference on.  The
# bound is relative to the logits' RMS; the measured drift prints beside
# it.
TP_LOGIT_RTOL = 0.05
# Every thread's stack goes to stderr if the run is still going after
# this many seconds (it keeps running): a run cut at a 1200 s limit then
# shows where its time went.
WATCHDOG_S = 1080

_T0 = time.perf_counter()


# lowering to MLIR and the XLA compile; tracing is left out because a
# nested jit's trace is also inside its caller's, so its events overlap
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileSeconds:
    """Seconds JAX spent lowering and compiling, summed from its monitoring
    events (register with
    ``jax.monitoring.register_event_duration_secs_listener``)."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.total += duration_secs


def _timed(clock: CompileSeconds, fn):
    """(result, wall seconds, compile seconds within them) of ``fn()``."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, clock.total - c0


def _fail(msg: str) -> None:
    print(f"[chip_smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _start(phase: str) -> None:
    """Progress on stderr, so that the end of a cut run's stderr says
    which phase it was in."""
    print(f"[chip_smoke] {phase} starts at {time.perf_counter() - _T0:.1f}s",
          file=sys.stderr, flush=True)


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def _agreement(got, ref) -> str:
    """Position-wise greedy-token agreement of two generations."""
    pairs = [(a, b) for g, r in zip(got, ref) for a, b in zip(g, r)]
    same = sum(a == b for a, b in pairs)
    first = next(((i, j) for i, (g, r) in enumerate(zip(got, ref))
                  for j, (a, b) in enumerate(zip(g, r)) if a != b), None)
    where = ("identical" if first is None
             else f"first divergence: request {first[0]} token {first[1]}")
    return f"{same}/{len(pairs)} tokens ({where})"


def kernel_phase(cfg, params, seed: int, device) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch, ops, ref

    phase = "kernels"
    _start(phase)
    ops.reset_route_counts()
    blocks = params["blocks"]
    sites = {"wq": blocks["attn"]["wq"], "wk": blocks["attn"]["wk"],
             "wo": blocks["attn"]["wo"], "w1": blocks["mlp"]["w1"],
             "w2": blocks["mlp"]["w2"]}
    matmul = jax.jit(ops.codebook_matmul)
    oracle = jax.jit(ref.codebook_matmul_ref)
    dequantize = jax.jit(ops.dequantize, static_argnums=2)
    rng = np.random.default_rng(seed)
    for site, p in sites.items():
        idx, book = p["w_idx"][0], p["codebook"][0]      # layer 0
        k, n = idx.shape
        for m in (8, 128):
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            got = np.asarray(matmul(x, idx, book))
            want = np.asarray(oracle(x, idx, book))
            w32 = book[idx.astype(jnp.int32)]
            if not np.array_equal(np.asarray(dequantize(idx, book,
                                                        jnp.float32)),
                                  np.asarray(w32)):
                raise AssertionError(f"dequantize {site} differs from "
                                     "codebook[w_idx]")
            w = np.asarray(w32.astype(jnp.bfloat16), np.float32)
            mag = np.abs(np.asarray(x, np.float32)) @ np.abs(w)
            err = np.abs(got - want)
            worst = float(np.max(err / np.maximum(mag, 1e-30)))
            if not np.all(np.isfinite(got)) or not np.all(
                    err <= KERNEL_RTOL * mag):
                raise AssertionError(
                    f"codebook {site} M={m} ({k}->{n}): max |err|/Σ|xw| "
                    f"{worst:.3g} > {KERNEL_RTOL:.3g}")
            # one-hot rows select weight rows: every output is ONE product
            # 1·w, so the dequantized weights must come back exactly
            rows = (np.arange(m) * (k // m)) % k
            onehot = np.zeros((m, k), np.float32)
            onehot[np.arange(m), rows] = 1.0
            sel = np.asarray(matmul(jnp.asarray(onehot, jnp.bfloat16),
                                    idx, book))
            if not np.array_equal(sel, w[rows]):
                raise AssertionError(
                    f"codebook {site} M={m}: dequantized rows differ from "
                    "bf16(codebook[w_idx])")
            _say(phase, f"codebook {site} M={m} ({k}->{n}) parity ok: "
                 f"max |err|/Σ|xw| {worst:.3g} (limit {KERNEL_RTOL:.3g}), "
                 "one-hot rows exact")

    # the lut route against the oracle with the engine's own table for the
    # layer-0 codebook at the 4096-level grid: integer sums, so exact.  On
    # a ragged K slice — the oracle gathers one table entry per MAC
    lut = jax.jit(ops.lut_matmul)
    lut_oracle = jax.jit(ref.lut_matmul_ref)
    for site in ("w1", "w2"):
        idx, book = sites[site]["w_idx"][0], sites[site]["codebook"][0]
        k, n = idx.shape
        spec = dispatch.make_lut_spec(np.asarray(book), k)
        table = dispatch.build_lut_table(book, spec)
        kk = min(LUT_K, k)
        for m in (2, 16):
            a = jnp.asarray(rng.integers(0, spec.levels, (m, kk)), jnp.int32)
            w = idx[:kk]
            if not np.array_equal(
                    np.asarray(lut(a, w, table)),
                    np.asarray(lut_oracle(a, w.astype(jnp.int32), table))):
                raise AssertionError(f"lut {site} M={m} differs from the "
                                     "oracle")
            _say(phase, f"lut {site} M={m} ({kk}->{n}, table "
                 f"{tuple(table.shape)}) exact")

    n_pages = 64
    table = jnp.asarray(rng.integers(0, n_pages, (4, 8)), jnp.int32)
    pools = {
        "int8 K/V pages": jnp.asarray(
            rng.integers(-128, 128, (n_pages, PAGE, cfg.n_kv, cfg.hd)),
            jnp.int8),
        "f32 scale pages": jnp.asarray(
            rng.random((n_pages, PAGE, cfg.n_kv)), jnp.float32),
    }
    gather = jax.jit(ops.gather_pages)
    for name, pool in pools.items():
        got = np.asarray(gather(pool, table))
        want = np.asarray(jnp.take(pool, table, axis=0))
        if not np.array_equal(got, want):
            raise AssertionError(f"gather_pages on {name} is not exact")
        _say(phase, f"gather_pages on {name} {tuple(pool.shape)} exact")
    _check_routes(phase, "codebook", "lut", "dequantize")
    _say(phase, f"peak_bytes_in_use {_peak_bytes(device)}")


def _check_routes(phase: str, *kernels: str) -> dict:
    """Print the trace-time kernel routes; on a chip the kernels must have
    run compiled, and nothing may have run in the Pallas interpreter."""
    from repro.kernels import ops

    routes = ops.route_counts()
    _say(phase, f"route counts {json.dumps(routes, sort_keys=True)}")
    interp = {k: v for k, v in routes.items() if k.endswith("_interpret")}
    if interp:
        raise AssertionError(f"kernels ran in interpret mode: {interp}")
    for kernel in kernels:
        if ops.supports_compiled_pallas() and kernel != "dense" \
                and not routes.get(f"{kernel}.pallas"):
            raise AssertionError(f"no {kernel} contraction took the Pallas "
                                 "kernel")
    return routes


def serve_phase(phase: str, model, params, backend: str, prompts,
                max_new: int, device, clock, ref=None):
    """Serve ``prompts`` once on a fresh paged-int8 engine and check the
    first-step logits; returns (engine, generated tokens)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.serving import ServeEngine

    _start(phase)
    ops.reset_route_counts()
    engine = ServeEngine(model, params, max_len=PROMPT_LEN + MAX_NEW + 8,
                         backend=backend, max_batch=len(prompts), paged=True,
                         page_size=PAGE, kv_dtype="int8")
    outs, wall, comp = _timed(clock,
                              lambda: engine.serve(prompts, max_new=max_new))
    gen = [o[len(p):] for o, p in zip(outs, prompts)]
    if any(len(g) != max_new for g in gen):
        raise AssertionError(f"{backend}: wrong generation lengths "
                             f"{[len(g) for g in gen]}")
    logits = np.asarray(engine.prefill_logits(prompts[0]).astype(
        jnp.float32))
    if logits.shape != (model.cfg.vocab,) or not np.all(
            np.isfinite(logits)):
        raise AssertionError(f"{backend}: first-step logits of shape "
                             f"{logits.shape} are not all finite")
    n_tok = len(prompts) * max_new
    _say(phase, f"served {len(prompts)} requests x {max_new} new tokens; "
         f"first-step logits finite, shape {logits.shape}")
    _say(phase, f"compile seconds {comp:.3f} of a {wall:.3f}s serve call")
    _say(phase, f"smoke timing, not a benchmark: "
         f"{n_tok / max(wall - comp, 1e-9):.3f} tok/s host clock, less "
         "compile seconds")
    _say(phase, f"peak_bytes_in_use {_peak_bytes(device)}")
    _check_routes(phase, backend, "dequantize")
    if ref is not None:
        _say(phase, f"greedy agreement with dense: "
             f"{_agreement(gen, [r[:max_new] for r in ref])}")
    return engine, gen


def server_phase(engine, cfg, seed: int, device, clock) -> None:
    from repro.kernels import ops
    from repro.launch.serve import drain
    from repro.serving.server import poisson_trace

    phase = "server"
    _start(phase)
    ops.reset_route_counts()
    trace = poisson_trace(seed, 6, rate=40.0, vocab=cfg.vocab,
                          plen=(2, PROMPT_LEN), max_new=(2, MAX_NEW),
                          priorities=(0, 1))
    (rep, wall, _), _, comp = _timed(
        clock, lambda: drain(engine, trace, quantum=2))
    want = sum(r["max_new"] for r in trace)
    if rep.n_requests != len(trace) or rep.n_tokens != want:
        raise AssertionError(f"drain served {rep.n_requests} requests / "
                             f"{rep.n_tokens} tokens; expected "
                             f"{len(trace)} / {want}")
    _say(phase, f"drained {rep.n_requests} requests / {rep.n_tokens} tokens "
         f"through the scheduler step API, {rep.preemptions} preemptions")
    _say(phase, f"compile seconds {comp:.3f} of a {wall:.3f}s drain")
    _say(phase, f"smoke timing, not a benchmark: "
         f"{rep.n_tokens / max(wall - comp, 1e-9):.3f} tok/s host clock, "
         "less compile seconds")
    _say(phase, f"peak_bytes_in_use {_peak_bytes(device)}")
    _check_routes(phase, "codebook", "dequantize")


def four_chip_phase(model, params, prompts, devices, clock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.serving import ServeEngine

    phase = "tp4"
    _start(phase)
    kw = dict(max_len=PROMPT_LEN + MAX_NEW + 8, backend="codebook",
              max_batch=len(prompts), paged=True, page_size=PAGE,
              kv_dtype="int8")
    engines = {4: ServeEngine(model, params, mesh=make_local_mesh(1, 4),
                              **kw),
               1: ServeEngine(model, jax.device_put(params, devices[0]),
                              **kw)}
    worst = 0.0
    for i, p in enumerate(prompts):
        l4, l1 = (np.asarray(engines[tp].prefill_logits(p).astype(
            jnp.float32)) for tp in (4, 1))
        if not (np.all(np.isfinite(l4)) and np.all(np.isfinite(l1))):
            raise AssertionError(f"request {i}: non-finite logits")
        rel = float(np.sqrt(np.mean((l4 - l1) ** 2) / np.mean(l1 ** 2)))
        worst = max(worst, rel)
        _say(phase, f"request {i}: first-step logits tp=4 vs tp=1 rel RMS "
             f"{rel:.3g}, max |diff| {float(np.max(np.abs(l4 - l1))):.3g}, "
             f"argmax {int(np.argmax(l4))} vs {int(np.argmax(l1))}")
    if worst > TP_LOGIT_RTOL:
        raise AssertionError(f"tp=4 logits drift {worst:.3g} > "
                             f"{TP_LOGIT_RTOL}")
    _say(phase, f"logits within {TP_LOGIT_RTOL} rel RMS (worst {worst:.3g})")
    gen = {}
    for tp, eng in engines.items():
        outs, wall, comp = _timed(clock,
                                  lambda: eng.serve(prompts, MAX_NEW))
        gen[tp] = [o[len(p):] for o, p in zip(outs, prompts)]
        _say(phase, f"tp={tp}: served {len(prompts)} requests x {MAX_NEW} "
             f"new tokens; compile seconds {comp:.3f} of a {wall:.3f}s "
             f"serve call; smoke timing, not a benchmark: "
             f"{len(prompts) * MAX_NEW / max(wall - comp, 1e-9):.3f} tok/s "
             "host clock, less compile seconds")
    _say(phase, f"greedy agreement tp=4 vs tp=1: {_agreement(gen[4], gen[1])}")
    for d in devices[:4]:
        _say(phase, f"{d} peak_bytes_in_use {_peak_bytes(d)}")


def run(cfg, *, seed: int = 0, four_chips: bool = False):
    """Every phase for ``cfg`` on the visible devices; raises on failure.
    ``main`` calls it at full width after finding the chip."""
    import jax
    import numpy as np

    from repro.launch.serve import make_params
    from repro.models.model_zoo import build

    devices = jax.devices()
    clock = CompileSeconds()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        _start("setup")
        model = build(cfg)
        params, wall, _ = _timed(clock, lambda: jax.block_until_ready(
            make_params(model, seed=seed, n_weights=N_WEIGHTS)))
        _say("setup", f"{cfg.name}: {cfg.n_layers} layers, d_model "
             f"{cfg.d_model}, vocab {cfg.vocab}, {cfg.dtype}; random "
             f"weights (seed {seed}) clustered to |W|={N_WEIGHTS} in index "
             f"form in {wall:.3f}s; peak_bytes_in_use "
             f"{_peak_bytes(devices[0])}")
        rng = np.random.default_rng(seed)
        prompts = [[int(t) for t in rng.integers(0, cfg.vocab, PROMPT_LEN)]
                   for _ in range(N_REQUESTS)]
        if four_chips:
            four_chip_phase(model, params, prompts, devices, clock)
            return
        kernel_phase(cfg, params, seed, devices[0])
        _, dense = serve_phase("dense", model, params, "dense", prompts,
                               MAX_NEW, devices[0], clock)
        engine, _ = serve_phase("codebook", model, params, "codebook",
                                prompts, MAX_NEW, devices[0], clock,
                                ref=dense)
        server_phase(engine, cfg, seed, devices[0], clock)
        del engine
        # last: the slowest engine, and its chip cost the least known
        serve_phase("lut", model, params, "lut", prompts[:2], 4, devices[0],
                    clock, ref=dense[:2])
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and trace are made from it")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 vs tp=1 check on four chips")
    args = ap.parse_args()
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        _fail(f"the repro package is not under {src}")
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    import repro.configs as configs

    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX found {len(devices)} {devices[0].platform} "
              "device(s)")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        _fail(f"needs {want} TPU chips; found {len(devices)}")
    faulthandler.dump_traceback_later(WATCHDOG_S)
    run(configs.get(ARCH), seed=args.seed, four_chips=args.four_chips)
    faulthandler.cancel_dump_traceback_later()
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
