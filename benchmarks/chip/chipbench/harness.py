"""One run of one cell: load, warm up, measure, check, report.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up makes the configuration's index-form weights on the chip from the
seed, builds one paged int8 ``ServeEngine`` on the codebook backend and a
``Server`` over it (where the engine block states ``tp``, the engine is
tensor-parallel over the cell's chips and each chip makes only its own
shard of the weights), and warms the cell's shapes: two short requests
served whole, then, in a closed loop, every client's slot filled and two
rounds decoded.  The window then offers the mix for ``--seconds`` seconds
on the host clock.  With ``--trace 1``
the profiler records the window and the run reports the per-layer metrics;
with ``--trace 0`` the end-to-end metrics.

After the window the program's state is freed and the plain reference
(``reference.py``, or the module the configuration names) scores a
seeded sample of the served requests; the widest gap by which a served
token's logit lies below the reference's best is held against
``limits/<cell>.json`` (or, where that file names it instead, the median
gap).  With ``--control fp8`` the control's same number is compared as
well, against the same limit, and the run comes out not correct where it
passes it.  The numbers compared go to
stderr as the last lines and into the result line under ``checks``.  The
last stdout line is the result; a run that finds no TPU, or fewer chips
than the cell asks for, prints none and exits 1.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import counts, loadgen, peaks, spec, traffic
from chipbench import trace as tr

# tokens every warm-up request has before the window: the admission's
# and two decode rounds', so each program has met the state its own
# outputs give it (on a mesh the sampling key comes back from a round
# with the mesh's sharding, and the next round and the next admission
# compile for it)
WARM_TOKENS = 3
# the longest a run waits after the window for the first token of a
# request due in it; one still without a token then has failed
DRAIN_S = 60.0
# the longest warm-up or fill before the window
WARM_S = 300.0
# lowering to MLIR and the XLA compile (tracing left out: a nested jit's
# trace is also inside its caller's, so those events overlap)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def say(msg: str) -> None:
    print(f"[chip-bench] {msg}", file=sys.stderr, flush=True)


class CompileSeconds:
    """Seconds JAX spent lowering and compiling, and how many backend
    compiles, summed from its monitoring events."""

    def __init__(self):
        self.total = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.total += duration_secs
        if event == _COMPILE_EVENTS[1]:
            self.compiles += 1


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: spec.Cell
    shape: counts.Shape
    peaks: object
    page_size: int
    seconds: float
    t_open: float
    t_close: float
    t_drained: float
    records: list            # Detached record of every request offered
    spans: list              # loadgen.Span of every admission and round
    setup_s: float
    memory_peak_bytes: int
    trace: dict | None

    def due_in_window(self) -> list:
        return [r for r in self.records
                if self.t_open <= r.due < self.t_close]

    def ttft(self, rec) -> float:
        """Due time to first token; a request with none counts the whole
        wait until the run stopped waiting."""
        t = rec.first_token
        return (self.t_drained if t is None else t) - rec.due

    def window_spans(self, name: str) -> list:
        return [s for s in self.spans
                if s.name == name and self.t_open <= s.start < self.t_close]

    def kernel_ops(self, name: str) -> list:
        """(HLO text, device seconds) of every op in the traced window
        whose instruction name holds ``name``; none without a trace."""
        return [] if self.trace is None else tr.kernel_ops(
            self.trace["ops"], name)


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file, checked
    against the published numbers the file states."""
    import repro.configs as configs

    prog = config["program"]
    mcfg = configs.get(prog["registry"]).replace(**prog.get("replace", {}))
    hf, a = config["hf"], config["assumed"]
    want = {
        "n_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
        "n_heads": hf["num_attention_heads"],
        "n_kv": hf["num_key_value_heads"],
        "hd": hf.get("head_dim", hf["hidden_size"]
                     // hf["num_attention_heads"]),
        "d_ff": hf["intermediate_size"], "vocab": hf["vocab_size"],
        "tie_embeddings": hf["tie_word_embeddings"],
        "rope_theta": hf["rope_theta"], "dtype": hf["torch_dtype"],
        "qk_norm": a["qk_norm"], "padded_vocab": a["padded_vocab"],
    }
    have = {k: getattr(mcfg, k) for k in want}
    bad = {k: (have[k], v) for k, v in want.items() if have[k] != v}
    if bad:
        raise ValueError(f"program config {mcfg.name} differs from the "
                         f"configuration file: {bad} (program, file)")
    return mcfg


def _check_device(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; found "
                         f"{len(devices)}")
    return devices[:chips]


def _hbm(devices) -> str:
    """Bytes in use and the peak so far, on the fullest chip."""
    stats = [d.memory_stats() or {} for d in devices]
    use = max(int(m.get("bytes_in_use", 0)) for m in stats)
    return (f"{use / 1e9:.3f} GB in use, peak {_peak_bytes(devices) / 1e9:.3f}"
            " GB (fullest chip)")


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _routes(on_tpu: bool) -> None:
    from repro.kernels import ops

    routes = ops.route_counts()
    say(f"route counts {json.dumps(routes, sort_keys=True)}")
    if on_tpu and (any(k.endswith("_interpret") for k in routes)
                   or not routes.get("codebook.pallas")):
        say("the codebook contraction did not run as the compiled Pallas "
            "kernel")


def serve_window(cell: spec.Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, devices, rate: float | None = None,
                 schedule_seed: int | None = None):
    """Set-up and window.  Returns (Run, samples): the samples are the
    (prompt, served tokens) pairs the reference will score, as plain
    lists, so nothing of the program outlives this call."""
    import jax

    from repro.models.model_zoo import build
    from repro.serving import ServeEngine

    from chipbench import weights

    clock = CompileSeconds()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        mix = dict(cell.mix)
        if rate is not None:
            mix["rate_per_s"] = rate
        if schedule_seed is not None:
            mix["schedule_seed"] = schedule_seed
        traffic.check_mix(mix)
        conf = cell.config
        tp, eng_kw = spec.engine_settings(cell)
        a = conf["assumed"]
        mcfg = program_config(conf)
        if traffic.max_total_len(mix) > eng_kw["max_len"]:
            raise ValueError(f"mix {cell.mix_name} can draw "
                             f"{traffic.max_total_len(mix)} tokens > "
                             f"max_len {eng_kw['max_len']}")
        model = build(mcfg)
        tree = weights.serving_tree(model, a["n_weights"])
        mesh = shardings = None
        if tp is not None:
            mesh, shardings = _tp_layout(tp, tree)
        say(f"device ready, tree known at {loadgen.now() - t_start:.3f}s "
            "since start")
        params = jax.block_until_ready(weights.make_params(
            tree, seed, a["n_weights"], a["weight_std"], shardings))
        say(f"weights made at {loadgen.now() - t_start:.3f}s since start; "
            f"{_hbm(devices)}")
        engine = ServeEngine(model, params, mesh=mesh, **eng_kw)
        session = loadgen.Session(engine, int(mix["quantum"]))
        rng = np.random.default_rng([int(seed), 2])
        vocab = mcfg.vocab
        clients = []
        # two short requests served whole, one after the other, first:
        # every program runs once, and then again on the state its own
        # outputs left
        ready = True
        for _ in range(2):
            warm = traffic.Request(0.0, tuple(int(t) for t in rng.integers(
                0, vocab, 2 * eng_kw["page_size"])), WARM_TOKENS)
            rec = session.submit(warm, loadgen.now())
            ready = ready and session.run_until(lambda: rec.finished,
                                                loadgen.now() + WARM_S)
        say(f"warm requests served at {loadgen.now() - t_start:.3f}s since "
            f"start; {_hbm(devices)}")
        if mix["loop"] == "open":
            requests = traffic.open_loop(mix, seed, seconds, vocab)
        elif ready:
            waves = {}

            def request(c, k):
                if k not in waves:
                    waves[k] = traffic.closed_wave(mix, seed, k, vocab)
                return waves[k][c]

            sent = [0] * int(mix["clients"])

            def next_request(c):
                sent[c] += 1
                return request(c, sent[c])

            clients = [session.submit(request(c, 0), loadgen.now())
                       for c in range(int(mix["clients"]))]
            ready = session.run_until(lambda: all(
                len(r.token_times) >= WARM_TOKENS for r in clients),
                loadgen.now() + WARM_S)
        if not ready:
            raise RuntimeError(f"set-up did not finish in {WARM_S}s")
        session.records = {r.rid: r for r in clients}
        session.engine.spans.clear()
        say(f"set-up compile seconds {clock.total:.3f} "
            f"({clock.compiles} backend compiles)")

        say(f"warm at {loadgen.now() - t_start:.3f}s since start; "
            f"{_hbm(devices)}")
        tdir = tempfile.mkdtemp(prefix="chip-bench-trace-") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        marks = {}

        def on_open():
            marks["compiles"] = clock.compiles
            # made after start_trace: an annotation made earlier records
            # nothing
            marks["ann"] = jax.profiler.TraceAnnotation("bench.window")
            marks["ann"].__enter__()

        def on_close():
            marks["ann"].__exit__(None, None, None)
            marks["compiles"] = clock.compiles - marks["compiles"]
            if trace:
                jax.profiler.stop_trace()

        if mix["loop"] == "open":
            t_open, t_close = loadgen.run_open(session, requests, seconds,
                                              on_open, on_close)
            due = [r for r in session.records.values()
                   if r.due < t_close]
            session.run_until(lambda: all(r.first_token is not None
                                          for r in due), t_close + DRAIN_S)
        else:
            t_open, t_close = loadgen.run_closed(session, next_request,
                                                clients, seconds, on_open,
                                                on_close)
        t_drained = loadgen.now()
        peak = _peak_bytes(devices)
        if mix["loop"] == "open":
            _say_load(session, t_open, t_close)
        say(f"compiles inside the window: {marks['compiles']}")
        _routes(devices[0].platform == "tpu")
        session.admit_starts()
        trace_sum = None
        if trace:
            loaded = tr.load(_xplane(tdir))
            shutil.rmtree(tdir, ignore_errors=True)
            if loaded.devices:
                trace_sum = tr.reduce(loaded)
                say(f"device seconds by program: {trace_sum['programs']}")
            else:
                say("the trace holds no device ops")
        records = list(session.records.values())
        samples = _samples(records, seed, t_open,
                           int(cell.limits["sample_requests"]))
        run = Run(cell=cell, shape=counts.Shape.of(conf),
                  peaks=_peaks(devices[0]), page_size=eng_kw["page_size"],
                  seconds=seconds,
                  t_open=t_open, t_close=t_close, t_drained=t_drained,
                  records=[_detach(r) for r in records],
                  spans=list(session.engine.spans),
                  setup_s=t_open - t_start, memory_peak_bytes=peak,
                  trace=trace_sum)
        return run, samples
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)


def _tp_layout(tp: int, tree):
    """The ('data', 'model') mesh of a tensor-parallel cell over its
    ``tp`` devices, and the serving placement of ``tree`` on it — the
    shardings the engine itself would put the weights to, so that they
    are made in place and the engine moves nothing."""
    import jax
    from jax.sharding import PartitionSpec

    from repro.distributed import sharding
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(model=tp)
    specs = sharding.serve_param_specs(tree)
    return mesh, jax.tree_util.tree_map(
        lambda s: sharding.named(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def _say_load(session, t_open: float, t_close: float) -> None:
    """How the window's load went: what was due, and answered by the
    close, the mean time to first token of the requests due in each third
    of the window (a queue that grows all through the run, above the knee,
    raises it third by third), and every request's (due, time to first
    token, prompt length)."""
    recs = sorted((r for r in session.records.values()
                   if t_open <= r.due < t_close), key=lambda r: r.due)
    if not recs:
        return
    by_close = sum(r.first_token is not None and r.first_token < t_close
                   for r in recs)
    third = (t_close - t_open) / 3
    means = []
    for k in range(3):
        w = [r.first_token - r.due for r in recs
             if r.first_token is not None
             and k <= (r.due - t_open) // third < k + 1]
        means.append(f"{np.mean(w):.3f}" if w else "nan")
    say(f"load: {len(recs)} due in the window, {by_close} answered by its "
        f"close, {len(recs) - by_close} still waiting then; mean time to "
        f"first token by third of the window {' / '.join(means)} s")
    say("load by request (due s, time to first token s, prompt): " + str(
        [(round(r.due - t_open, 2),
          None if r.first_token is None else round(r.first_token - r.due, 3),
          r.prompt_len) for r in recs]))


def _peaks(device):
    if device.platform != "tpu":
        return None
    return peaks.lookup(device.device_kind)


def _xplane(tdir: str) -> str:
    import glob

    found = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file, found {found}")
    return found[0]


@dataclasses.dataclass
class Detached:
    """A record without its handle (which holds the scheduler)."""

    due: float
    sent: float
    prompt_len: int
    max_new: int
    token_times: list
    admit_start: float | None
    finished: bool

    @property
    def first_token(self):
        return self.token_times[0] if self.token_times else None


def _detach(r) -> Detached:
    return Detached(r.due, r.sent, r.prompt_len, r.max_new,
                    list(r.token_times), r.admit_start, r.finished)


def _samples(records, seed: int, t_open: float, n: int) -> list:
    """The requests the reference scores: the one with the most tokens
    (prompt and served) and, drawn from the seed, others with tokens
    served in the window."""
    cand = [r for r in records if r.handle.tokens
            and any(t >= t_open for t in r.token_times)]
    if not cand:
        return []
    cand.sort(key=lambda r: (-(r.prompt_len + len(r.handle.tokens)), r.rid))
    rest = cand[1:]
    rng = np.random.default_rng([int(seed), 3])
    pick = [cand[0]] + [rest[i] for i in sorted(rng.permutation(len(rest))
                                                [:n - 1])]
    return [(list(r.handle.prompt), list(r.handle.tokens)) for r in pick]


def measure(run: Run) -> dict:
    """Every metric this run reports, by its reader."""
    out = {}
    for m in run.cell.metrics:
        v = spec.reader(m["name"], run.cell.root)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# the numbers a limits file may hold against the served tokens' gaps
GAP_NUMBERS = {"max_logit_gap": max,
               "median_logit_gap": lambda g: float(np.median(g))}


def check(cell: spec.Cell, seed: int, samples, control: str | None):
    """The comparison that decides ``correct``: (correct, checks).
    Compared are the numbers of ``GAP_NUMBERS`` that the cell's limits
    file names, each against its own limit; every one has to lie within
    it.  With a ``control`` the control's same numbers are compared too."""
    reference = spec.reference(cell)
    limits = {k: float(cell.limits[k]) for k in GAP_NUMBERS
              if k in cell.limits}
    if not limits:
        raise ValueError(f"limits of {cell.name} name none of "
                         f"{sorted(GAP_NUMBERS)}")
    t0 = time.perf_counter()
    if not samples:
        return False, {"served_requests_scored": {"value": 0, "limit": 1}}
    got = reference.served_gaps(
        cell.config, seed, samples, int(cell.limits["sample_requests"]),
        int(cell.config["engine"]["max_len"]), lower=control)
    gaps = got["served"]
    say(f"reference scored {len(samples)} requests, {len(gaps)} served "
        f"tokens in {time.perf_counter() - t0:.3f}s; gap median "
        f"{float(np.median(gaps)):.6g}, widest {max(gaps)!r}, share of "
        f"tokens at the reference's best "
        f"{float(np.mean(np.asarray(gaps) == 0)):.4f}")
    if control:
        cg = got["control"]
        say(f"control ({control}) widest gap {max(cg)!r}, median "
            f"{float(np.median(cg)):.6g} over {len(cg)} positions")
    checks = {k: {"value": GAP_NUMBERS[k](gaps), "limit": v}
              for k, v in limits.items()}
    if control:
        checks.update({f"control_{control}_{k}": {
            "value": GAP_NUMBERS[k](got["control"]), "limit": v}
            for k, v in limits.items()})
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             control: str | None = None, rate: float | None = None,
             schedule_seed: int | None = None) -> dict:
    import jax

    devices = _check_device(cell.chips, require_tpu)
    run, samples = serve_window(cell, seed, seconds, trace, t_start,
                                devices, rate, schedule_seed)
    metrics = measure(run)
    attempted, failed = _attempts(run)
    for name in ("sched_admit", "serve_step"):
        spans = run.window_spans(name)
        say(f"{name}: {len(spans)} calls in the window, "
            f"{sum(x.end - x.start for x in spans):.3f} host seconds")
    due = run.due_in_window()
    if due:
        say(f"time to first token of each request due in the window, "
            f"sorted: {sorted(round(run.ttft(r), 4) for r in due)}")
    gc.collect()
    jax.clear_caches()
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    say(f"program state freed: {live} bytes of arrays still live")
    correct, checks = check(cell, seed, samples, control)
    d = devices[0]
    result = {"correct": bool(correct and failed == 0 and attempted > 0),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": {"platform": d.platform, "kind": d.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": run.memory_peak_bytes}}
    if run.trace is not None:
        result["device"].update(busy_s=run.trace["busy_s"],
                                window_s=run.trace["window_s"])
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def _attempts(run: Run) -> tuple:
    """(attempted, failed): an open loop attempts every request due in
    the window and fails those with no first token; a closed loop attempts
    every request served in the window."""
    if run.cell.mix["loop"] == "open":
        due = run.due_in_window()
        return len(due), sum(r.first_token is None for r in due)
    served = [r for r in run.records
              if any(run.t_open <= t < run.t_close for t in r.token_times)]
    return len(served), 0


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="also compare the lower-precision control, which "
                         "has to come out not correct (limit calibration; "
                         "the benchmark's runs never do)")
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open loop's rate (the knee sweep)")
    ap.add_argument("--schedule-seed", type=int, default=None,
                    help="override the mix's schedule_seed (the knee sweep)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, bool(args.trace))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, control=args.control, rate=args.rate,
                      schedule_seed=args.schedule_seed)
    for name, c in result["checks"].items():
        say(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
