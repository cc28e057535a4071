"""One traced run of a cell, put down to the program's own names.

    python3 benchmarks/chip/attribute.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

Set-up and window are ``run.py --trace 1``'s, with the server given a
``Telemetry``: the scheduler and engine then write their host regions
(``sched.*``, ``engine.*``, DESIGN.md §13) into the profile, on the device
ops' clock.  The profile is reduced by ``trace.reduce`` as the harness
does, and by ``regions.attribute``.  Nothing is checked against the
reference.  The last stdout line (and ``--out``) is one JSON object:

- ``metrics``: the cell's per-layer metrics, by their readers;
- ``busy_s``, ``window_s``, ``device_ops``: ``trace.reduce``'s;
- ``idle_gaps``, ``device_scopes``, ``round_idle_ms``,
  ``prefill_chunk_device_ms``, ``kv_read_ms``: ``regions.attribute``'s;
- ``tokens_per_round``: the change in the ``engine.tokens`` counter over
  the change in ``engine.steps`` between snapshots at the window's open
  and close;
- ``queued_p95_s``: the 95th percentile of the program's ``queued`` span
  (arrival to the start of the admitting placement) over the requests
  that arrived in the window;
- ``longest_admission``: the window's longest ``engine.admit`` region,
  the regions inside it and the device's busy time under it;
- ``longest_gap``: the window's longest stretch with no op on the
  device, the program regions over it, and the other profile events
  (runtime threads, the device's program line) that overlap it most.

The harness builds its server and drops the profile itself, so for the
length of one run this wraps ``loadgen.Session`` (a server with a
``Telemetry``), ``loadgen.run_open`` / ``run_closed`` (the snapshots) and
``trace.load`` (the regions and op name stacks).  Exits 1 without a TPU,
as ``run.py`` does.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from unittest import mock  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


@contextlib.contextmanager
def program_telemetry(got: dict):
    """Runs inside give the server a ``Telemetry`` (``got["telemetry"]``),
    counter snapshots at the window's open and close (``got["open"]``,
    ``got["close"]``), and the profile's regions, op name stacks,
    ``trace.Trace`` and its other events of 0.1 ms or more
    (``got["regions"]``, ``got["stacks"]``, ``got["trace"]``,
    ``got["events"]``)."""
    import jax

    from repro.serving.server import Server
    from repro.serving.telemetry import Telemetry

    from chipbench import loadgen, regions
    from chipbench import trace as tr

    tel = got["telemetry"] = Telemetry()

    class Session(loadgen.Session):
        def __init__(self, engine, quantum: int):
            self.engine = loadgen.TimedEngine(engine)
            self.server = Server(self.engine, clock=loadgen.WallClock(),
                                 quantum=quantum, telemetry=tel)
            self.sched = self.server.sched
            self.records = {}

    def snapshotting(run):
        def wrapped(*args):
            *rest, on_open, on_close = args

            def opened():
                on_open()
                got["open"] = tel.snapshot()["counters"]

            def closed():
                got["close"] = tel.snapshot()["counters"]
                on_close()

            return run(*rest, opened, closed)
        return wrapped

    def load(path):
        data = pathlib.Path(path).read_bytes()
        pd = jax.profiler.ProfileData.from_serialized_xspace(data)
        got["regions"] = regions.host_regions(pd)
        got["stacks"] = regions.op_stacks(data)
        got["trace"] = tr.from_profile(pd)
        got["events"] = [(f"{p.name}:{line.name}", e.name, e.start_ns,
                          e.start_ns + e.duration_ns)
                         for p in pd.planes for line in p.lines
                         if line.name != tr.OPS_LINE
                         for e in line.events if e.duration_ns >= 100_000]
        return got["trace"]

    with mock.patch.object(loadgen, "Session", Session), \
            mock.patch.object(loadgen, "run_open",
                              snapshotting(loadgen.run_open)), \
            mock.patch.object(loadgen, "run_closed",
                              snapshotting(loadgen.run_closed)), \
            mock.patch.object(tr, "load", load):
        yield


def longest_admission(trace, regions: list) -> dict | None:
    """The window's longest ``engine.admit`` region: its length, each
    region inside it (name, start after the admission's, length; ms), and
    the device's busy milliseconds under it."""
    from chipbench import trace as tr

    lo, hi = tr.window(trace)
    admits = [r for r in regions
              if r[0] == "engine.admit" and lo <= r[1] < hi]
    if not admits:
        return None
    _, a, b = max(admits, key=lambda r: r[2] - r[1])
    inner = [[n, (s - a) / 1e6, (e - s) / 1e6] for n, s, e in regions
             if a <= s and e <= b and (s, e) != (a, b)]
    busy = tr.busy_intervals(tr._clip(trace.devices[0], a, b)) \
        if trace.devices else []
    return {"ms": (b - a) / 1e6, "device_busy_ms":
            sum(y - x for x, y in busy) / 1e6,
            "chunks": sum(r[0] == "engine.prefill_chunk" for r in inner),
            "regions": inner}


def longest_gap(trace, regions: list, events: list, top: int = 12):
    """The window's longest stretch with no op on the first device: when
    (seconds into the window), how long, every program region over its
    midpoint, and the ``top`` events of the rest of the profile that overlap it
    most (line, name, overlap in ms)."""
    from chipbench import trace as tr

    lo, hi = tr.window(trace)
    gaps, t = [], lo
    for a, b in tr.busy_intervals(tr._clip(trace.devices[0], lo, hi)) \
            + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if not gaps:
        return None
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    over = sorted(((line, n, (min(e, b) - max(s, a)) / 1e6)
                   for line, n, s, e in events if s < b and e > a),
                  key=lambda x: -x[2])
    return {"at_s": (a - lo) / 1e9, "ms": (b - a) / 1e6,
            "regions": [n for n, s, e in regions if s <= (a + b) / 2 < e],
            "overlapping": [list(x) for x in over[:top]]}


def attribute_cell(cell, seed: int, seconds: float, t_start: float, *,
                   require_tpu: bool = True) -> dict:
    from chipbench import harness, regions
    from chipbench.stats import percentile

    devices = harness._check_device(cell.chips, require_tpu)
    got = {}
    with program_telemetry(got):
        run, _ = harness.serve_window(cell, seed, seconds, True, t_start,
                                      devices)
    out = {"metrics": harness.measure(run)}
    opened, closed = got["open"], got["close"]
    steps = closed.get("engine.steps", 0) - opened.get("engine.steps", 0)
    out["tokens_per_round"] = ((closed.get("engine.tokens", 0)
                                - opened.get("engine.tokens", 0)) / steps
                               if steps else None)
    waits = [e["t1"] - e["t0"] for e in got["telemetry"].event_log()
             if e.get("name") == "queued"
             and run.t_open <= e["t0"] < run.t_close]
    out["queued_p95_s"] = percentile(waits, 95) if waits else None
    out["regions"] = len(got["regions"])
    if run.trace is not None:
        out.update(busy_s=run.trace["busy_s"],
                   window_s=run.trace["window_s"],
                   device_ops=run.trace["device_ops"])
        iterations = sum(s.info["iterations"]
                         for s in run.window_spans("serve_step"))
        out.update(regions.attribute(got["trace"], got["regions"],
                                     got["stacks"], iterations))
        out["longest_admission"] = longest_admission(got["trace"],
                                                     got["regions"])
        out["longest_gap"] = longest_gap(got["trace"], got["regions"],
                                         got["events"])
    return out


def main(argv=None) -> int:
    if not (REPO / "src" / "repro").is_dir():
        print(f"[chip-bench] the program is not here: no {REPO}/src/repro",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(REPO / "src"), str(HERE)]
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="also write the result to this file")
    args = ap.parse_args(argv)
    try:
        out = attribute_cell(spec.load_cell(args.workload, True), args.seed,
                             args.seconds, T_START)
    except SystemExit as e:
        if isinstance(e.code, str):
            print(f"[chip-bench] {e.code}", file=sys.stderr)
            return 1
        raise
    line = json.dumps(out)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
