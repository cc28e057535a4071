"""From a profiler trace to device busy time, kernel time and idle gaps.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it with
nothing but JAX.  A device plane (``/device:TPU:<n>``) holds the ops the
chip ran on its ``XLA Ops`` line; host planes hold the benchmark's own
``TraceAnnotation`` spans (``bench.window``, ``bench.sched_admit``,
``bench.serve_step``) on the same clock.

- busy: the union of the op intervals inside ``bench.window``, averaged
  over the device planes; idle share is 1 - busy / window.
- ops: every op of the first device inside the window, clipped to it,
  as (HLO text, seconds); ``kernel_ops`` picks a kernel's out of them by
  its instruction name, and the HLO text states the shapes.
- top ops: by total seconds, grouped by HLO instruction name without its
  number; an op that encloses others (a ``while`` over layers) is left
  out, so nothing counts twice.
- idle gaps: each stretch of the window with no op on the first device,
  named by the host span it falls in (``between calls`` when none).
- collectives: the seconds of the first device's window in which a
  collective op ran (all-reduce, all-gather, reduce-scatter,
  collective-permute, all-to-all: by opcode, as a fusion that calls one,
  or as the ``-start`` and ``-done`` halves of an async one), as the
  union of their intervals, so a pair whose halves overlap counts once;
  and that device's own busy seconds beside it.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
_COLL = (r"(?:all-reduce|all-gather|reduce-scatter|collective-permute|"
         r"all-to-all)")
# right of " = ": the opcode, with its async suffix, or a fusion's callee
_COLLECTIVE = re.compile(rf"(?:^|[\s)}}]){_COLL}(?:-start|-done)?\(|"
                         rf"calls=%{_COLL}")


@dataclasses.dataclass
class Trace:
    devices: list          # per device: [(name, start_ns, end_ns)] ops
    spans: list            # [(name, start_ns, end_ns)] bench.* host spans
    modules: list = dataclasses.field(default_factory=list)
    #                        first device: [(name, start_ns, end_ns)] of
    #                        each program run (jit__loop_fn_codebook(...))


def from_profile(pd) -> Trace:
    devices, spans, modules = [], [], []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices.append(sorted(ops, key=lambda o: o[1]))
                if len(devices) == 1:
                    modules = [(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for line in plane.lines
                               if line.name == MODULES_LINE
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith("bench."))
    return Trace(devices, sorted(spans, key=lambda s: s[1]), modules)


def load(path) -> Trace:
    import jax

    return from_profile(jax.profiler.ProfileData.from_file(str(path)))


def save(trace: Trace, path, name_chars: int = 240) -> None:
    """Write a trace as gzipped JSON (op names cut to ``name_chars``,
    which keeps an op's result and first operand shapes)."""
    import gzip
    import json

    doc = {"devices": [[[n[:name_chars], a, b] for n, a, b in ops]
                       for ops in trace.devices],
           "spans": [list(s) for s in trace.spans]}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load_saved(path) -> Trace:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return Trace([[tuple(o) for o in ops] for ops in doc["devices"]],
                 [tuple(s) for s in doc["spans"]])


def window(trace: Trace) -> tuple:
    w = [s for s in trace.spans if s[0] == WINDOW]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(w)}")
    return w[0][1], w[0][2]


def _clip(ops, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops
            if b > lo and a < hi]


def busy_intervals(ops) -> list:
    """Union of [start, end) intervals, merged and sorted."""
    out = []
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _family(name: str) -> str:
    """An op's instruction name without its number (``%fusion.12 = ...``
    -> ``fusion``), so repeats of one op add up."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def _leaves(ops) -> list:
    """Ops that enclose no other op (XLA ops nest or are disjoint)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= o[2]]


def _programs(modules, lo, hi) -> dict:
    """Device seconds of each jitted program in [lo, hi), by name without
    its fingerprint (``jit__loop_fn_codebook(123...)``)."""
    out = {}
    for n, a, b in _clip(modules, lo, hi):
        n = n.split("(", 1)[0]
        out[n] = out.get(n, 0.0) + (b - a) / 1e9
    return out


def is_collective(name: str) -> bool:
    """Whether an op's HLO text is a collective, whatever its instruction
    is called (a ``psum`` shows as ``%psum.3 = ... all-reduce(...)``)."""
    parts = name.split(" = ", 1)
    return len(parts) == 2 and _COLLECTIVE.search(parts[1]) is not None


def kernel_ops(ops, name: str) -> list:
    """The ops whose instruction name (left of `` = ``) holds ``name``, as
    (HLO text, seconds); a consumer that names the kernel as an operand
    is not the kernel."""
    return [(n, s) for n, s in ops if name in n.split(" = ", 1)[0]]


def reduce(trace: Trace, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, the first device's busy and
    collective seconds, the window's ops of the first device as (HLO
    text, seconds), the device seconds of each program, and the
    breakdown."""
    lo, hi = window(trace)
    if not trace.devices:
        raise ValueError("the trace holds no device ops")
    per_dev = [_clip(ops, lo, hi) for ops in trace.devices]
    busy = [sum(b - a for a, b in busy_intervals(ops)) for ops in per_dev]
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy) / 1e9
    ops0 = per_dev[0]
    collective = busy_intervals([o for o in ops0 if is_collective(o[0])])
    fam = {}
    for n, a, b in _leaves(ops0):
        fam[_family(n)] = fam.get(_family(n), 0.0) + (b - a) / 1e9
    gaps, t = [], lo
    for a, b in busy_intervals(ops0) + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    calls = [s for s in trace.spans if s[0] != WINDOW]
    starts = [s[1] for s in calls]
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        host = (calls[i][0] if i >= 0 and mid < calls[i][2]
                else "between calls")
        named.append([host, (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "first_busy_s": busy[0] / 1e9,
        "collective_s": sum(b - a for a, b in collective) / 1e9,
        "ops": [(n, (b - a) / 1e9) for n, a, b in ops0],
        "programs": sorted(([n, t] for n, t in _programs(trace.modules, lo,
                                                         hi).items()),
                           key=lambda x: -x[1]),
        "device_ops": sorted(([n, s] for n, s in fam.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": named[:top],
    }
