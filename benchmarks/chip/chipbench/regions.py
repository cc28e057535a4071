"""The program's own names in a traced window: host regions and device
scopes, and the idle time and device time they account for.

``trace.py`` reads what the benchmark names itself: its ``bench.*`` host
spans, and each device op by its HLO text.  The program names its work
too (DESIGN.md §13):

- host regions, ``Telemetry.region``: ``sched.step``, ``sched.admit``,
  ``sched.emit``, ``engine.admit``, ``engine.prefill_chunk``,
  ``engine.first_token``, ``engine.decode_round`` and its phases
  ``engine.decode.{stage,launch,wait,fetch,collect}``.  They are
  ``TraceAnnotation``s on the host planes, on the device ops' clock, and
  appear only where the server runs with a ``Telemetry``.
- device scopes, ``jax.named_scope``: ``prefill_chunk`` and
  ``decode_step``, and below them ``kv_read``, ``attention``, ``mlp``,
  ``head``; ``sample`` beside them.  On TPU a scope reaches the trace only
  as the ``tf_op`` stat of an op's event metadata on the ``XLA Ops`` line,
  the op's name stack, e.g. ``jit(_loop_fn[codebook])/while/body/
  decode_step/while/body/closed_call/kv_read/convert_element_type``.
  ``ProfileData`` does not expose metadata stats, so ``op_stacks`` reads
  them from the file's protobuf wire format.

``attribute`` reduces a window with both.  A trace of a program without
them leaves every gap its ``bench.*`` name and every op ``(none)``, and
the per-round and per-chunk quantities None.
"""

from __future__ import annotations

import bisect
import itertools

from chipbench import trace as tr

REGION_PREFIXES = ("sched.", "engine.")
# scopes of a model step's parts; an op takes the innermost one in its stack
PART_SCOPES = ("kv_read", "attention", "mlp", "head", "sample")
STEP_SCOPES = ("prefill_chunk", "decode_step")
NO_SCOPE = "(none)"
TF_OP = "tf_op"


# --- the XSpace wire format, as far as op name stacks need it ---------------
#
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map<int64,
# XEventMetadata>), stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7; XStatMetadata: id = 1, name = 2.

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """(field number, value) of each field of the message in buf[lo:hi];
    a length-delimited value is its (start, end) in ``buf``."""
    i = lo
    hi = len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value span of one map entry (key = 1, value = 2)."""
    return next((v for f, v in _fields(buf, *span) if f == 2), None)


def op_stacks(data: bytes) -> dict:
    """Each device op's name stack (``tf_op``) by the op's name (its HLO
    text, the event name ``ProfileData`` gives), over every device plane.
    An op whose text two programs share with different stacks keeps the
    first."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, events, stats = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                events.append(_map_value(buf, v))
            elif g == 5:
                sm = _map_value(buf, v)
                if sm is not None:
                    d = dict(_fields(buf, *sm))
                    if 2 in d:
                        stats[d.get(1, 0)] = _text(buf, d[2])
        if not tr._DEVICE.match(name):
            continue
        for em in events:
            if em is None:
                continue
            op, stack = None, None
            for g, v in _fields(buf, *em):
                if g == 2:
                    op = _text(buf, v)
                elif g == 5:
                    st = dict(_fields(buf, *v))
                    if stats.get(st.get(1)) != TF_OP:
                        continue
                    if 5 in st:
                        stack = _text(buf, st[5])
                    elif 7 in st:
                        stack = stats.get(st[7])
            if op is not None and stack is not None:
                out.setdefault(op, stack)
    return out


def host_regions(pd) -> list:
    """(name, start_ns, end_ns) of every program region on the host
    planes of a ``ProfileData``, by start."""
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in pd.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(REGION_PREFIXES)),
                  key=lambda r: r[1])


def scope_of(stack: str | None) -> str:
    """The innermost part scope in a name stack, else its step scope,
    else ``(none)``."""
    if not stack:
        return NO_SCOPE
    parts = [c.split(":", 1)[0] for c in stack.split("/")]
    for c in reversed(parts):
        if c in PART_SCOPES:
            return c
    for c in reversed(parts):
        if c in STEP_SCOPES:
            return c
    return NO_SCOPE


def _in_stack(stack: str | None, *scopes) -> bool:
    parts = {c.split(":", 1)[0] for c in (stack or "").split("/")}
    return all(s in parts for s in scopes)


def _innermost(regions, starts, reach, t) -> str | None:
    """The latest-starting region that covers ``t`` (regions nest);
    ``reach[i]`` is the latest end among ``regions[:i + 1]``."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if reach[i] <= t:
            return None
        if regions[i][2] > t:
            return regions[i][0]
    return None


def _busy_within(busy, starts, a, b) -> float:
    """Nanoseconds of the busy intervals inside [a, b)."""
    total = 0
    for i in range(max(bisect.bisect_right(starts, a) - 1, 0), len(busy)):
        lo, hi = busy[i]
        if lo >= b:
            break
        total += max(0, min(hi, b) - max(lo, a))
    return total


def attribute(trace: tr.Trace, regions: list, stacks: dict,
              iterations: int | None = None, top: int = 10) -> dict:
    """The window of ``trace`` put down to the program's own names.

    - ``idle_gaps``: the ``top`` longest stretches with no op on the
      first device, each named ``<bench span>><innermost region>`` by the
      program region over its midpoint (``trace.reduce``'s name where
      none covers it);
    - ``device_scopes``: device seconds of the window's leaf ops by
      ``scope_of`` their name stack;
    - ``round_idle_ms``: device-idle time inside the ``engine.decode_round``
      regions that start in the window, per round;
    - ``prefill_chunk_device_ms``: device seconds of ops under
      ``prefill_chunk`` per ``engine.prefill_chunk`` region in the window;
    - ``kv_read_ms``: device seconds of ops under ``decode_step`` and
      ``kv_read`` per decode iteration (``iterations``, the loop
      iterations the window's rounds ran);
    - ``region_ms``: by region name, how many start in the window and
      their host milliseconds in all.
    """
    lo, hi = tr.window(trace)
    ops = tr._clip(trace.devices[0], lo, hi)
    busy = tr.busy_intervals(ops)
    bstarts = [a for a, _ in busy]
    regs = [r for r in regions if r[2] > lo and r[1] < hi]
    rstarts = [r[1] for r in regs]
    reach = list(itertools.accumulate((r[2] for r in regs), max))
    calls = [s for s in trace.spans if s[0] != tr.WINDOW]
    cstarts = [s[1] for s in calls]

    gaps, t = [], lo
    for a, b in busy + [[hi, hi]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        i = bisect.bisect_right(cstarts, mid) - 1
        host = (calls[i][0] if i >= 0 and mid < calls[i][2]
                else "between calls")
        inner = _innermost(regs, rstarts, reach, mid)
        named.append([host if inner is None else f"{host}>{inner}",
                      (b - a) / 1e9])
    named.sort(key=lambda g: -g[1])

    scopes = {}
    chunk_ns = kv_ns = 0
    for n, a, b in tr._leaves(ops):
        stack = stacks.get(n)
        s = scope_of(stack)
        scopes[s] = scopes.get(s, 0.0) + (b - a) / 1e9
        if _in_stack(stack, "prefill_chunk"):
            chunk_ns += b - a
        if _in_stack(stack, "decode_step", "kv_read"):
            kv_ns += b - a

    def started(name):
        return [r for r in regs if r[0] == name and lo <= r[1] < hi]

    rounds = started("engine.decode_round")
    idle_ns = sum(min(b, hi) - a - _busy_within(busy, bstarts, a, min(b, hi))
                  for _, a, b in rounds)
    chunks = started("engine.prefill_chunk")
    region_ms = {}
    for n, a, b in regs:
        if lo <= a < hi:
            c = region_ms.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += (b - a) / 1e6
    return {
        "idle_gaps": named[:top],
        "device_scopes": sorted(([s, v] for s, v in scopes.items()),
                                key=lambda x: -x[1]),
        "round_idle_ms": idle_ns / len(rounds) / 1e6 if rounds else None,
        "prefill_chunk_device_ms": (chunk_ns / len(chunks) / 1e6
                                    if chunks and chunk_ns else None),
        "kv_read_ms": (kv_ns / iterations / 1e6
                       if iterations and kv_ns else None),
        "region_ms": region_ms,
    }
