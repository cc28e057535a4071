"""The program's own names in a trace (``chipbench/regions.py``) and the
run that records them (``benchmarks/chip/attribute.py``).

A hand-made profile — written in the XSpace wire format, so that
``ProfileData`` and ``regions.op_stacks`` read the same bytes — holds
device ops under name stacks and nested program regions; its reduction is
worked out by hand below.  The recorded TPU trace of a program without
either keeps every gap's ``bench.*`` name and puts every op under
``(none)``.  A tiny cell's run on the CPU records the regions and the
telemetry the attribution reads.
"""

import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "tiny_trace.json.gz"
sys.path[:0] = [str(HERE), str(pathlib.Path(__file__).resolve().parent)]

from chipbench import regions  # noqa: E402
from chipbench import trace as tr  # noqa: E402

PREFILL = "jit(_prefill_chunk_fn[codebook])/while/body/prefill_chunk/" \
    "while/body/closed_call/"
DECODE = "jit(_loop_fn[codebook])/while/body/decode_step/while/body/" \
    "closed_call/"

# (name, start ns, end ns) on the host
HOST = [
    ("bench.window", 0, 100_000),
    ("sched.step", 1_000, 90_000),
    ("sched.admit", 1_000, 30_000),
    ("bench.sched_admit", 1_100, 29_900),
    ("engine.admit", 1_200, 29_800),
    ("engine.prefill_chunk", 1_300, 10_000),
    ("engine.prefill_chunk", 10_000, 20_000),
    ("engine.first_token", 20_000, 29_000),
    ("bench.serve_step", 30_000, 80_000),
    ("engine.decode_round", 30_100, 79_900),
    ("engine.decode.stage", 30_100, 32_000),
    ("engine.decode.launch", 32_000, 35_000),
    ("engine.decode.wait", 35_000, 70_000),
    ("engine.decode.fetch", 70_000, 75_000),
    ("engine.decode.collect", 75_000, 79_900),
    ("sched.emit", 80_000, 90_000),
]
# (HLO text, start ns, end ns, name stack or None) on the device
OPS = [
    ("%fusion.1 = bf16[1,16,2048] fusion()", 4_000, 18_000,
     PREFILL + "attention/dot_general:"),
    ("%convert.2 = f32[1,1408,8,128] convert()", 18_000, 21_000,
     PREFILL + "kv_read/convert_element_type:"),
    ("%reduce.3 = s32[1] reduce()", 24_000, 28_000,
     "jit(_sample)/sample/argmax:"),
    ("%while.7 = (s32[]) while()", 36_000, 68_000,
     "jit(_loop_fn[codebook])/while:"),
    ("%convert.4 = f32[64,1408,8,128] convert()", 36_000, 46_000,
     DECODE + "kv_read/convert_element_type:"),
    ("%codebook_matmul_pallas.5 = f32[64,6144] custom-call()", 46_000,
     66_000, DECODE + "mlp/jit(codebook_matmul_pallas)/"
     "codebook_matmul_pallas/pallas_call:"),
    ("%copy.6 = s8[4] copy()", 66_000, 68_000, None),
]


# --- a minimal XSpace writer -----------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _bytes(field: int, body) -> bytes:
    body = body.encode() if isinstance(body, str) else body
    return _varint(field << 3 | 2) + _varint(len(body)) + body


def _plane(name: str, line: str, events: list, stacks: dict) -> bytes:
    """An XPlane with one line of (name, start ns, end ns) events; an
    event's metadata carries a ``tf_op`` stat where ``stacks`` has one."""
    ids = {n: i + 1 for i, n in enumerate(dict.fromkeys(e[0] for e in events))}
    evs = b"".join(_bytes(4, _int(1, ids[n]) + _int(2, a * 1000)
                          + _int(3, (b - a) * 1000)) for n, a, b in events)
    body = _bytes(2, name) + _bytes(3, _int(1, 1) + _bytes(2, line)
                                    + _int(3, 0) + evs)
    for n, i in ids.items():
        md = _int(1, i) + _bytes(2, n)
        if stacks.get(n):
            md += _bytes(5, _int(1, 1) + _bytes(5, stacks[n]))
        body += _bytes(4, _int(1, i) + _bytes(2, md))
    body += _bytes(5, _int(1, 1) + _bytes(2, _int(1, 1) + _bytes(2, "tf_op")))
    return _bytes(1, body)


def _xspace() -> bytes:
    return (_plane("/device:TPU:0", tr.OPS_LINE, [o[:3] for o in OPS],
                   {o[0]: o[3] for o in OPS})
            + _plane("/host:CPU", "python", HOST, {}))


@pytest.fixture(scope="module")
def profile():
    import jax

    data = _xspace()
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    return tr.from_profile(pd), regions.host_regions(pd), \
        regions.op_stacks(data)


def test_the_wire_reader_and_profiledata_agree(profile):
    trace, regs, stacks = profile
    assert [o[0] for o in trace.devices[0]] == [o[0] for o in sorted(
        OPS, key=lambda o: o[1])]
    assert stacks == {o[0]: o[3] for o in OPS if o[3]}
    assert [r[0] for r in regs] == [h[0] for h in HOST
                                    if not h[0].startswith("bench.")]
    assert [s[0] for s in trace.spans] == [h[0] for h in HOST
                                           if h[0].startswith("bench.")]


def test_gaps_are_named_by_the_innermost_region(profile):
    got = regions.attribute(*profile, iterations=2)
    assert got["idle_gaps"] == [
        ["between calls>sched.emit", 32e-6],
        ["bench.serve_step>engine.decode.launch", 8e-6],
        ["bench.sched_admit>engine.prefill_chunk", 4e-6],
        ["bench.sched_admit>engine.first_token", 3e-6],
    ]
    # where no region covers a gap, the name is trace.reduce's
    plain = regions.attribute(profile[0], [], {}, iterations=2)
    assert plain["idle_gaps"] == tr.reduce(profile[0])["idle_gaps"]


def test_device_seconds_by_scope(profile):
    got = dict(regions.attribute(*profile)["device_scopes"])
    assert got == pytest.approx({"mlp": 20e-6, "attention": 14e-6,
                                 "kv_read": 13e-6, "sample": 4e-6,
                                 "(none)": 2e-6})
    # leaf ops only: the enclosing while counts nowhere
    assert sum(got.values()) == pytest.approx(
        sum(s for _, s in tr.reduce(profile[0])["device_ops"]))


def test_round_and_chunk_quantities(profile):
    got = regions.attribute(*profile, iterations=2)
    # the round [30.1, 79.9) us is busy for [36, 68) us
    assert got["round_idle_ms"] == pytest.approx((49_800 - 32_000) / 1e6)
    # prefill_chunk ops 14 + 3 us over two engine.prefill_chunk regions
    assert got["prefill_chunk_device_ms"] == pytest.approx(8_500 / 1e6)
    # decode_step/kv_read ops 10 us over two iterations
    assert got["kv_read_ms"] == pytest.approx(5_000 / 1e6)
    assert got["region_ms"]["engine.prefill_chunk"] == \
        [2, pytest.approx((8_700 + 10_000) / 1e6)]
    assert got["region_ms"]["engine.decode.wait"] == \
        [1, pytest.approx(35_000 / 1e6)]


def test_scope_of_takes_the_innermost_part():
    assert regions.scope_of(DECODE + "attention/kv_read/gather:") == \
        "kv_read"
    assert regions.scope_of(DECODE + "squeeze:") == "decode_step"
    assert regions.scope_of("jit(_loop_fn[codebook])/while:") == "(none)"
    assert regions.scope_of(None) == "(none)"


def test_a_program_without_names_gives_none():
    t = tr.load_saved(RECORDED)
    got = regions.attribute(t, [], {}, iterations=10)
    reduced = tr.reduce(t)
    assert got["idle_gaps"] == reduced["idle_gaps"]
    assert [s for s, _ in got["device_scopes"]] == ["(none)"]
    for k in ("round_idle_ms", "prefill_chunk_device_ms", "kv_read_ms"):
        assert got[k] is None, k
    assert got["region_ms"] == {}


def test_tiny_cell_run_records_regions_and_telemetry(tmp_path):
    import attribute
    from chip_bench_fixtures import CELL, tiny_cell
    from chipbench import spec

    root = tiny_cell(tmp_path).root
    cell = spec.load_cell(CELL, True, repo=root.parents[1], root=root)
    got = attribute.attribute_cell(cell, 2 ** 31 + 9, 2.0,
                                   time.perf_counter(), require_tpu=False)
    assert got["regions"] > 0
    assert got["tokens_per_round"] >= 1
    assert got["queued_p95_s"] is not None and got["queued_p95_s"] >= 0
    assert "sched.queue_wait_p95_s.chat" in got["metrics"]
    assert "device_scopes" not in got         # the CPU has no device plane
