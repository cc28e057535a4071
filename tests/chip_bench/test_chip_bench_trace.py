"""From a profiler trace to idle share, kernel time and the breakdown:
on a hand-made trace with known answers, on a small trace recorded on a
TPU v5e by the harness itself (``chip_bench_fixtures.py``: a 2-layer,
width-128 qwen3 cell traced for 0.25 s), and on the CPU profiler's own
output."""

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmarks" / "chip")]

from chipbench import readers, trace  # noqa: E402

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "tiny_trace.json.gz"
KERNEL = "codebook_matmul_pallas"
MS = 1_000_000


def _hand_made():
    k = ("%codebook_matmul_pallas.3 = f32[16,256]{1,0} custom-call("
         "bf16[16,128]{1,0} %x, s16[128,256]{1,0} %w)")
    ops = [("%while.1 = (s32[]) while(%t)", 10 * MS, 40 * MS),   # encloses
           (k, 10 * MS, 20 * MS),
           ("%fusion.2 = bf16[16,128] fusion(f32[16,256] "
            "%codebook_matmul_pallas.3)", 20 * MS, 40 * MS),
           ("%fusion.7 = bf16[4] fusion()", 60 * MS, 70 * MS)]
    spans = [("bench.window", 0, 100 * MS),
             ("bench.serve_step", 5 * MS, 45 * MS),
             ("bench.sched_admit", 50 * MS, 75 * MS)]
    return trace.Trace([ops], spans)


def test_hand_made_trace_reduces_to_known_numbers():
    r = trace.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.04)          # [10,40) + [60,70)
    assert r["idle_share"] == pytest.approx(0.6)
    # a consumer naming the kernel as its operand is not the kernel
    assert [s for _, s in trace.kernel_ops(r["ops"], KERNEL)] == \
        [pytest.approx(0.01)]
    assert len(r["ops"]) == 4
    ops = dict(r["device_ops"])
    assert "while" not in ops                          # encloses others
    assert ops == {"fusion": pytest.approx(0.03),
                   "codebook_matmul_pallas": pytest.approx(0.01)}
    gaps = r["idle_gaps"]
    assert gaps[0] == ["between calls", pytest.approx(0.03)]   # [70,100)
    assert ["bench.sched_admit", pytest.approx(0.02)] in gaps  # [40,60)
    assert ["bench.serve_step", pytest.approx(0.0)] not in gaps
    assert sum(g for _, g in gaps) == pytest.approx(0.06)


def test_a_trace_needs_one_window():
    t = _hand_made()
    t.spans = [s for s in t.spans if s[0] != "bench.window"]
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_recorded_tpu_trace():
    t = trace.load_saved(RECORDED)
    r = trace.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    calls = trace.kernel_ops(r["ops"], KERNEL)
    assert calls and all(s > 0 for _, s in calls)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert {h for h, _ in r["idle_gaps"]} <= {
        "bench.sched_admit", "bench.serve_step", "between calls"}
    assert all(not n.startswith("while") for n, _ in r["device_ops"])


def test_recorded_trace_gives_a_roofline_share_under_100():
    import json

    from chipbench import counts, peaks

    conf = json.loads((ROOT / "benchmarks/chip/configs/qwen3-1.7b.json")
                      .read_text())
    conf["hf"].update(hidden_size=128, intermediate_size=256,
                      vocab_size=512)

    class Run:
        ops = trace.reduce(trace.load_saved(RECORDED))["ops"]
        shape = counts.Shape.of(conf)

        @classmethod
        def kernel_ops(cls, name):
            return trace.kernel_ops(cls.ops, name)

    Run.peaks = peaks.lookup("TPU v5 lite")
    share = readers.codebook_roofline(Run)
    assert 0 < share < 100


def test_cpu_profile_reads_spans_and_no_device(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.serve_step"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    t = trace.load(path)
    assert not t.devices
    assert [s[0] for s in t.spans] == ["bench.window", "bench.serve_step"]
    lo, hi = trace.window(t)
    assert lo <= t.spans[1][1] and t.spans[1][2] <= hi


def _collective_trace():
    """Device 0: a fusion, an async all-reduce whose -start event
    encloses its -done, the fusion that reads its result, and a psum that
    XLA named for its op; device 1 only computes."""
    ops0 = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, "
             "calls=%fused_computation", 0, 10 * MS),
            ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} "
             "%fusion.1), channel_id=1, to_apply=%add", 10 * MS, 20 * MS),
            ("%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} "
             "%all-reduce-start.1)", 15 * MS, 20 * MS),
            ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.1), "
             "kind=kLoop, calls=%fused_computation.2", 20 * MS, 30 * MS),
            ("%psum.3 = f32[8]{0:T(128)} all-reduce(f32[8]{0} %fusion.2), "
             "channel_id=2, to_apply=%add", 30 * MS, 35 * MS)]
    ops1 = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 0, 70 * MS)]
    return trace.Trace([ops0, ops1], [("bench.window", 0, 100 * MS)])


def test_collectives_count_an_async_pair_once():
    r = trace.reduce(_collective_trace())
    assert r["busy_s"] == pytest.approx(0.0525)        # (35 + 70) / 2
    assert r["first_busy_s"] == pytest.approx(0.035)
    # [10, 20) for the pair (not 10 + 5), and the psum's [30, 35)
    assert r["collective_s"] == pytest.approx(0.015)
    run = types.SimpleNamespace(trace=r)
    assert readers.collective_share(run) == pytest.approx(100 * 15 / 35)


def test_no_collective_reads_nothing():
    r = trace.reduce(trace.load_saved(RECORDED))       # one chip
    assert r["collective_s"] == 0.0
    assert readers.collective_share(types.SimpleNamespace(trace=r)) is None
    assert readers.collective_share(types.SimpleNamespace(trace=None)) \
        is None


@pytest.mark.parametrize("text,want", [
    ("%psum.1 = bf16[32,4096]{1,0:T(8,128)(2,1)} all-reduce(%f), "
     "channel_id=1", True),
    ("%fusion = bf16[32,1024]{1,0} fusion(%fusion.3), kind=kCustom, "
     "calls=%all-reduce-scatter", True),
    ("%ag = (bf16[2]{0}, u32[]) all-gather-start(bf16[1]{0} %x)", True),
    ("%cp.2 = f32[4]{0} collective-permute-done(f32[4]{0} %cp.1)", True),
    ("%fusion.2 = bf16[16]{0} fusion(f32[16]{0} %all-reduce.3)", False),
    ("%all-reduce.3 = f32[4]{0} fusion(%x), calls=%fused_computation",
     False)])
def test_a_collective_is_known_by_its_opcode(text, want):
    assert trace.is_collective(text) is want
