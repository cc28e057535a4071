"""BENCHMARK.json keeps to the benchmark's contract, and a cell, a mix, a
configuration or a metric added as new files and entries is found with no
edit to the harness — a metric of a kernel the harness names nowhere
too."""

import json
import pathlib
import re
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(HERE)]

from chipbench import counts, harness, spec, trace  # noqa: E402

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "tiny_trace.json.gz"

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p and not p.startswith("/")
    names = [c["name"] for c in BENCH["configs"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    assert len(set(metrics)) == len(metrics)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["why"]) and _line(c["source"])
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])


def test_metrics_keep_to_the_contract():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])
    for w in BENCH["workloads"]:
        for trace in (False, True):
            ms = spec.metrics_for(BENCH, w["name"], trace)
            assert ms and all((HERE / "metrics" / f"{m['name']}.py").is_file()
                              for m in ms)
        assert "setup_s" in {m["name"] for m in spec.metrics_for(
            BENCH, w["name"], False)}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.load_cell(cell, False)
    assert c.config["engine"]["backend"] == "codebook"
    compared = [c.limits[k] for k in harness.GAP_NUMBERS if k in c.limits]
    assert compared and min(compared) > 0 and c.limits["sample_requests"] > 0


def test_new_cell_mix_config_and_metric_are_found_as_files(tmp_path):
    repo, root = tmp_path, tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    shutil.copy(root / "configs" / "qwen3-1.7b.json",
                root / "configs" / "qwen3-1.7b-b32.json")
    bench["configs"].append({**bench["configs"][0], "name": "qwen3-1.7b-b32",
                             "file": "benchmarks/chip/configs/"
                                     "qwen3-1.7b-b32.json"})
    (root / "traffic" / "bursty.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 2.0, "quantum": 1, "schedule_seed": 0,
         "prompt": {"median": 64, "sigma": 0.5, "min": 16, "max": 256},
         "output": {"median": 32, "sigma": 0.5, "min": 8, "max": 64}}))
    (root / "limits" / "qwen3-1.7b-b32.bursty.json").write_text(
        json.dumps({"sample_requests": 2, "max_logit_gap": 1.0}))
    (root / "metrics" / "rounds.count.bursty.py").write_text(
        "def read(run):\n    return float(len(run.spans))\n")
    # a kernel no file of the harness names: the reader picks its ops
    (root / "metrics" / "page_gather.device_ms.bursty.py").write_text(
        "def read(run):\n"
        "    ops = run.kernel_ops('page_gather_pallas')\n"
        "    return sum(s for _, s in ops) * 1e3 if ops else None\n")
    bench["workloads"].append({"name": "qwen3-1.7b-b32.bursty",
                               "config": "qwen3-1.7b-b32",
                               "traffic": "bursty", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "rounds.count.bursty", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "serving/engine.py",
                               "moves": "ttft_p95_s",
                               "workloads": ["qwen3-1.7b-b32.bursty"]})
    bench["per_layer"].append({"name": "page_gather.device_ms.bursty",
                               "unit": "ms", "better": "lower",
                               "source": "device_trace",
                               "layer": "kernels/page_gather.py",
                               "moves": "ttft_p95_s",
                               "workloads": ["qwen3-1.7b-b32.bursty"]})
    (repo / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("qwen3-1.7b-b32.bursty", True, repo=repo, root=root)
    assert cell.mix["rate_per_s"] == 2.0
    assert cell.config_name == "qwen3-1.7b-b32"
    assert [m["name"] for m in cell.metrics] == [
        "rounds.count.bursty", "page_gather.device_ms.bursty"]
    run = harness.Run(
        cell=cell, shape=counts.Shape.of(cell.config), peaks=None,
        page_size=16, seconds=1.0, t_open=0.0, t_close=1.0, t_drained=1.0,
        records=[], spans=[1, 2, 3], setup_s=1.0, memory_peak_bytes=0,
        trace=trace.reduce(trace.load_saved(RECORDED)))
    got = harness.measure(run)
    assert got["rounds.count.bursty"] == {"value": 3.0, "unit": "1"}
    assert got["page_gather.device_ms.bursty"]["value"] > 0
    # without a trace the kernel's reader finds nothing and is left out
    run.trace = None
    assert set(harness.measure(run)) == {"rounds.count.bursty"}
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell", False, repo=repo, root=root)


def test_step_mfu_divides_by_the_cells_chips():
    """The same window's FLOPs over four chips' peak read a quarter of
    what they read over one."""
    import dataclasses

    from chipbench import loadgen, peaks, readers

    cell = spec.load_cell("mistral-large-123b-l3.longgen", True)
    spans = [loadgen.Span("serve_step", 0.1 * i, 0.1 * i + 0.12, {
        "tokens": 32, "ctx_sum": 32 * 400 + 528, "iterations": 1,
        "live": 32, "batch": 32}) for i in range(8)]
    spans.append(loadgen.Span("sched_admit", 0.9, 1.0, {
        "prompt_len": 100, "chunks": 7}))

    def mfu(chips):
        run = harness.Run(
            cell=dataclasses.replace(cell, chips=chips),
            shape=counts.Shape.of(cell.config),
            peaks=peaks.lookup("TPU v5 lite"), page_size=16, seconds=1.0,
            t_open=0.0, t_close=1.0, t_drained=1.0, records=[],
            spans=spans, setup_s=1.0, memory_peak_bytes=0, trace=None)
        return readers.step_mfu(run)

    assert mfu(1) > 0
    assert mfu(4) == pytest.approx(mfu(1) / 4, rel=1e-12)
