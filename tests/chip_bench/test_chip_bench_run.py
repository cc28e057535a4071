"""Whole runs without the chip: the entry point refuses to measure a
CPU, and the harness's own run — set-up, window, metrics, the
reference's comparison — at a tiny size on the CPU comes out correct,
and not correct once a served token is altered where it is produced."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(HERE), str(pathlib.Path(__file__).resolve().parent)]

from chip_bench_fixtures import tiny_cell  # noqa: E402
from chipbench import harness  # noqa: E402


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _run(cwd, *args):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3-1.7b.chat", "--seed", "7", "--seconds", "1", "--trace", "0",
         *args], cwd=cwd, env=_env(), capture_output=True, text=True,
        timeout=300)


def test_no_tpu_means_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_are_not_a_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_cell(tmp_path_factory.mktemp("tiny"))


def _tiny_run(cell):
    return harness.run_cell(cell, 2 ** 31 + 5, 2.0, False,
                            time.perf_counter(), require_tpu=False)


def test_tiny_run_is_correct(tiny):
    res = _tiny_run(tiny)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert {"ttft_p95_s", "itl_p95_ms", "setup_s"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_an_altered_token_is_not_correct(tiny, monkeypatch):
    from repro.serving.engine import ServeEngine

    step = ServeEngine.serve_step

    def altered(self, st, quantum=1):
        toks, done = step(self, st, quantum)
        return {b: [(t + 1 + b) % 512 for t in ts]
                for b, ts in toks.items()}, done

    monkeypatch.setattr(ServeEngine, "serve_step", altered)
    res = _tiny_run(tiny)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_sweep_overrides_rate_and_schedule(tiny):
    """The knee sweep's overrides of the mix: the rate sets how many
    requests a window offers (floor(rate x seconds)), under another
    schedule seed; the run stays correct."""
    res = harness.run_cell(tiny, 9, 2.0, False, time.perf_counter(),
                           require_tpu=False, rate=2.0, schedule_seed=3)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 4 and res["failed"] == 0


def _gap(cell):
    samples = [([3, 1, 4, 1, 5, 9, 2, 6], [5, 3, 5, 8])]
    return harness.check(cell, 2 ** 31 + 5, samples, None)[1][
        "max_logit_gap"]["value"]


def test_a_configuration_names_its_reference(tmp_path):
    """A configuration's ``reference`` key picks the module that scores
    the cell, from the cell's own tree; with no key the harness's
    ``reference.py`` scores it."""
    import json

    from chipbench import spec

    cell = tiny_cell(tmp_path)
    plain = _gap(cell)
    assert 0 <= plain < 1000
    (cell.root / "chipbench").mkdir()
    (cell.root / "chipbench" / "reference_copy.py").write_text(
        (HERE / "chipbench" / "reference.py").read_text()
        + "\n\n_plain = served_gaps\n\n\n"
          "def served_gaps(*args, **kw):\n"
          "    got = _plain(*args, **kw)\n"
          "    got['served'] = [g + 1000.0 for g in got['served']]\n"
          "    return got\n")
    path = cell.root / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    path.write_text(json.dumps({**conf, "reference": "reference_copy"}))
    named = spec.load_cell(cell.name, False, repo=tmp_path, root=cell.root)
    assert _gap(named) == pytest.approx(plain + 1000.0)
    path.write_text(json.dumps({**conf, "reference": "../reference"}))
    with pytest.raises(ValueError):
        _gap(spec.load_cell(cell.name, False, repo=tmp_path,
                            root=cell.root))


def test_a_limits_file_names_the_numbers_compared(tmp_path, monkeypatch):
    """A limits file that names ``median_logit_gap`` beside
    ``max_logit_gap`` has both compared, each against its own limit: the
    served run passes both, and a run whose served tokens are altered
    where they are produced fails one."""
    import json

    from chipbench import spec
    from repro.serving.engine import ServeEngine

    cell = tiny_cell(tmp_path)
    path = cell.root / "limits" / f"{cell.name}.json"
    path.write_text(json.dumps({"sample_requests": 4, "max_logit_gap": 0.6,
                                "median_logit_gap": 0.3}))
    cell = spec.load_cell(cell.name, False, repo=tmp_path, root=cell.root)
    res = _tiny_run(cell)
    assert list(res["checks"]) == ["max_logit_gap", "median_logit_gap"]
    assert res["correct"], res["checks"]
    step = ServeEngine.serve_step

    def altered(self, st, quantum=1):
        toks, done = step(self, st, quantum)
        return {b: [(t + 1 + b) % 512 for t in ts]
                for b, ts in toks.items()}, done

    monkeypatch.setattr(ServeEngine, "serve_step", altered)
    res = _tiny_run(cell)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
