"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the repository root lists configurations, cells
(workloads) and metrics; each piece lives in a file of its own beside the
harness, so a later change adds files and entries and edits nothing:

    benchmarks/chip/configs/<config>.json    model, cut and engine settings
    benchmarks/chip/traffic/<mix>.json       loop, rate or clients, lengths
    benchmarks/chip/limits/<cell>.json       what `correct` compares
    benchmarks/chip/metrics/<metric>.py      one reader per metric
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]      # benchmarks/chip
REPO = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    mix_name: str
    mix: dict
    limits: dict
    metrics: tuple        # BENCHMARK.json metric entries this run reports
    root: pathlib.Path    # the benchmarks/chip tree its files came from


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_benchmark(repo: pathlib.Path = REPO) -> dict:
    return _json(repo / "BENCHMARK.json")


def metrics_for(bench: dict, cell: str, trace: bool) -> tuple:
    """The end-to-end metrics (``trace`` false) or per-layer metrics
    (``trace`` true) that ``cell`` reports: those that list it under
    ``workloads``, and those with no such list."""
    group = bench["per_layer" if trace else "end_to_end"]
    return tuple(m for m in group if cell in m.get("workloads", (cell,)))


def load_cell(name: str, trace: bool, repo: pathlib.Path = REPO,
              root: pathlib.Path = HERE) -> Cell:
    bench = load_benchmark(repo)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_json(repo / configs[w["config"]]["file"]),
        mix_name=w["traffic"], mix=_json(root / "traffic" /
                                         f"{w['traffic']}.json"),
        limits=_json(root / "limits" / f"{name}.json"),
        metrics=metrics_for(bench, name, trace), root=root)


def reader(metric: str, root: pathlib.Path = HERE):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = root / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
