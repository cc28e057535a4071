"""Finding a cell's pieces by name.

``BENCHMARK.json`` at the repository root lists configurations, cells
(workloads) and metrics; each piece lives in a file of its own beside the
harness, so a later change adds files and entries and edits nothing:

    benchmarks/chip/configs/<config>.json    model, cut and engine settings
    benchmarks/chip/traffic/<mix>.json       loop, rate or clients, lengths
    benchmarks/chip/limits/<cell>.json       what `correct` compares
    benchmarks/chip/metrics/<metric>.py      one reader per metric
    benchmarks/chip/chipbench/<module>.py    a configuration's reference,
                                             named by its "reference" key
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

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
    cell = Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_json(repo / configs[w["config"]]["file"]),
        mix_name=w["traffic"], mix=_json(root / "traffic" /
                                         f"{w['traffic']}.json"),
        limits=_json(root / "limits" / f"{name}.json"),
        metrics=metrics_for(bench, name, trace), root=root)
    engine_settings(cell)
    return cell


def engine_settings(cell: Cell) -> tuple:
    """(tp, the ``ServeEngine`` keyword arguments) of the cell's
    ``engine`` block.  ``tp`` is None where the block states none (one
    device, no mesh); where it does, it has to be the cell's ``chips``."""
    kw = dict(cell.config["engine"])
    tp = kw.pop("tp", None)
    if tp is not None and (isinstance(tp, bool) or tp != cell.chips):
        raise ValueError(f"cell {cell.name}: engine tp {tp!r} is not the "
                         f"cell's {cell.chips} chips")
    return tp, kw


def reader(metric: str, root: pathlib.Path = HERE):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return _load(root / "metrics" / f"{metric}.py", "chipbench_metric_"
                 + metric.replace(".", "_").replace("-", "_")).read


def reference(cell: Cell):
    """The plain reference that scores the cell: the module its
    configuration names under ``reference`` (default ``reference``), from
    ``chipbench/`` of the cell's tree where it is there, else from the
    harness's own."""
    name = cell.config.get("reference", "reference")
    if not name.isidentifier():
        raise ValueError(f"configuration {cell.config_name}: reference "
                         f"{name!r} is not a module name")
    path = cell.root / "chipbench" / f"{name}.py"
    if not path.is_file():
        path = HERE / "chipbench" / f"{name}.py"
    return _load(path, f"chipbench_reference_{name}")


def _load(path: pathlib.Path, module: str):
    """The module of ``path``, under the name ``module`` (kept in
    ``sys.modules``, as dataclasses need; loaded again where the name
    was another file's)."""
    mod = sys.modules.get(module)
    if mod is not None and mod.__file__ == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[module]
        raise
    return mod
