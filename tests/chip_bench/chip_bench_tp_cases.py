"""Child-side cases of the chip benchmark's tensor-parallel tests.

``tests/tp_rig.py`` runs them in a subprocess under four forced host
devices (the main test process keeps its single device), so each takes
and returns JSON-serialisable data only.
"""

import pathlib
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "benchmarks" / "chip"), str(HERE)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chip_bench_fixtures import tiny_cell  # noqa: E402
from chipbench import harness, weights  # noqa: E402

SEED = 2 ** 31 + 77


def sharded_weights(registry: str = "mistral-large-123b") -> dict:
    """The reduced model's weights made with the serving placement over
    four devices, against the same made on one: every leaf's values, and
    which stacked ``w_idx`` leaves some device holds whole."""
    import repro.configs as C
    from repro.models.model_zoo import build

    model = build(C.get(registry).reduced().replace(dtype="bfloat16"))
    tree = weights.serving_tree(model, 1000)
    _, shardings = harness._tp_layout(4, tree)
    one = weights.make_params(tree, SEED, 1000, 0.02)
    four = weights.make_params(tree, SEED, 1000, 0.02, shardings)
    differ, whole, stacked = [], [], 0
    for (kp, a), b in zip(jax.tree_util.tree_flatten_with_path(one)[0],
                          jax.tree_util.tree_leaves(four)):
        path = weights.leaf_path(kp)
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            differ.append(path)
        if path.startswith("blocks/") and path.endswith("w_idx"):
            stacked += 1
            if any(s.data.shape == b.shape for s in b.addressable_shards):
                whole.append(path)
    return {"differ": differ, "whole": whole, "stacked": stacked,
            "devices": len({d for x in jax.tree_util.tree_leaves(four)
                            for d in x.devices()})}


def _record_meshes():
    """Note the mesh of every ``ServeEngine`` the harness builds."""
    import repro.serving as serving

    meshes = []

    class Recorded(serving.ServeEngine):
        def __post_init__(self):
            meshes.append(None if self.mesh is None
                          else dict(self.mesh.shape))
            super().__post_init__()

    serving.ServeEngine = Recorded
    return meshes


def tp_run(seed: int = SEED) -> dict:
    """A tiny ``tp: 4`` cell through the harness's whole run, as served,
    then with every served token altered where it is produced."""
    from repro.serving.engine import ServeEngine

    cell = tiny_cell(pathlib.Path(tempfile.mkdtemp()), chips=4,
                     engine={"tp": 4})
    meshes = _record_meshes()
    out = {}
    res = harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                           require_tpu=False)
    out["served"] = {k: res[k] for k in ("correct", "attempted", "failed",
                                         "checks")}
    out["served"]["count"] = res["device"]["count"]
    step = ServeEngine.serve_step

    def altered(self, st, quantum=1):
        toks, done = step(self, st, quantum)
        return {b: [(t + 1 + b) % 512 for t in ts]
                for b, ts in toks.items()}, done

    ServeEngine.serve_step = altered
    try:
        res = harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                               require_tpu=False)
    finally:
        ServeEngine.serve_step = step
    out["altered"] = {k: res[k] for k in ("correct", "checks")}
    out["meshes"] = meshes
    return out
