"""Small cells for the chip benchmark's CPU tests, and the recorder of the
checked-in TPU trace they reduce.

    python3 tests/chip_bench/chip_bench_fixtures.py   # on a TPU host

records ``data/tiny_trace.json.gz``: the harness's own traced run of the
tiny cell for 0.25 s, the trace kept as it loads it.
"""

import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "tiny_trace.json.gz"
CELL = "tiny.chat"
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, intermediate_size=256, vocab_size=512,
            num_hidden_layers=2)


def tiny_cell(repo: pathlib.Path, chips: int = 1,
              engine: dict | None = None):
    """A cell of a 2-layer, width-128 qwen3 under a small open loop on
    ``chips`` devices, written as files under ``repo`` and loaded from
    there; ``engine`` adds to the configuration's engine block."""
    if str(HERE) not in sys.path:
        sys.path[:0] = [str(HERE)]
    from chipbench import spec

    root = repo / "benchmarks" / "chip"
    for d in ("configs", "traffic", "limits"):
        (root / d).mkdir(parents=True)
    shutil.copytree(HERE / "metrics", root / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((HERE / "configs" / "qwen3-1.7b.json").read_text())
    conf["hf"].update(TINY)
    conf["program"] = {"registry": "qwen3-1.7b", "replace": dict(
        n_layers=2, d_model=128, n_heads=4, n_kv=2, head_dim=32, d_ff=256,
        vocab=512)}
    conf["assumed"]["padded_vocab"] = 512
    conf["engine"].update(max_batch=4, max_len=128, **(engine or {}))
    (root / "configs" / "tiny.json").write_text(json.dumps(conf))
    (root / "traffic" / "tinychat.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 4.0, "quantum": 1, "schedule_seed": 0,
        "prompt": {"median": 30, "sigma": 0.5, "min": 8, "max": 80},
        "output": {"median": 10, "sigma": 0.5, "min": 4, "max": 40}}))
    limits = json.loads((HERE / "limits" / "qwen3-1.7b.chat.json")
                        .read_text())
    (root / "limits" / f"{CELL}.json").write_text(json.dumps(
        {**limits, "sample_requests": 4}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "x", "reduced": [],
                         "why": "x",
                         "file": "benchmarks/chip/configs/tiny.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny",
                           "traffic": "tinychat", "chips": chips,
                           "why": "x"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (repo / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load_cell(CELL, False, repo=repo, root=root)


def record(out: pathlib.Path = RECORDED, seed: int = 11) -> None:
    import tempfile

    sys.path[:0] = [str(ROOT / "src")]
    from chipbench import harness, spec, trace

    cell = tiny_cell(pathlib.Path(tempfile.mkdtemp()))
    cell = spec.load_cell(CELL, True, repo=cell.root.parents[1],
                          root=cell.root)
    load = trace.load

    def load_and_keep(path):
        t = load(path)
        trace.save(t, out)
        return t

    trace.load = load_and_keep
    try:
        harness.run_cell(cell, seed, 0.25, True, time.perf_counter())
    finally:
        trace.load = load
    print(f"wrote {out}")


if __name__ == "__main__":
    record()
