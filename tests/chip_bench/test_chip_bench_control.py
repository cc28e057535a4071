"""The control: the plain reference computed one precision step below
the configuration's bf16 — every contraction's operands in float8 e4m3 —
put in the program's place, comes out not correct where the program
comes out correct.  On the chip this was read at the cells' own sizes
(PERF.md gives the readings); here at a size a CPU test holds: a 4-layer,
width-512 qwen3 with a 16384-row vocabulary, where the program's widest
gap read 0.005-0.010 and the control's 0.062-0.167 on six seeds, so the
limit of this size sits at 0.03."""

import json
import pathlib
import shutil
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path[:0] = [str(HERE)]

from chipbench import harness, spec  # noqa: E402

CELL = "mid.chat"
LIMIT = 0.03


@pytest.fixture(scope="module")
def mid(tmp_path_factory):
    repo = tmp_path_factory.mktemp("mid")
    root = repo / "benchmarks" / "chip"
    for d in ("configs", "traffic", "limits"):
        (root / d).mkdir(parents=True)
    shutil.copytree(HERE / "metrics", root / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.loads((HERE / "configs" / "qwen3-1.7b.json").read_text())
    conf["hf"].update(hidden_size=512, num_attention_heads=8,
                      num_key_value_heads=4, head_dim=64,
                      intermediate_size=1536, vocab_size=16384,
                      num_hidden_layers=4)
    conf["program"] = {"registry": "qwen3-1.7b", "replace": dict(
        n_layers=4, d_model=512, n_heads=8, n_kv=4, head_dim=64, d_ff=1536,
        vocab=16384)}
    conf["assumed"]["padded_vocab"] = 16384
    conf["engine"].update(max_batch=4, max_len=256)
    (root / "configs" / "mid.json").write_text(json.dumps(conf))
    (root / "traffic" / "midchat.json").write_text(json.dumps({
        "loop": "open", "rate_per_s": 2.0, "quantum": 1, "schedule_seed": 0,
        "prompt": {"median": 30, "sigma": 0.5, "min": 8, "max": 80},
        "output": {"median": 80, "sigma": 0.3, "min": 40, "max": 150}}))
    (root / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"sample_requests": 4, "max_logit_gap": LIMIT}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "mid", "source": "x", "reduced": [],
                         "why": "x",
                         "file": "benchmarks/chip/configs/mid.json"}]
    bench["workloads"] = [{"name": CELL, "config": "mid",
                           "traffic": "midchat", "chips": 1, "why": "x"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (repo / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load_cell(CELL, False, repo=repo, root=root)


def test_fp8_control_fails_where_the_program_passes(mid):
    res = harness.run_cell(mid, 5, 3.0, False, time.perf_counter(),
                           require_tpu=False, control="fp8")
    prog = res["checks"]["max_logit_gap"]
    ctl = res["checks"]["control_fp8_max_logit_gap"]
    assert prog["value"] <= prog["limit"] == LIMIT, res["checks"]
    assert ctl["value"] > ctl["limit"] == LIMIT, res["checks"]
    # the harness's own verdict: the control in the program's place fails
    assert not res["correct"], res
