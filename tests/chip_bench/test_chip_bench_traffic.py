"""The traffic generator: deterministic per seed, one schedule per mix
whatever the run's seed, and stratified draws of the stated laws."""

import json
import pathlib
import statistics
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmarks" / "chip")]

from chipbench import traffic  # noqa: E402

MIXES = ROOT / "benchmarks" / "chip" / "traffic"
CHAT = json.loads((MIXES / "chat.json").read_text())
LONG = json.loads((MIXES / "longgen.json").read_text())
SEED = 2 ** 31 + 12345          # seeds past 32 signed bits are valid


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(CHAT, SEED, 51, 151936)
    b = traffic.open_loop(CHAT, SEED, 51, 151936)
    assert a == b
    c = traffic.open_loop(CHAT, SEED + 1, 51, 151936)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_every_seed_offers_the_mix_schedule_with_its_own_tokens():
    a = traffic.open_loop(CHAT, 1, 51, 151936)
    b = traffic.open_loop(CHAT, 2, 51, 151936)
    def shape(rs):
        return [(r.due, len(r.prompt), r.max_new) for r in rs]

    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert len(a) == int(CHAT["rate_per_s"] * 51)
    assert max(r.due for r in a) < 51
    c = traffic.open_loop({**CHAT, "schedule_seed": 1}, 1, 51, 151936)
    assert shape(c) != shape(a)
    assert sorted(len(r.prompt) for r in c) == sorted(len(r.prompt) for r in a)
    assert sorted(r.due for r in c)[-1] < 51


def test_lengths_follow_the_stated_lognormal():
    law = CHAT["prompt"]
    xs = traffic.lognormal_lengths(law, 4001)
    assert statistics.median(xs) == law["median"]
    assert xs.min() == law["min"] and xs.max() == law["max"]
    inner = xs[(xs > law["min"]) & (xs < law["max"])]
    # the log of the unclipped draws has the stated sigma: its quartiles
    # sit at median * exp(+-0.6745 sigma)
    q1, q3 = np.percentile(xs, [25, 75])
    assert q1 == pytest.approx(law["median"] * np.exp(-0.6745 * law["sigma"]),
                               rel=0.01)
    assert q3 == pytest.approx(law["median"] * np.exp(0.6745 * law["sigma"]),
                               rel=0.01)
    assert len(inner) > 0.9 * len(xs)


def test_open_loop_gaps_are_exponential_at_the_rate():
    g = traffic.exponential_gaps(0.5, 1000)
    assert g.mean() == pytest.approx(2.0)
    assert np.median(g) == pytest.approx(2.0 * np.log(2), rel=0.01)


def test_closed_waves_draw_one_request_per_client():
    w0 = traffic.closed_wave(LONG, SEED, 0, 32768)
    w1 = traffic.closed_wave(LONG, SEED, 1, 32768)
    assert len(w0) == LONG["clients"] == 32
    assert w0 == traffic.closed_wave(LONG, SEED, 0, 32768)
    other = traffic.closed_wave(LONG, SEED + 1, 0, 32768)
    assert [r.max_new for r in other] == [r.max_new for r in w0]
    assert [r.prompt for r in other] != [r.prompt for r in w0]
    assert sorted(r.max_new for r in w0) == sorted(r.max_new for r in w1)
    assert [r.max_new for r in w0] != [r.max_new for r in w1]
    assert all(LONG["output"]["min"] <= r.max_new <= LONG["output"]["max"]
               for r in w0)
    assert all(0 <= t < 32768 for r in w0 for t in r.prompt)


@pytest.mark.parametrize("mix", ["chat", "longgen"])
def test_shipped_mixes_are_valid(mix):
    m = json.loads((MIXES / f"{mix}.json").read_text())
    traffic.check_mix(m)
    assert traffic.max_total_len(m) >= m["prompt"]["max"]


def test_a_bad_mix_is_refused():
    with pytest.raises(ValueError):
        traffic.check_mix({**CHAT, "loop": "sideways"})
    with pytest.raises(ValueError):
        traffic.check_mix({**CHAT, "rate_per_s": 0})
    with pytest.raises(ValueError):
        traffic.check_mix({k: v for k, v in CHAT.items()
                           if k != "schedule_seed"})
