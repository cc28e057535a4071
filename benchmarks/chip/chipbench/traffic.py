"""The one traffic generator: every mix is a data file it reads.

A mix (``traffic/<mix>.json``) names its loop and its length laws:

    {"loop": "open", "rate_per_s": 0.5, "schedule_seed": 0,
     "prompt": {"median": 192, "sigma": 0.9, "min": 32, "max": 1024},
     "output": {"median": 96, "sigma": 0.8, "min": 16, "max": 384},
     "quantum": 1}

    {"loop": "closed", "clients": 32, "schedule_seed": 0,
     "prompt": {...}, "output": {...}, "quantum": 1}

Lengths are lognormal (``median``, ``sigma`` of the log), rounded and
clipped to [``min``, ``max``].  Open-loop gaps are exponential at
``rate_per_s`` (Poisson arrivals).  Every set of draws is stratified — the
i-th of n values sits at quantile (i + 0.5) / n — and ``schedule_seed``
permutes the sets: it fixes the schedule, which request comes when with
which lengths.  The run's seed draws the token ids (and, elsewhere, the
weights).  The schedule is the mix's and not the run's because on these
cells the order alone moved a window's time-to-first-token tail by 64 %
between seeds, against 2 % between two runs of one order (PERF.md): a
run seed that reorders would make the tail a reading of the order.  A
closed loop draws one stratified set of ``clients`` requests per wave:
the k-th request of every client comes from wave k.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    due: float          # open loop: seconds after the window opens
    prompt: tuple       # token ids
    max_new: int


def stratified(n: int) -> np.ndarray:
    """Quantiles (i + 0.5) / n, i < n."""
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(law: dict, n: int) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf(q) for q in stratified(n)])
    x = law["median"] * np.exp(law["sigma"] * z)
    return np.clip(np.round(x), law["min"], law["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n stratified exponential gaps, scaled so that their mean is exactly
    1 / rate."""
    g = -np.log1p(-stratified(n))
    return g / g.mean() / rate


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed), *stream])


def _requests(order, ids, mix: dict, n: int, vocab: int, dues) -> list:
    plens = order.permutation(lognormal_lengths(mix["prompt"], n))
    outs = order.permutation(lognormal_lengths(mix["output"], n))
    return [Request(float(d), tuple(int(t) for t in
                                    ids.integers(0, vocab, int(p))), int(o))
            for d, p, o in zip(dues, plens, outs)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests due in a window of ``seconds``: floor(rate x seconds)
    of them, the first due at 0 and every one before ``seconds``."""
    n = max(1, int(mix["rate_per_s"] * seconds))
    order = _rng(mix["schedule_seed"], 0)
    gaps = order.permutation(exponential_gaps(mix["rate_per_s"], n))
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return _requests(order, _rng(seed, 0), mix, n, vocab, dues)


def closed_wave(mix: dict, seed: int, wave: int, vocab: int) -> list:
    """Wave ``wave`` of a closed loop: one request per client."""
    n = int(mix["clients"])
    return _requests(_rng(mix["schedule_seed"], 1, wave), _rng(seed, 1, wave),
                     mix, n, vocab, [0.0] * n)


def max_total_len(mix: dict) -> int:
    """The longest prompt plus the longest answer the mix can draw."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])


def check_mix(mix: dict) -> None:
    if not isinstance(mix.get("schedule_seed"), int):
        raise ValueError("a mix needs an integer schedule_seed")
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"mix loop {mix.get('loop')!r} is not open/closed")
    key = "rate_per_s" if mix["loop"] == "open" else "clients"
    if not mix.get(key, 0) > 0:
        raise ValueError(f"a {mix['loop']} loop needs {key} > 0")
    for law in ("prompt", "output"):
        d = mix[law]
        if not (1 <= d["min"] <= d["median"] <= d["max"]
                and d["sigma"] >= 0 and math.isfinite(d["sigma"])):
            raise ValueError(f"bad {law} law {d}")
