"""Paper Fig. 2: fitting a parabola with 2 hidden units under tanhD(L)."""

from __future__ import annotations

from functools import partial

import jax

from benchmarks._common import train_regressor
from repro.data.synthetic import parabola_batch
from repro.models import papernets as PN


def run(steps=600):
    rows = []
    for label, kind, levels in [("tanh", "tanh", 0), ("relu6", "relu6", 0),
                                ("tanhD(2)", "tanh", 2),
                                ("tanhD(8)", "tanh", 8),
                                ("tanhD(256)", "tanh", 256)]:
        init = lambda k: PN.mlp_init(k, 1, [2], 1)
        apply = partial(_apply, kind)
        _, _, mse = train_regressor(init, apply, parabola_batch,
                                    steps=steps, lr=2e-2, act_levels=levels)
        rows.append(("fig2_parabola", label, f"{mse:.5f}"))
    return rows


def _apply(kind, p, x, act_levels):
    return PN.mlp_apply(p, x, kind, act_levels)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(r))
