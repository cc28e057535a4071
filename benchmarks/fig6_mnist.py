"""Paper Fig. 6: MNIST-style classification — activation levels × |W| ×
hidden width (pseudo-MNIST; offline container)."""

from __future__ import annotations

from functools import partial

from benchmarks._common import recall_at, train_classifier
from repro.data.synthetic import pseudo_mnist_batch
from repro.models import papernets as PN


def _apply(kind, p, x, act_levels, key):
    return PN.mlp_apply(p, x, kind, act_levels)


def run(steps=250):
    rows = []
    grid = [
        ("tanh", 0, 0), ("relu6", 0, 0),
        ("tanhD(8)", 8, 0), ("tanhD(32)", 32, 0),
        ("tanh |W|=100", 0, 100), ("tanh |W|=1000", 0, 1000),
        ("tanhD(32) |W|=100", 32, 100), ("tanhD(32) |W|=1000", 32, 1000),
    ]
    data = lambda s: pseudo_mnist_batch(s, 64, noise=0.45)
    data_eval = lambda s: pseudo_mnist_batch(s, 128, noise=0.45)
    for hidden in (4, 16):
        for label, levels, nw in grid:
            kind = "relu6" if label.startswith("relu") else "tanh"
            init = lambda k: PN.mlp_init(k, 784, [hidden, hidden], 10)
            params, _, _ = train_classifier(
                init, partial(_apply, kind), data,
                steps=steps, act_levels=levels, n_weights=nw,
                cluster_every=60)
            acc = recall_at(partial(_apply, kind), data_eval,
                            params, levels)[1]
            rows.append(("fig6_mnist", f"h{hidden} {label}", f"{acc:.4f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(r))
