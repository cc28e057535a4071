"""Paper Table 1: AlexNet experiments, CPU-scaled.

Width-scaled AlexNet on synthetic 200-class images.  Rows mirror the
paper's experiment numbers: #0 ReLU baseline, #1 ReLU6, #3 |A|=32,
#5 |A|=8, #6 k-means |W|=1000 (2% subsample, no dropout), #7 k-means
|W|=100, #9 Laplacian |W|=1000 (no dropout).  The "quantized inputs"
column quantizes pixels to |A| levels (paper's rightmost columns).
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp

from benchmarks._common import recall_at, train_classifier
from repro.core.activations import quantize_input
from repro.data.synthetic import class_images
from repro.models import papernets as PN

N_CLASSES = 200
IMG = 32
WIDTH = 0.25


def _apply(kind, qin, p, x, act_levels, key):
    if qin and act_levels:
        x = quantize_input(x, act_levels, -1.0, 1.0)
    return PN.alexnet_apply(p, x, kind, act_levels, dropout_rate=0.0,
                            key=None)


def _data(s, batch=32):
    return class_images(s, batch=batch, side=IMG, n_classes=N_CLASSES)


def run(steps=400):
    rows = []
    exps = [
        ("#0 relu",            "relu",  0,   0,    None,          False),
        ("#1 relu6",           "relu6", 0,   0,    None,          False),
        ("#3 |A|=32",          "relu6", 32,  0,    None,          False),
        ("#5 |A|=8",           "relu6", 8,   0,    None,          False),
        ("#6 kmeans2% |W|=1000", "relu6", 32, 1000, "kmeans",     False),
        ("#7 kmeans2% |W|=100", "relu6", 32, 100,  "kmeans",      False),
        ("#9 laplacian |W|=1000", "relu6", 32, 1000, "laplacian_l1", False),
        ("#3q |A|=32 qin",     "relu6", 32,  0,    None,          True),
        ("#9q lap |W|=1000 qin", "relu6", 32, 1000, "laplacian_l1", True),
    ]
    for label, kind, levels, nw, method, qin in exps:
        init = lambda k: PN.alexnet_init(k, N_CLASSES, WIDTH, img=IMG)
        params, _, _ = train_classifier(
            init, partial(_apply, kind, qin), _data, steps=steps,
            lr=1e-3, act_levels=levels, n_weights=nw,
            cluster_every=100, method=method or "kmeans",
            subsample=0.02 if method == "kmeans" else 1.0)
        rec = recall_at(partial(_apply, kind, qin), _data, params, levels)
        rows.append(("table1_alexnet", label,
                     f"r@1={rec[1]:.3f} r@5={rec[5]:.3f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(r))
