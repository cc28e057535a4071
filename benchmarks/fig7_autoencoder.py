"""Paper Fig. 7: auto-encoding (real-valued regression) under both
quantizations — FC and conv architectures, relative-to-baseline L2."""

from __future__ import annotations

from functools import partial

from benchmarks._common import train_regressor
from repro.data.synthetic import smooth_images
from repro.models import papernets as PN


def run(steps=300):
    rows = []
    # --- FC auto-encoder -----------------------------------------------------
    data_fc = lambda s: {"x": smooth_images(s, 16, 16).get("x").reshape(16, -1)}
    grid = [("tanh", 0, 0), ("relu6", 0, 0), ("tanhD(32)", 32, 0),
            ("tanhD(256)", 256, 0), ("tanh |W|=100", 0, 100),
            ("tanh |W|=1000", 0, 1000), ("tanhD(32) |W|=1000", 32, 1000)]
    base = None
    for label, levels, nw in grid:
        kind = "relu6" if label.startswith("relu") else "tanh"
        init = lambda k: PN.fc_autoencoder_init(k, 16 * 16 * 3, n=0.5)
        ap = lambda p, x, lv: PN.fc_autoencoder_apply(p, x, kind, lv)
        _, _, mse = train_regressor(init, ap, data_fc, steps=steps,
                                    act_levels=levels, n_weights=nw,
                                    cluster_every=80)
        if base is None:
            base = mse
        rows.append(("fig7_fc_ae", label, f"{mse / base:.3f}"))
    # --- conv auto-encoder ---------------------------------------------------
    data_cv = lambda s: smooth_images(s, 8, 32)
    base = None
    for label, levels, nw in [("tanh", 0, 0), ("tanhD(32)", 32, 0),
                              ("tanh |W|=1000", 0, 1000),
                              ("tanhD(32) |W|=1000", 32, 1000)]:
        init = lambda k: PN.conv_autoencoder_init(k, n=0.5)
        ap = lambda p, x, lv: PN.conv_autoencoder_apply(p, x, "tanh", lv)
        _, _, mse = train_regressor(init, ap, data_cv, steps=steps,
                                    act_levels=levels, n_weights=nw,
                                    cluster_every=80)
        if base is None:
            base = mse
        rows.append(("fig7_conv_ae", label, f"{mse / base:.3f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(r))
