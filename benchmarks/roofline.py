"""§Roofline: three-term analysis per (arch × shape × mesh) from the
dry-run artifacts (see launch/dryrun.py for how the numbers are produced
and trip-count-corrected).

Terms (seconds/step/device — TPU v5e):
    compute    = flops_per_device / 197e12        (bf16 MXU peak)
    memory     = bytes_per_device / 819e9         (HBM bandwidth)
    collective = coll_bytes_per_device / 50e9     (per-link ICI bandwidth)

MODEL_FLOPS = 6·N·D (train, dense) / 6·N_active·D (MoE) / 2·N·tokens
(decode); the useful-compute ratio MODEL_FLOPS / (chips · flops_per_device)
flags remat/dispatch waste.
"""

from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


def _param_counts(arch: str):
    import repro.configs as C
    from repro.models.model_zoo import build
    from repro.launch.steps import abstract_params
    cfg = C.get(arch)
    params = abstract_params(build(cfg))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    total = emb = expert = 0
    for kp, v in leaves:
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        n = 1
        for s in v.shape:
            n *= s
        total += n
        if "embed" in path or "lm_head" in path:
            emb += n
        if "/moe/w" in "/" + path:
            expert += n
    n_body = total - emb
    if cfg.n_experts:
        active = n_body - expert + expert * cfg.top_k / cfg.n_experts
    else:
        active = n_body
    return total, n_body, active, cfg


def model_flops(arch: str, shape: dict):
    total, n_body, active, cfg = _param_counts(arch)
    toks = shape["global_batch"] * shape["seq_len"]
    if shape["kind"] == "train":
        return 6.0 * active * toks
    if shape["kind"] == "prefill":
        return 2.0 * active * toks
    return 2.0 * active * shape["global_batch"]   # decode: 1 new token/seq


def analyse(rec: dict) -> dict:
    from repro.configs.base import SHAPES
    import dataclasses
    if rec.get("status") != "ok":
        return rec
    corr = rec.get("corrected") or {}
    flops = corr.get("flops", rec["flops_per_device"])
    bts = corr.get("bytes", rec["bytes_per_device"])
    coll = corr.get("coll",
                    rec["collectives_per_device"].get("total", 0))
    t_c = flops / PEAK_FLOPS
    t_m = bts / HBM_BW
    t_x = coll / LINK_BW
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dominant = max(terms, key=terms.get)
    sh = dataclasses.asdict(SHAPES[rec["shape"]])
    mf = model_flops(rec["arch"], sh)
    useful = mf / max(flops * rec["n_devices"], 1.0)
    # roofline fraction: useful model compute versus the time the dominant
    # term pins the step to, at peak
    bound_s = max(terms.values())
    frac = (mf / rec["n_devices"] / PEAK_FLOPS) / max(bound_s, 1e-30)
    return {**rec, "terms": terms, "dominant": dominant,
            "model_flops": mf, "useful_ratio": useful,
            "roofline_fraction": frac}


def run(results_dir: str = "dryrun_results"):
    rows = []
    for f in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        rec = json.load(open(f))
        if rec.get("status") == "skipped":
            rows.append(("roofline", rec["cell"], "SKIP:" + rec["reason"]))
            continue
        if rec.get("status") != "ok":
            rows.append(("roofline", rec.get("cell", f), "ERROR"))
            continue
        a = analyse(rec)
        t = a["terms"]
        rows.append(("roofline", a["cell"],
                     f"compute={t['compute_s'] * 1e3:.2f}ms "
                     f"memory={t['memory_s'] * 1e3:.2f}ms "
                     f"collective={t['collective_s'] * 1e3:.2f}ms "
                     f"dom={a['dominant'].split('_')[0]} "
                     f"useful={a['useful_ratio']:.2f} "
                     f"roofline_frac={a['roofline_fraction']:.2f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(r))
