"""Paper §4 memory table: packed / entropy-coded model size on really
trained + clustered networks, plus the A×W table overhead and the LUT-vs-
matmul CPU timing (the paper's lookups-vs-multiplies claim; inverted on
TPU, DESIGN.md §2)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._common import timer, train_classifier
from repro.core import clustering, fixedpoint as fp
from repro.core.activations import ActQuantConfig
from repro.core.export import memory_report
from repro.core.lut import LutConfig, build_tables
from repro.core.quantizer import codebook_indices
from repro.data.synthetic import pseudo_mnist_batch
from repro.kernels import ops
from repro.models import papernets as PN


def _apply(p, x, act_levels, key):
    return PN.mlp_apply(p, x, "tanh", act_levels)


def run(steps=250):
    rows = []
    # train a real clustered net so the index distribution is the trained one
    init = lambda k: PN.mlp_init(k, 784, [128, 128], 10)
    params, qstate, wq = train_classifier(
        init, _apply, lambda s: pseudo_mnist_batch(s, 64), steps=steps,
        act_levels=32, n_weights=1000, cluster_every=60)
    idx_tree, books = codebook_indices(params, wq, qstate)
    rep = memory_report(idx_tree, 1000, 32)
    rows.append(("memory_savings", "trained-mlp",
                 rep.row().replace(",", ";")))
    rows.append(("memory_savings", "savings_vs_fp32",
                 f"{100 * rep.savings_vs_fp32:.1f}%"))
    rows.append(("memory_savings", "entropy_savings_vs_fp32",
                 f"{100 * rep.entropy_savings_vs_fp32:.1f}%"))
    rows.append(("memory_savings", "bits_per_weight",
                 f"{rep.entropy_bits_per_w:.2f}"))
    # projection to the paper's AlexNet scale (50M params): the A×W table
    # amortises away; packed savings -> the pure 10-vs-32-bit ratio, and the
    # entropy figure uses OUR measured index entropy (the paper's <7 bits
    # reflects their AlexNet's peakier trained histogram — recorded in
    # EXPERIMENTS.md as a distribution-dependent claim).
    n50 = 50_000_000
    packed50 = 1 - (n50 * rep.index_bits / 8 + rep.table_bytes) / (4 * n50)
    ent50 = 1 - (n50 * rep.entropy_bits_per_w / 8 + rep.table_bytes) / (4 * n50)
    rows.append(("memory_savings", "projected_50M_packed",
                 f"{100 * packed50:.1f}%"))
    rows.append(("memory_savings", "projected_50M_entropy",
                 f"{100 * ent50:.1f}%"))

    # LUT engine vs float matmul: µs per layer on CPU
    act = ActQuantConfig("tanh", 32)
    book = np.asarray(books[""])
    tabs = build_tables(book, LutConfig(act=act, table_entries=4096),
                        fan_in=785)
    w = params["layer0"]["w"]
    wi = clustering.assign_to_centers(w, jnp.asarray(book)).astype(jnp.int32)
    x = pseudo_mnist_batch(0, 64)["x"]
    xi = fp.input_to_indices(jnp.tanh(x), act)

    t_float = timer(jax.jit(lambda x, w: x @ w), x, w)
    t_int = timer(jax.jit(partial(fp.int_linear, tables=tabs)), xi, wi, None)
    rows.append(("lut_speed", "float_matmul_us", f"{t_float:.0f}"))
    rows.append(("lut_speed", "int_lut_engine_us", f"{t_int:.0f}"))

    # Pallas (interpret) sanity timing for the TPU codebook path
    t_cb = timer(lambda: ops.codebook_matmul(
        x, wi.astype(jnp.int16), jnp.asarray(book)))
    rows.append(("lut_speed", "codebook_matmul_interpret_us", f"{t_cb:.0f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    for r in run():
        print(",".join(r))
