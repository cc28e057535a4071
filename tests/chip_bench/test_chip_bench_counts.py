"""Percentiles, the codebook kernel's 10-bit roofline count and the model
FLOP count, from shapes alone."""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmarks" / "chip")]

from chipbench import counts, peaks, stats  # noqa: E402

QWEN = json.loads((ROOT / "benchmarks/chip/configs/qwen3-1.7b.json")
                  .read_text())
MISTRAL = json.loads((ROOT / "benchmarks/chip/configs/"
                      "mistral-large-123b-l3.json").read_text())


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy_over_all_samples(q):
    xs = np.random.default_rng(q).lognormal(0, 1, 37)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_no_samples():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_index_plane_counts_ten_bits_at_1000_weights():
    assert counts.index_bits(1000) == 10
    assert counts.index_bits(1024) == 10
    assert counts.index_bits(1025) == 11
    shape = counts.Shape.of(QWEN)
    flops, nbytes = counts.codebook_call(shape, 64, 2048, 6144)
    assert flops == 2 * 64 * 2048 * 6144
    assert nbytes == 2048 * 6144 * 10 / 8 + 4 * 1000 + 64 * 2048 * 2 \
        + 64 * 6144 * 4


def test_v5e_codebook_decode_call_is_bandwidth_bound():
    p = peaks.lookup("TPU v5 lite")
    shape = counts.Shape.of(MISTRAL)
    flops, nbytes = counts.codebook_call(shape, 32, 12288, 28672)
    assert counts.least_seconds(flops, nbytes, p) == pytest.approx(
        nbytes / 819e9)
    # a 12288 x 28672 plane at 10 bits is 440 MB: ~0.54 ms at 819 GB/s
    assert nbytes / 819e9 == pytest.approx(5.4e-4, rel=0.02)


def test_projections_are_the_published_matrices():
    q, m = counts.Shape.of(QWEN), counts.Shape.of(MISTRAL)
    assert q.projections() == [
        ("wq", 2048, 2048), ("wk", 2048, 1024), ("wv", 2048, 1024),
        ("wo", 2048, 2048), ("w1", 2048, 6144), ("w3", 2048, 6144),
        ("w2", 6144, 2048)]
    assert max(max(k, n) for _, k, n in m.projections()) == 28672


def test_model_flops_from_shapes():
    q = counts.Shape.of(QWEN)
    # qwen3-1.7b: 28 layers of 2048x(2048+1024+1024) + 2048x2048
    # + 3 x 2048x6144 matrix weights
    assert q.layer_weights() == 2048 * 4096 + 2048 * 2048 + 3 * 2048 * 6144
    one = counts.model_flops(q, 1, 100, heads=1)
    assert one == (2 * q.layer_weights() * 28 + 4 * 16 * 128 * 28 * 100
                   + 2 * 2048 * 151936)
    assert counts.prefill_ctx_sum(4) == 1 + 2 + 3 + 4


def test_peaks_refuse_an_unknown_device():
    assert peaks.lookup("TPU v5 lite").flops == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
