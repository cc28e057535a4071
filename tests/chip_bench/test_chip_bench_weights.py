"""The seed-made index-form weights: the tree, shapes and dtypes that
``launch.serve.make_params`` gives, and per-layer planes the reference
can rebuild alone."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmarks" / "chip")]

from chipbench import weights as W  # noqa: E402

SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def reduced():
    import repro.configs as C
    from repro.launch.serve import make_params
    from repro.models.model_zoo import build

    out = {}
    for name in ("qwen3-1.7b", "mistral-large-123b"):
        model = build(C.get(name).reduced().replace(dtype="bfloat16"))
        out[name] = (model, make_params(model, seed=3, n_weights=1000))
    return out


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mistral-large-123b"])
def test_tree_matches_make_params(reduced, name):
    model, want = reduced[name]
    tree = W.serving_tree(model, 1000)
    got = W.make_params(tree, SEED, 1000, 0.02)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for (kp, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                          jax.tree_util.tree_leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype), W.leaf_path(kp)


def test_planes_rebuild_layer_by_layer(reduced):
    model, _ = reduced["qwen3-1.7b"]
    p = W.make_params(W.serving_tree(model, 1000), SEED, 1000, 0.02)
    key = W.base_key(SEED)
    stacked = p["blocks"]["mlp"]["w1"]["w_idx"]
    for layer in range(stacked.shape[0]):
        alone = W.index_plane(key, "blocks/mlp/w1/w_idx", stacked.shape[1:],
                              1000, jnp.int16, layer)
        assert bool(jnp.all(alone == stacked[layer]))
    emb = W.index_plane(key, "embed/w_idx", p["embed"]["w_idx"].shape, 1000,
                        jnp.int16)
    assert bool(jnp.all(emb == p["embed"]["w_idx"]))


def test_codebook_is_a_function_of_the_id(reduced):
    model, _ = reduced["qwen3-1.7b"]
    p = W.make_params(W.serving_tree(model, 1000), SEED, 1000, 0.02)
    key = W.base_key(SEED)
    book = p["blocks"]["attn"]["wq"]["codebook"][0]
    ids = p["blocks"]["attn"]["wq"]["w_idx"][1]
    val = jax.jit(lambda k, i: W.codebook_value(k, i, 1000, 0.02))(key, ids)
    assert bool(jnp.all(val == book[ids.astype(jnp.int32)]))
    assert bool(jnp.all(jnp.diff(book) > 0))
    assert float(jnp.std(book)) == pytest.approx(0.02, rel=0.05)
    assert int(ids.min()) >= 0 and int(ids.max()) < 1000


def test_seeds_give_different_weights():
    a = W.index_plane(W.base_key(5), "x", (64, 64), 1000, jnp.int16)
    b = W.index_plane(W.base_key(5 + 2 ** 31), "x", (64, 64), 1000,
                      jnp.int16)
    assert bool(jnp.any(a != b))
    with pytest.raises(ValueError):
        W.base_key(-1)


def test_a_stacked_leaf_of_any_rank_is_made_layer_by_layer():
    """An expert leaf (L, E, K, N) under a stacked prefix is made one
    layer's (E, K, N) plane at a time, as the reference rebuilds it; a
    leaf outside the stacked prefixes is made whole."""
    tree = {"blocks": {"moe": {"w1": {
                "w_idx": jax.ShapeDtypeStruct((3, 4, 16, 32), jnp.int16),
                "codebook": jax.ShapeDtypeStruct((3, 1000), jnp.float32)}}},
            "embed": {"w_idx": jax.ShapeDtypeStruct((2, 64, 16), jnp.int16),
                      "codebook": jax.ShapeDtypeStruct((1000,),
                                                       jnp.float32)}}
    p = W.make_params(tree, SEED, 1000, 0.02)
    key = W.base_key(SEED)
    experts = p["blocks"]["moe"]["w1"]["w_idx"]
    assert experts.shape == (3, 4, 16, 32)
    for layer in range(3):
        alone = W.index_plane(key, "blocks/moe/w1/w_idx", (4, 16, 32), 1000,
                              jnp.int16, layer)
        assert bool(jnp.all(alone == experts[layer]))
    whole = W.index_plane(key, "embed/w_idx", (2, 64, 16), 1000, jnp.int16)
    assert bool(jnp.all(whole == p["embed"]["w_idx"]))
