"""Autotune harness (kernels/autotune.py): determinism, cache replay,
config validity — the properties that make the tuning cache CI-safe."""

import json

import pytest

from repro.kernels import autotune

SHAPES = [dict(kernel="lut", plat=p, m=m, k=k, n=n, dtype="int32",
               table_shape=(4096, 256))
          for p in ("xla", "tpu") for m in (1, 8, 64)
          for (k, n) in ((128, 128), (128, 256), (256, 128))] + \
         [dict(kernel="codebook", plat=p, m=m, k=k, n=n, dtype=d,
               table_shape=(256,))
          for p in ("xla", "tpu") for m in (1, 64)
          for d in ("float32", "bfloat16") for (k, n) in ((128, 256),)]


@pytest.fixture(autouse=True)
def _fresh_memory_cache():
    autotune.clear_memory_cache()
    yield
    autotune.clear_memory_cache()


def test_two_runs_byte_identical(tmp_path):
    """Seeded cost-model tuning over the same shape set must write
    byte-identical JSON — the property that lets CI regenerate and diff
    the committed cache."""
    paths = [str(tmp_path / f"cache_{i}.json") for i in (0, 1)]
    for p in paths:
        autotune.clear_memory_cache()
        autotune.autotune_shapes(SHAPES, path=p, seed=0)
    b0 = open(paths[0], "rb").read()
    b1 = open(paths[1], "rb").read()
    assert b0 == b1
    assert len(json.loads(b0)) == len(SHAPES)


def test_shuffled_shape_order_same_bytes(tmp_path):
    """Cache contents must not depend on tuning order (sorted dump)."""
    p0, p1 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    autotune.autotune_shapes(SHAPES, path=p0)
    autotune.clear_memory_cache()
    autotune.autotune_shapes(list(reversed(SHAPES)), path=p1)
    assert open(p0, "rb").read() == open(p1, "rb").read()


def test_cold_vs_warm_identical_configs(tmp_path):
    """Configs resolved from a warm file cache equal cold cost-model
    picks: replaying the committed cache never changes routing."""
    path = str(tmp_path / "cache.json")
    cold = {}
    for s in SHAPES:
        key = autotune.cache_key(s["kernel"], s["plat"], s["m"], s["k"],
                                 s["n"], s["dtype"], s["table_shape"])
        cold[key] = autotune.kernel_config(
            s["kernel"], s["m"], s["k"], s["n"], dtype=s["dtype"],
            plat=s["plat"], table_shape=s["table_shape"])
    autotune.autotune_shapes(SHAPES, path=path)

    # warm pass: resolve through the file via REPRO_TUNING_CACHE
    import os
    autotune.clear_memory_cache()
    old = os.environ.get("REPRO_TUNING_CACHE")
    os.environ["REPRO_TUNING_CACHE"] = path
    try:
        for s in SHAPES:
            key = autotune.cache_key(s["kernel"], s["plat"], s["m"], s["k"],
                                     s["n"], s["dtype"], s["table_shape"])
            warm = autotune.kernel_config(
                s["kernel"], s["m"], s["k"], s["n"], dtype=s["dtype"],
                plat=s["plat"], table_shape=s["table_shape"])
            assert warm == cold[key], key
    finally:
        if old is None:
            del os.environ["REPRO_TUNING_CACHE"]
        else:
            os.environ["REPRO_TUNING_CACHE"] = old


def test_configs_are_valid_candidates():
    """Every resolved config is drawn from the candidate space: xla picks
    carry impl='xla' (+ variant/kc for lut), tpu picks are Pallas tiles
    respecting lane/sublane quanta and the VMEM budget."""
    for s in SHAPES:
        cfg = autotune.kernel_config(
            s["kernel"], s["m"], s["k"], s["n"], dtype=s["dtype"],
            plat=s["plat"], table_shape=s["table_shape"])
        cands = autotune.candidates(
            s["kernel"], s["plat"], s["m"], s["k"], s["n"], s["dtype"],
            s["table_shape"])
        assert cfg in cands, (s, cfg)
        if s["plat"] == "xla" and s["kernel"] == "lut":
            assert cfg["impl"] == "xla"
            assert cfg["variant"] in ("rows", "flat")
            assert cfg["kc"] in (32, 64, 128)
        elif s["plat"] == "xla":
            assert cfg["impl"] == "xla"
        elif s["kernel"] == "lut":
            assert cfg["impl"] == "pallas"
            assert cfg["bm"] == min(s["m"], 8) and cfg["bn"] % 128 == 0 \
                and cfg["bk"] % 32 == 0
        else:
            assert cfg["impl"] == "pallas"
            assert cfg["bm"] % 8 == 0 and cfg["bn"] % 128 == 0 \
                and cfg["bk"] % 128 == 0


def test_committed_cache_is_canonical_and_fresh():
    """The checked-in tuning_cache.json must be byte-identical to what
    autotune_shapes would write for its own keys today — i.e. regenerable
    in CI, with no stale keys from an older cost model or key schema."""
    path = autotune.default_cache_path()
    committed = json.loads(open(path).read())
    assert committed, "committed tuning cache is empty"
    for key in committed:
        kernel, plat, shape, dtype, table = key.split("|")
        assert kernel in ("lut", "codebook")
        assert plat in ("tpu", "xla")
        assert dtype == ("int32" if kernel == "lut" else dtype)
    # canonical dump round-trips byte-identically
    blob = json.dumps(committed, sort_keys=True, indent=1) + "\n"
    assert blob == open(path).read()


@pytest.mark.parametrize("m", [2, 16])
def test_lut_tpu_route_is_rows_kernel(m):
    """On a TPU the lut engine resolves to the row-gather Pallas kernel at
    every table size, the paper's 4096 × 1000 working point included: the
    kernel streams table rows, so no tile has to hold the whole table."""
    for table in ((4096, 1000), (512, 1000), (4096, 256)):
        cfg = autotune.kernel_config("lut", m, 2048, 6144, dtype="int32",
                                     plat="tpu", table_shape=table)
        assert cfg["impl"] == "pallas" and cfg["bm"] == min(m, 8), \
            (table, cfg)


def test_explicit_cache_dict_short_circuits():
    """An explicit cache dict takes precedence over both the in-process
    cache and the cost model — the autotune_shapes accumulation path."""
    key = autotune.cache_key("lut", "xla", 8, 128, 128, "int32", (4096, 256))
    sentinel = {"impl": "xla", "variant": "rows", "kc": 64}
    cache = {key: sentinel}
    got = autotune.kernel_config("lut", 8, 128, 128, dtype="int32",
                                 plat="xla", table_shape=(4096, 256),
                                 cache=cache)
    assert got is sentinel
