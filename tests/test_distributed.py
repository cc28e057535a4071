"""Distribution substrate: gradient compression codec, sharding specs, and
multi-device equivalence (the latter in a subprocess with 8 fake devices so
the main pytest process keeps the real single-device view)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.grad_compression import lap_dequantize, lap_quantize
from repro.distributed import sharding as SH

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_lap_codec_error_bounded():
    key = jax.random.PRNGKey(0)
    for scale in (1e-4, 1.0, 37.0):
        g = jax.random.laplace(key, (20_000,)) * scale + 0.3 * scale
        idx, a, b = lap_quantize(g)
        q = lap_dequantize(idx, a, b)
        assert idx.dtype == jnp.uint8
        rel = float(jnp.linalg.norm(q - g) / jnp.linalg.norm(g))
        # L1-optimal (not L2-optimal) 256-level grid: ~6% rel-L2 error
        assert rel < 0.08, (scale, rel)


def test_lap_codec_wire_format_small():
    """8-bit index + two scalars per tensor: 4x fewer wire bytes than f32."""
    g = jax.random.normal(jax.random.PRNGKey(1), (1000,))
    idx, a, b = lap_quantize(g)
    assert idx.nbytes * 4 + 8 <= g.nbytes + 8


def test_param_specs_cover_all_leaves():
    import repro.configs as C
    from repro.models.model_zoo import build
    from repro.launch import steps as ST
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(1, 1)
    for name in ("qwen3-1.7b", "qwen3-moe-30b-a3b", "zamba2-2.7b",
                 "rwkv6-7b", "whisper-small"):
        model = build(C.get(name).reduced())
        params = ST.abstract_params(model)
        specs = ST.params_partition_specs(model, mesh)
        ps, ss = jax.tree.leaves(params), jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        assert len(ps) == len(ss), name
        for p, s in zip(ps, ss):
            assert len(s) <= p.ndim, (name, p.shape, s)


_MULTIDEV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.grad_compression import (compressed_psum_tree,
                                                init_error_state)

from repro.distributed.compat import make_mesh, shard_map
mesh = make_mesh((2, 4), ("pod", "data"))
key = jax.random.PRNGKey(0)
grads = {"w": jax.random.laplace(key, (2, 4, 64)),
         "b": jax.random.laplace(jax.random.fold_in(key, 1), (2, 8))}

def exchange(g, e):
    red, ne = compressed_psum_tree(g, e, "pod")
    return red, ne

fn = shard_map(exchange, mesh=mesh,
                   in_specs=({"w": P("pod"), "b": P("pod")},
                             {"w": P("pod"), "b": P("pod")}),
                   out_specs=({"w": P("pod"), "b": P("pod")},
                              {"w": P("pod"), "b": P("pod")}),
                   check_vma=False)
err = init_error_state(grads)
red, err2 = jax.jit(fn)(grads, err)
# exact mean over the pod axis as reference: dim 0 is pod-sharded in halves
def pod_mean(v):
    half = (v[:1] + v[1:]) / 2.0
    return jnp.concatenate([half, half], axis=0)
exact = {k: pod_mean(v) for k, v in grads.items()}
rel = float(jnp.linalg.norm(red["w"] - exact["w"]) /
            jnp.linalg.norm(exact["w"]))
# error feedback: residual nonzero, bounded
enorm = float(jnp.linalg.norm(err2["w"]))
print(json.dumps({"rel": rel, "enorm": enorm}))
assert rel < 0.08, rel

# multi-device train-step equivalence: 4-device mesh == single device
import repro.configs as C
from repro.models.model_zoo import build
from repro.launch import steps as ST
from repro.optim import OptConfig, init_opt_state
cfg = C.get("qwen3-1.7b").reduced().replace(n_layers=2, dtype="float32")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = init_opt_state(params, OptConfig(lr=1e-3))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0,
                                      cfg.vocab)}
from repro.distributed.compat import make_mesh
mesh2 = make_mesh((2, 2), ("data", "model"))
pspec = ST.params_partition_specs(model, mesh2)
psh = ST.shardings_for(pspec, mesh2)
step1 = jax.jit(ST.make_train_step(model, OptConfig(lr=1e-3), None))
p1, _, m1 = step1(params, opt, batch)
step2 = jax.jit(ST.make_train_step(model, OptConfig(lr=1e-3), mesh2),
                in_shardings=(psh, None, None))
p2, _, m2 = step2(params, opt, batch)
d = max(float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                  "max_param_delta": d}))
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
assert d < 1e-4
print("MULTIDEV_OK")
"""


@pytest.mark.tier2
def test_multidevice_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _MULTIDEV], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "MULTIDEV_OK" in out.stdout, out.stdout + out.stderr


_DECODE_MESH = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp
import repro.configs as C
from repro.models import transformer as T

from repro.distributed.compat import make_mesh
mesh = make_mesh((2, 8), ("data", "model"))
key = jax.random.PRNGKey(0)
for name, kvq in (("llama3.2-3b", False), ("codeqwen1.5-7b", True),
                  ("zamba2-2.7b", False)):
    cfg = C.get(name).reduced().replace(kv_quant=kvq, kv_block=8)
    p = T.init_params(key, cfg)
    B, S = 2, 16
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    c1 = T.init_cache(cfg, B, 32, dtype=jnp.float32)
    c2 = jax.tree.map(lambda x: x, c1)
    sl = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))
    sm = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c, mesh))
    for t in range(S):
        l1, c1 = sl(p, toks[:, t:t + 1], c1)
        l2, c2 = sm(p, toks[:, t:t + 1], c2)
    err = float(jnp.max(jnp.abs(l1 - l2)))
    # noise floor: bf16 psum payload (~0.4% of partial outputs); int8 KV
    # adds its own quantization noise on top (measured ~0.02 on the 0.4.x
    # CPU backend — keep a margin above the floor, not at it)
    assert err < (3e-2 if kvq else 8e-3), (name, err)
    print(name, "ok", err)
print("DECODE_MESH_OK")
"""


@pytest.mark.tier2
def test_shardmap_flash_decode_matches_local():
    """The §Perf(a) explicit flash-decode (shard_map over the S-sharded
    cache, int8 or bf16) must be numerically identical to the single-device
    decode path, ring buffers included."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _DECODE_MESH], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "DECODE_MESH_OK" in out.stdout, out.stdout + out.stderr


def test_local_mesh_refuses_to_shrink():
    """A mesh asking for more devices than exist raises instead of
    silently becoming a smaller layout than the one requested."""
    import jax
    from repro.launch.mesh import make_local_mesh

    n = len(jax.devices())
    assert make_local_mesh(1, n).shape["model"] == n
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(1, n + 1)
