"""Production meshes.  Functions, not constants — importing this module
never touches jax device state (per brief)."""

from __future__ import annotations

import jax

from repro.distributed.compat import make_mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (16, 16) = 256 chips or multi-pod (2, 16, 16) = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first data·model local devices.

    Raises when fewer devices exist: a mesh that silently shrank would
    run (and report) a different parallel layout than the one asked for.
    """
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a ({data}, {model}) mesh needs {data * model} "
                         f"devices; found {n} ({jax.default_backend()})")
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
