"""Production mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

from repro.parallel.sharding import make_mesh

__all__ = ["make_production_mesh", "make_pipe_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pipe_mesh(n_stages: int = 8):
    """Mesh for the pipeline-parallel library tests."""
    return make_mesh((n_stages,), ("pipe",))
