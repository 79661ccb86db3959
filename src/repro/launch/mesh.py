"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run must set XLA_FLAGS before any jax initialization).

Axis names ("pod", "data", "model") are the PHYSICAL side of the logical
axis-rule tables in :mod:`repro.dist.sharding` — install a mesh with
``axis_rules(LM_RULES, mesh)`` and the models' logical `shard` annotations
resolve onto it (see docs/ARCHITECTURE.md, stage 5).
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist locally, as a 1-D data mesh (tests/examples)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
