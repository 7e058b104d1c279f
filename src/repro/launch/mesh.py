"""Production mesh factory (assignment MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

from repro.dist.mesh import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)
