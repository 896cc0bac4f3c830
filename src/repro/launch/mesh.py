"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state; `jax.make_mesh` is only called when a launcher actually asks for a
mesh (the dry-run sets XLA_FLAGS for 512 host devices *before* any import).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, examples, elastic re-mesh), every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1D 'data' mesh (examples/CI)."""
    return make_mesh((len(jax.devices()),), ("data",))
