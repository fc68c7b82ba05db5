"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the compiler propagates
    shardings from the in/out specs, as the model code is written for
    (JAX 0.9 defaults new meshes to ``Explicit`` sharding-in-types)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int | None = None):
    """Mesh over whatever devices exist (tests / examples / elastic restart)."""
    n = len(jax.devices())
    m = model_axis or (2 if n % 2 == 0 and n > 1 else 1)
    return make_mesh((n // m, m), ("data", "model"))
