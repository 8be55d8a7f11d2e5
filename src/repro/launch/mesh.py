"""Production mesh construction. A FUNCTION (not a module-level constant) so
importing this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings
    through every op, as the serving and training layouts assume (pinned
    with ``with_sharding_constraint``). ``jax.make_mesh`` otherwise types
    the axes ``Explicit``, under which a gather from a sharded operand
    (the vocab-sharded embedding lookup) must name its out-sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small (data, model) mesh over however many (possibly fake) local
    devices exist — serving tensor-parallelism and distribution tests.

    Validates the request against ``jax.device_count()`` up front: a
    too-large mesh would otherwise surface as an opaque shape error deep
    inside the first jit that touches it. Simulate devices on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes its backend).
    """
    if data < 1 or model < 1:
        raise ValueError(
            f"mesh axes must be positive, got data={data} model={model}")
    need, have = data * model, jax.device_count()
    if need > have:
        raise ValueError(
            f"requested a {data}x{model} (data x model) mesh = {need} "
            f"devices but only {have} are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} (before jax "
            f"initializes) to simulate them, or shrink the mesh")
    return _auto_mesh((data, model), ("data", "model"))
