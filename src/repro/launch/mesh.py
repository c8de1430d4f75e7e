"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because
the dry-run forces 512 host devices via XLA_FLAGS before first jax init,
while tests/benches must see the single real CPU device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the first ``data * model`` local devices.

    Naming the devices makes a mesh smaller than the host (a 1x1 mesh
    on a four-chip host) explicit: it takes device 0 onwards.
    """
    n = data * model
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(f"a {data}x{model} mesh needs {n} devices, found {len(devices)}")
    return _make_mesh((data, model), ("data", "model"), devices=devices[:n])


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh: ('pod','data') when present."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
