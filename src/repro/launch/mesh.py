"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and smoke tests
must see 1 CPU device while the dry-run forces 512 host devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the LM step's sharding constraints are layout hints that
    # XLA's propagation resolves, not the type-level asserts they become on
    # jax.make_mesh's default Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1D 'data' mesh (tests, examples)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def serve_devices(n: int | None = None) -> list:
    """The first ``n`` local devices for the data-parallel serving plane
    (None = all of them).

    The serving tier replicates the model per device and shards the BATCH,
    so it wants a flat device list, not a mesh.  On CPU-only hosts the
    platform exposes one device unless ``XLA_FLAGS=
    --xla_force_host_platform_device_count=N`` is set BEFORE jax first
    initializes — the error message repeats that because by the time this
    raises, it is too late to set it in-process.
    """
    devs = jax.devices()
    if n is None:
        return list(devs)
    if n > len(devs):
        raise ValueError(
            f"serve_devices({n}): only {len(devs)} local devices exist; "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} in the environment before jax initializes")
    return list(devs[:n])


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the batch: ('pod','data') multi-pod, ('data',) else."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
