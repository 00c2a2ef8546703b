"""Active-mesh context and sharding-hint primitives.

``mesh_context(mesh)`` declares the mesh a jitted step is being traced
for; ``constrain`` then resolves *logical* axis names against it:

- ``"dp"``    -> the data-parallel axes present on the mesh (``("pod",
                 "data")`` on the multi-pod mesh, ``("data",)`` otherwise)
- ``"model"`` -> the tensor-parallel axis, when the mesh has one
- ``None``    -> unsharded

Hints are *divisibility-safe*: an axis whose size does not divide the
array dimension is dropped rather than forcing GSPMD padding, and with no
active mesh (single device, or the ``repro.dist``-less containers served
by ``repro.models/_dist_compat.py``) ``constrain`` is the identity — the
same layer code traces everywhere.
"""
from __future__ import annotations

import contextlib
import threading

DP_AXES = ("pod", "data")

_state = threading.local()


def current_mesh():
    """The mesh declared by the innermost :func:`mesh_context`, or None."""
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def mesh_context(mesh):
    """Declare ``mesh`` as the active mesh for ``constrain`` resolution.

    Composes with (and does not replace) jax's own ``with mesh:`` scope;
    launchers typically enter both: ``with mesh, ctx.mesh_context(mesh):``.
    """
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


def mesh_sizes(mesh) -> dict:
    """{axis name: size} for concrete and abstract meshes alike."""
    return dict(mesh.shape)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes present on ``mesh``, in fixed order."""
    names = mesh.axis_names
    return tuple(a for a in DP_AXES if a in names)


def axis_entry(axes: tuple[str, ...]):
    """PartitionSpec entry for a tuple of mesh axes (unwrap singletons)."""
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def resolve(mesh, spec, shape=None):
    """Logical spec entries -> a concrete ``PartitionSpec`` for ``mesh``.

    ``spec`` entries are None, ``"dp"``, or a mesh axis name.  When
    ``shape`` is given, axes whose size product does not divide the
    corresponding dimension are dropped (divisibility safety).
    """
    from jax.sharding import PartitionSpec as P

    sizes = mesh_sizes(mesh)
    entries = []
    for d, s in enumerate(spec):
        if s is None:
            entries.append(None)
            continue
        axes = dp_axes(mesh) if s == "dp" else (
            (s,) if s in sizes else ())
        if shape is not None and axes:
            k = 1
            for a in axes:
                k *= sizes[a]
            if k == 0 or shape[d] % k != 0:
                axes = ()
        entries.append(axis_entry(axes))
    return P(*entries)


def constrain(x, *spec):
    """Pin ``x`` to the resolved sharding of ``spec`` on the active mesh.

    Identity when no mesh is active; the real twin of the no-op in
    ``repro.models/_dist_compat.py``.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    import jax
    from jax.sharding import NamedSharding

    p = resolve(mesh, spec, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, p))


def planner_mesh(n_devices: int | None = None, *, devices=None,
                 axis: str = "data"):
    """1-D mesh over host devices for frame-sharded stream planning.

    The rebalancing planner (``repro.rebalance.planner``) shards the time
    axis of a frame stream over the data-parallel axis; this is the
    entry point that names it.  The axis vocabulary is shared with
    ``repro.launch.mesh`` (``DP_AXES``), so a planner mesh composes with
    :func:`dp_axes` / :func:`resolve` like the production meshes do.

    Deliberately touches jax device state only when called (this module
    stays import-light; the dry-run sets XLA_FLAGS before first init).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"planner_mesh: {n_devices} devices requested, "
                             f"{len(devs)} available (set XLA_FLAGS="
                             f"--xla_force_host_platform_device_count=N "
                             f"before jax initializes to force host devices)")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def planner_axes(mesh) -> tuple[str, ...]:
    """The mesh axes a frame stream is sharded over: the DP axes.

    Shared resolution point for ``rebalance.planner`` and
    ``launch.mesh`` — a 1-D :func:`planner_mesh` and the production
    2-/3-axis meshes answer through the same ``DP_AXES`` order.
    """
    axes = dp_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no data-parallel axis "
                         f"(expected one of {DP_AXES})")
    return axes


def abstract_mesh(shape, axes):
    """A device-less ``AbstractMesh`` of the given axis sizes and names."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))
