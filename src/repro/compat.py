"""Thin wrappers over the JAX sharding APIs the repo uses (JAX 0.9).

Call sites import these names from here rather than from ``jax``:

* ``shard_map``       — ``jax.shard_map``; ``check_vma=None`` keeps the
                        library default.
* ``axis_size``       — ``jax.lax.axis_size``.
* ``make_mesh``       — ``jax.make_mesh`` with every axis ``Auto``.
* ``get_abstract_mesh`` — the ambient trace-time mesh, or None.
* ``use_mesh``        — context manager the step builders use to make a
                        physical mesh ambient at trace time.
* ``constraint_sharding`` — what to hand ``with_sharding_constraint`` for a
                        PartitionSpec: a ``NamedSharding`` for a physical
                        mesh, the bare spec under an abstract-mesh context.
"""
from __future__ import annotations

import contextlib

import jax

axis_size = jax.lax.axis_size


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` keeps the library default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kw)


def get_abstract_mesh():
    """The ambient trace-time mesh, or None when outside any mesh context.
    Callers only rely on ``axis_names`` / ``shape``."""
    m = jax.sharding.get_abstract_mesh()
    return m if m is not None and m.axis_names else None


@contextlib.contextmanager
def use_mesh(mesh):
    """Make physical ``mesh`` ambient for sharding hints at trace time."""
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        yield


def constraint_sharding(mesh, spec):
    """Resolve a PartitionSpec against the ambient mesh for
    ``with_sharding_constraint``."""
    if isinstance(mesh, jax.sharding.Mesh):
        return jax.sharding.NamedSharding(mesh, spec)
    return spec                          # abstract mesh: context resolves it
