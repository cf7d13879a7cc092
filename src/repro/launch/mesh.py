"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before any jax import; tests/benches see the real single device.

Every mesh the serving path uses has Auto axes: ``jax.make_mesh`` defaults
to Explicit axes, under which the corpus shard_map steps' gathers and
broadcasts raise sharding-type errors. ``auto_axes`` converts a caller's
mesh at the library's entry points (``shard_corpus``, the step factories).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis Auto."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 = 256 chips per pod; the multi-pod
    variant stacks 2 pods on a leading 'pod' axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int = 0, *, axes=("data", "model")):
    """Small debug mesh over whatever devices exist (tests/examples)."""
    n = n_devices or len(jax.devices())
    if len(axes) == 2:
        # favor model axis when n allows
        model = 1
        for m in (8, 4, 2, 1):
            if n % m == 0:
                model = m
                break
        return make_mesh((n // model, model), axes)
    return make_mesh((n,), axes)
