"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).

Topology (TPU v5e target):
  single pod:  16 x 16 = 256 chips, axes (data, model)
  multi-pod:   2 x 16 x 16 = 512 chips, axes (pod, data, model);
               'pod' is pure data parallelism over DCN.
Scaling beyond 2 pods only grows the 'pod' axis — the sharding rules are
pod-count-agnostic.

Mesh axes are ``AxisType.Auto``: the model is written for compiler-
propagated (GSPMD) shardings. ``jax.make_mesh`` defaults to Explicit
axes, under which array types carry shardings and the model's gathers
and contractions would each need an ``out_sharding``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh over however many (fake) devices tests configured."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
