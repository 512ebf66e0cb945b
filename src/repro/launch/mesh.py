"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model); the pod axis
composes with data for batch/FSDP sharding (pure DP across the inter-pod
links, TP kept inside a pod).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).

Every mesh here has ``Auto`` axes: parameters and batches carry
``NamedSharding`` placements and XLA inserts the FSDP all-gathers and
reductions (``distributed/sharding.py``).  ``jax.make_mesh`` defaults to
``Explicit`` axes, under which every gather and matmul of the model would
have to name its output sharding.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """A mesh over ``devices`` (default: all) whose axes are all Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the real local device (tests/examples)."""
    n = jax.local_device_count()
    return make_mesh((n, 1), ("data", "model"))


def make_serve_mesh(n_data: int = 0, n_model: int = 1):
    """Serve-time mesh: data-parallel by default, TP optional.

    The streaming serve path shards request microbatches (and FSDP-shards
    estimator params) across ``data``; the estimator is small enough that
    ``model`` usually stays 1.  ``n_data=0`` takes every local device —
    on CPU, tests and benchmarks multiply devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    the first jax import).
    """
    n = n_data or max(1, jax.local_device_count() // n_model)
    return make_mesh((n, n_model), ("data", "model"))
