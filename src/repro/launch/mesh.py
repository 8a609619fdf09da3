"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; smoke tests see
one device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: the partitioner places
    intermediates from the in/out shardings, which the pjit train step and
    the shard_map quantize engine rely on (``make_mesh`` now defaults to
    ``Explicit`` axes, which make every sharding part of an array's type)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1, n_pod: int | None = None):
    """Small mesh over however many (possibly fake) devices exist."""
    if n_pod:
        return _auto_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_model_mesh(n_model: int | None = None):
    """1-D ``("model",)`` mesh for the distributed quantization engine.

    Quantization is pure model parallelism (column shards of each weight),
    so ``quantize_model(..., mesh=make_model_mesh())`` puts every local
    device on the model axis.  ``n_model`` defaults to all local devices."""
    n = n_model or len(jax.devices())
    return _auto_mesh((n,), ("model",))


def data_axes_of(mesh) -> tuple:
    return tuple(ax for ax in mesh.axis_names if ax in ("pod", "data"))


def pcontext_for(mesh):
    from repro.models.parallel import PContext
    da = data_axes_of(mesh)
    return PContext(mesh=mesh, data_axes=da if len(da) > 1 else da[0],
                    model_axis="model")
