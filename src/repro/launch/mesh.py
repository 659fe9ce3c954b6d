"""Mesh construction + sharding-spec utilities (FSDP/ZeRO, batch specs).

Nothing at import time touches jax device state; ``make_production_mesh`` is
a function per the dry-run contract.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Params = Any


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """``jax.make_mesh`` with Auto axes.  The model and trainer state their
    shardings as specs and leave propagation to the compiler; Explicit axes
    (``jax.make_mesh``'s default) would make every gather and constraint
    spell out its output sharding."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (16, 16) (data, model) = 256 chips.  Multi-pod: 2 pods =
    (2, 16, 16) (pod, data, model) = 512 chips — "pod" is the slow
    (DCN/inter-pod) axis and carries only DP traffic."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Tiny mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    return make_mesh((n // model_axis, model_axis), ("data", "model"))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


def batch_spec(mesh: Mesh, ndim: int) -> P:
    """Shard dim 0 (global batch) over the data axes."""
    return P(data_axes(mesh), *([None] * (ndim - 1)))


def batch_shardings(mesh: Mesh, batch_like: Params) -> Params:
    return jax.tree.map(
        lambda x: NamedSharding(mesh, batch_spec(mesh, np.ndim(x))),
        batch_like)


# ---------------------------------------------------------------------------
# FSDP / ZeRO: spread parameters (and thus optimizer moments) over the data
# axes on top of their TP axis.
# ---------------------------------------------------------------------------


def fsdp_specs(specs: Params, shapes: Params, mesh: Mesh) -> Params:
    """For every >=2D param whose spec leaves a dim unsharded, shard its
    largest divisible unsharded dim over the data axes.  Params+moments then
    occupy 1/|mesh| of their global size per device (ZeRO-3-equivalent
    memory; XLA all-gathers shards just-in-time)."""
    daxes = data_axes(mesh)
    dtotal = data_size(mesh)

    def fix(spec: P, shape) -> P:
        dims = tuple(shape.shape) if hasattr(shape, "shape") else tuple(shape)
        if len(dims) < 2 or dtotal == 1:
            return spec
        entries = list(spec) + [None] * (len(dims) - len(spec))
        best, best_size = -1, 0
        for i, (e, n) in enumerate(zip(entries, dims)):
            if e is None and n % dtotal == 0 and n > best_size:
                best, best_size = i, n
        if best >= 0:
            entries[best] = daxes if len(daxes) > 1 else daxes[0]
        return P(*entries)

    return jax.tree.map(fix, specs, shapes,
                        is_leaf=lambda x: isinstance(x, P))


def named(mesh: Mesh, specs: Params) -> Params:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def param_shapes(init_fn, *args) -> Params:
    """Shapes without allocation (jax.eval_shape)."""
    return jax.eval_shape(init_fn, *args)
