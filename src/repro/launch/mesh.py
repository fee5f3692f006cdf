"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before any device query.

Every mesh is built by ``_mesh``: its axes are ``Auto``, so GSPMD
propagates shardings from the explicit in/out shardings of each program.
(``jax.make_mesh`` defaults to ``Explicit`` axes, under which sharding is
part of every array's type and gathers such as the embedding lookup must
state their output sharding.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding.partition import ShardCtx


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    pure data parallelism (DCN-crossing gradient all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_ctx(*, multi_pod: bool = False) -> ShardCtx:
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return ShardCtx(mesh=mesh, batch_axes=batch_axes, model_axis="model")


def make_device_ctx(data: int, model: int, *, fsdp: bool = False) -> ShardCtx:
    """(data x model) mesh over the currently visible devices — real chips
    or ``--xla_force_host_platform_device_count`` simulated ones (the
    mesh-equivalence tests and the DP scaling benchmark use the latter).

    Serving default is ``fsdp=False``: decode re-gathers every weight every
    step under FSDP, so weights are replicated over ``data`` and only
    tensor-parallel over ``model`` (see ``ShardCtx.fsdp``).
    """
    mesh = _mesh((data, model), ("data", "model"))
    return ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                    fsdp=fsdp)


def local_ctx() -> ShardCtx:
    """Single-device ctx for CPU tests/examples."""
    return ShardCtx(mesh=None)
