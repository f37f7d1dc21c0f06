"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device initialization — the dry-run must set
XLA_FLAGS before anything initializes the backend.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax


class MeshError(ValueError):
    """Requested mesh shape does not fit the available devices."""


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagates the
    shardings) — jax's own default is ``Explicit``."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def _check_devices(shape: Tuple[int, ...], axes: Tuple[str, ...],
                   what: str) -> None:
    n = math.prod(shape)
    avail = len(jax.devices())
    if avail < n:
        dims = "x".join(str(s) for s in shape)
        raise MeshError(
            f"{what} needs {n} devices ({dims} over axes {axes}) but only "
            f"{avail} {'is' if avail == 1 else 'are'} available — on a "
            f"host-only machine set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax "
            f"initializes, or request a smaller mesh")


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod prepends a 2-pod axis.

    Raises :class:`MeshError` naming the requested vs available device
    count when the pod does not fit — never silently truncates."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _check_devices(shape, axes,
                   f"make_production_mesh(multi_pod={multi_pod})")
    n = math.prod(shape)
    return make_mesh(shape, axes, jax.devices()[:n])


def make_test_mesh(n_devices: Optional[int] = None, *,
                   model: Optional[int] = None):
    """Small ``(data, model)`` mesh over however many (host) devices exist
    — for CI tests and host-device sharded serving. ``model`` pins the
    tensor-parallel axis; it must divide ``n_devices``."""
    n = n_devices or len(jax.devices())
    model = model or (2 if n % 2 == 0 and n > 1 else 1)
    if model < 1 or n % model != 0:
        raise MeshError(
            f"make_test_mesh: model axis {model} does not divide the "
            f"{n} requested device(s) — a ({n // model}, {model}) "
            f"(data, model) mesh is not expressible; pick a model degree "
            f"dividing {n}")
    data = n // model
    _check_devices((data, model), ("data", "model"),
                   f"make_test_mesh(n_devices={n}, model={model})")
    return make_mesh((data, model), ("data", "model"),
                     jax.devices()[: data * model])


def required_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
