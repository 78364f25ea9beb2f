"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
jax import and only then calls this.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes.

    The installed JAX builds Explicit axes by default, under which the
    store's slice updates and gathers on a sharded stack would each
    need their output sharding spelled out; with Auto axes XLA
    propagates the stack's ``NamedSharding`` through them."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(n_devices: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


def local_data_mesh(min_devices: int = 2,
                    n_devices: int | None = None):
    """1-D ``data`` mesh over the local devices, or ``None`` when
    fewer than ``min_devices`` exist (callers degrade to default
    placement).  ``n_devices`` builds over just the first N devices —
    how tests exercise the degraded single-device mesh that auto-
    disables the collective query path.  The shared builder for
    benchmarks/tests/examples."""
    n_avail = len(jax.devices())
    n = n_devices or n_avail
    if n_avail < max(min_devices, n):
        return None
    return _auto_mesh((n,), ("data",), devices=jax.devices()[:n])
