"""Production mesh: (pod, data, model).

Single pod = one 16x16 v5e slice (256 chips); multi-pod adds a leading
'pod' axis (2 pods = 512 chips) that only DP gradient reductions cross.
Defined as functions so importing this module never touches jax device
state (device count is locked at first jax init — dryrun.py sets
XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    import numpy as np

    dev_array = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_dev_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests on whatever devices exist."""
    import numpy as np

    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    dev_array = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_data_mesh(n_devices: int = 0, *, axis: str = "data"):
    """1-D pure data-parallel mesh over the first ``n_devices`` local devices.

    This is the mesh the sparse leg uses (sparse/mesh_engine.py sharded
    corpus passes, ops.bcd_solve_batched ``devices=`` lambda-grid fan-out):
    documents / lambda-grid problems shard along the single ``data`` axis and
    nothing is model-parallel.  ``n_devices`` of 0 means all local devices.
    Off-TPU the device count comes from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which must be set
    before the first jax init (device topology is locked at that point).
    """
    import numpy as np

    devices = jax.devices()
    n = int(n_devices) if n_devices else len(devices)
    if n > len(devices):
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    dev_array = np.asarray(devices[:n])
    return jax.sharding.Mesh(dev_array, (axis,),
                             axis_types=(jax.sharding.AxisType.Auto,))
