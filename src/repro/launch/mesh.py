"""Production mesh factories.

Functions, not module-level constants, so importing never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Production topology: TPU v5e, 16×16 = 256 chips per pod; the multi-pod mesh
adds a leading "pod" axis (2 pods = 512 chips) connected over DCN.  Axes:
  pod   — pure data parallelism across pods (gradient all-reduce over DCN)
  data  — within-pod data parallelism / sequence sharding for long context
  model — tensor / expert parallelism
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# XLA flags a real TPU deployment would launch with (latency-hiding overlap of
# collectives with compute; documented here, applied by launch scripts).
TPU_XLA_PERF_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true "
)


def _auto_mesh(shape, names):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules
    (``sharding.rules``) annotate inputs and let the compiler propagate,
    which ``Explicit`` axes (the ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def make_mesh_for(n_devices: int | None = None,
                  model_parallel: int | None = None):
    """Size a ("data", "model") serving mesh to the devices that exist.

    The production factory below hard-codes the 16×16 pod shape and can only
    run on that topology; everything else — engines, tests, the host-platform
    smoke — goes through this so the device count is discovered, not assumed.

      n_devices       total devices to use (default: all visible devices)
      model_parallel  size of the "model" axis (default: all of them — pure
                      tensor parallelism; must divide n_devices)
    """
    n = int(n_devices) if n_devices else len(jax.devices())
    m = int(model_parallel) if model_parallel else n
    if m <= 0 or n % m != 0:
        raise ValueError(
            f"model_parallel={m} does not divide n_devices={n}")
    return _auto_mesh((n // m, m), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return _auto_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh_for(256, model_parallel=16)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small host-device mesh for unit tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count >= data*model*max(pod,1))."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def make_slice_mesh(devices, model_parallel: int):
    """Mesh over a *sub-slice* of a pod (Clover serving instance): the given
    devices become a (1, model_parallel) (data, model) mesh."""
    import numpy as np
    devs = np.asarray(devices).reshape(1, model_parallel)
    from jax.sharding import Mesh
    return Mesh(devs, ("data", "model"))
