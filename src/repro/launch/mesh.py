"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; callers (dryrun.py, benchmarks) set XLA_FLAGS *before* the first
jax import.

Simulating devices on a host: jax locks the device count at first
initialization, so the flag must be in the environment before jax is
imported —

    XLA_FLAGS=--xla_force_host_platform_device_count=<n>

README.md ("Environment variables & flags") is the canonical list of the
knobs (REPRO_HE_BACKEND, host-device-count) shared by the benchmarks, CI
legs, and these helpers.
"""
from __future__ import annotations

import numpy as np


def _make_mesh(shape, axes, devices):
    """jax.make_mesh with Auto axes.

    Failures (usually a device count that cannot fill `shape`) re-raise
    with a pointer to the knob that fixes them — like every other error in
    this module, it names the README section so operators never have to
    read this source to recover."""
    import jax

    try:
        return jax.make_mesh(
            shape, axes, devices=devices,
            axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    except ValueError as e:
        raise RuntimeError(
            f"could not build mesh {dict(zip(axes, shape))}: {e}. "
            "Host-simulated devices come from XLA_FLAGS="
            "--xla_force_host_platform_device_count=<n>, which must be set "
            "before the first jax import — see README.md 'Environment "
            "variables & flags'.") from e


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    import jax

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} exist. "
            "jax locks the device count at first init, so set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 in the "
            "environment BEFORE the first jax import (repro.launch.dryrun "
            "sets this automatically; see README.md 'Environment variables "
            "& flags').")
    return _make_mesh(shape, axes, devices[:n])


def make_host_mesh():
    """Trivial 1x1 mesh for CPU smoke runs."""
    import jax

    return _make_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def make_he_mesh(n_limbs: int, n_devices: int | None = None, *,
                 devices=None):
    """("data", "model") mesh for the sharded HE engine (DESIGN.md §8).

    Picks the largest model-axis size that divides BOTH `n_limbs` (so whole
    limbs map to shards) and the device count (so the mesh is full); the
    remaining factor becomes the data axis for ciphertext-chunk sharding.

    Args:
        n_limbs: RNS limb count of the CkksContext the mesh will serve.
        n_devices: devices to use (default: all available).
        devices: explicit device list (default jax.devices()).

    Returns:
        A jax Mesh with axes ("data", "model"), data*model == n_devices.
    """
    import jax

    devs = list(devices if devices is not None else jax.devices())
    k = int(n_devices if n_devices is not None else len(devs))
    if k > len(devs):
        raise RuntimeError(
            f"make_he_mesh asked for {k} devices but only {len(devs)} "
            "exist; simulate more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<n> set "
            "before the first jax import (see README.md 'Environment "
            "variables & flags').")
    m = max(d for d in range(1, k + 1) if n_limbs % d == 0 and k % d == 0)
    return _make_mesh((k // m, m), ("data", "model"), devs[:k])
