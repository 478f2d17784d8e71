"""Production mesh definitions (TPU v5e pods).

Single pod = 16x16 = 256 chips, axes ("data", "model").
Multi-pod   = 2x16x16 = 512 chips, axes ("pod", "data", "model") — the pod
axis carries data parallelism (and joins the FSDP group for archs that set
``fsdp_axes=("pod", "data")``).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# hardware constants used by the roofline analysis (TPU v5e)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D ('data',) mesh (tests/CPU)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))


def make_engine_mesh(n_devices: int | None = None):
    """1-D ('shard',) mesh for edge-partitioned summarization engines.

    Uses the first ``n_devices`` local devices (all of them by default); the
    ShardedSummarizer lays one or more engine replicas on each.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(f"need 1..{len(devs)} devices, got {n}")
    return Mesh(np.asarray(devs[:n]), ("shard",))


def chips(mesh) -> int:
    return mesh.devices.size
