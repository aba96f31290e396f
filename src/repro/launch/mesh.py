"""Production meshes (TPU v5e).

Single pod: (data=16, model=16) = 256 chips.  Multi-pod: a leading "pod"
axis of 2 -> 512 chips; AD-GDA nodes map to the flattened ("pod","data")
axes so gossip's ring neighbors land on ICI within a pod and only the
ring's two pod-boundary edges cross DCN — exactly the thin-cut regime the
compressed gossip targets (DESIGN §3).

Functions, not module constants, so importing never initializes devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_cpu_mesh",
    "make_node_mesh",
    "node_axes",
    "NODE_AXIS",
]

NODE_AXIS = "nodes"  # logical name used in PartitionSpecs for the AD-GDA node dim


def _auto_mesh(shape, axes, devices=None):
    """A mesh whose axes the compiler partitions automatically (GSPMD): the
    code places arrays with NamedShardings and leaves propagation to XLA,
    so no axis carries its sharding in the array types."""
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 512 if multi_pod else 256
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"production mesh needs {need} devices, found {len(devices)} — "
            "run via repro.launch.dryrun (which forces 512 host devices) or on real hardware"
        )
    return _auto_mesh(shape, axes, devices[:need])


def node_axes(mesh) -> tuple[str, ...]:
    """Mesh axes the AD-GDA node dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_nodes(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get("pod", 1) * sizes["data"])


def make_cpu_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU smoke/integration tests on the real local devices."""
    return _auto_mesh((data, model), ("data", "model"))


def make_node_mesh(num_nodes: int):
    """Mesh whose ``data`` axis carries the gossip node shards — the target
    of the ``ppermute`` exchange backend (core/exchange.py).

    Uses the largest available device count that divides ``num_nodes`` so
    every device hosts an equal contiguous node block (the backend's
    requirement); on a single-device host this degenerates to a (1, 1) mesh
    and the neighbor exchanges run as local rolls.  Force a multi-device CPU
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    avail = len(jax.devices())
    data = max(k for k in range(1, min(avail, num_nodes) + 1) if num_nodes % k == 0)
    return _auto_mesh((data, 1), ("data", "model"), jax.devices()[:data])
