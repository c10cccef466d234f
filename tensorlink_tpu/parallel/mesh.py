"""Device mesh construction.

The reference's unit of capacity is one worker's GPU bytes
(nodes/worker_thread.py:128-166); on TPU it is a slice of a device mesh.
Axis convention (scaling-book style):

- ``data``    — batch sharding (DP); gradients psum over it
- ``fsdp``    — parameter/optimizer sharding (ZeRO-3), usually same ICI links
- ``tensor``  — megatron TP inside a layer (legacy GSPMD dense path)
- ``expert``  — MoE expert parallelism
- ``seq``     — sequence/context parallelism (ring attention)
- ``stage``   — pipeline stages
- ``tp``      — explicit tensor parallelism for the paged serving path
  (shard_map, bitwise-exact collectives — see docs/SHARDING.md)

Meshes are built so axes that carry the most traffic (tensor/tp) map to
the innermost (fastest ICI) device dimension.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_ORDER = ("stage", "data", "fsdp", "expert", "seq", "tensor", "tp")


@dataclass(frozen=True)
class MeshPlan:
    """Resolved axis sizes for one node's mesh."""

    axis_sizes: dict[str, int] = field(default_factory=dict)

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.axis_sizes.values():
            n *= s
        return n

    def names(self) -> tuple[str, ...]:
        return tuple(a for a in AXIS_ORDER if self.axis_sizes.get(a, 1) > 1) or (
            "data",
        )


def build_mesh(
    axis_sizes: dict[str, int],
    devices: list | None = None,
) -> Mesh:
    """Build a Mesh with axes ordered outer→inner so ``tensor`` lands on the
    fastest links. Axes of size 1 are kept (harmless, simplifies specs)."""
    devices = devices if devices is not None else jax.devices()
    names = [a for a in AXIS_ORDER if a in axis_sizes]
    extra = [a for a in axis_sizes if a not in AXIS_ORDER]
    names += extra
    sizes = [axis_sizes[a] for a in names]
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(sizes)
    return Mesh(arr, tuple(names))


def local_mesh(**axis_sizes: int) -> Mesh:
    """Convenience: mesh over all local devices; one axis may be -1."""
    devs = jax.devices()
    sizes = dict(axis_sizes) if axis_sizes else {"data": -1}
    wild = [a for a, s in sizes.items() if s == -1]
    if wild:
        known = int(np.prod([s for s in sizes.values() if s != -1]))
        sizes[wild[0]] = len(devs) // known
    return build_mesh(sizes, devs)


def serving_mesh(
    tp: int, dp: int = 1, devices: list | None = None
) -> Mesh:
    """The ``(dp, tp)`` mesh the paged serving/serve-train path runs on.

    ``tp`` is innermost (fastest ICI links — it carries the per-chunk
    activation gathers), ``data`` outermost (it only carries the zero1
    gradient reduction). A pure-serving replica uses ``dp=1``; the
    flattened device index is ``data_idx * tp + tp_idx``, which is the
    order zero1 × TP slices optimizer state by (engine/training.py)."""
    return build_mesh({"data": int(dp), "tp": int(tp)}, devices)


def shard(mesh: Mesh, spec: P):
    return NamedSharding(mesh, spec)


def put(mesh: Mesh, tree, specs):
    """device_put a pytree with a matching PartitionSpec pytree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree,
        specs,
        is_leaf=lambda x: x is None,
    )
