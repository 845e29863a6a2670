"""Device-mesh construction — the TPU replacement for peer topology wiring.

Where the reference wires TCP connections between PeerIDs (srcs/go/rchannel),
the TPU build arranges chips into a `jax.sharding.Mesh` and lets XLA route
collectives over ICI/DCN.  This module owns:

  - canonical axis names (dp / fsdp / tp / pp / sp / ep) and their meanings,
  - hierarchical meshes: an outer `dcn` axis (across hosts/pods) times inner
    `ici` axes (within a pod slice) — the analog of the reference's
    local/global/cross strategy split (session/session.go:21-37),
  - small helpers to build meshes on real TPUs or on the CPU backend with
    `--xla_force_host_platform_device_count=N` for multi-chip testing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis order: outermost (slowest-varying, crosses DCN first) to
# innermost.  Data parallel outermost so its collectives can ride DCN while
# tp/sp stay on ICI.
AXIS_ORDER = ("dp", "fsdp", "pp", "sp", "ep", "tp")

DATA_AXES = ("dp", "fsdp")  # gradient reduction axes

#: `make_mesh`'s first question about devices in this process: (monotonic
#: start, end, platform, device count).  The TPU runtime comes up at the
#: first such question anyone asks; this package sits below the span
#: recorder, so monitor/programs.py reads the stamp from here and keeps it
#: as the `boot:backend` phase where nobody above asked before
first_asked: Optional[Tuple[float, float, str, int]] = None


def _all_devices() -> Sequence[jax.Device]:
    global first_asked
    t0 = time.monotonic()
    devs = jax.devices()
    if first_asked is None:
        first_asked = (t0, time.monotonic(), devs[0].platform, len(devs))
    return devs


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes; -1 for one auto axis (filled from device count)."""

    axes: Tuple[Tuple[str, int], ...]

    @classmethod
    def make(cls, **sizes: int) -> "MeshSpec":
        unknown = [k for k in sizes if k not in AXIS_ORDER]
        if unknown:
            raise ValueError(f"unknown axes {unknown}; valid: {AXIS_ORDER}")
        ordered = tuple((a, sizes[a]) for a in AXIS_ORDER if a in sizes)
        if sum(1 for _, v in ordered if v == -1) > 1:
            raise ValueError("at most one -1 axis")
        return cls(axes=ordered)

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = dict(self.axes)
        known = int(np.prod([v for v in sizes.values() if v != -1])) if sizes else 1
        for a, v in sizes.items():
            if v == -1:
                if n_devices % known:
                    raise ValueError(f"{n_devices} devices not divisible by {known}")
                sizes[a] = n_devices // known
        total = int(np.prod(list(sizes.values()))) if sizes else 1
        if total != n_devices:
            raise ValueError(f"mesh {sizes} != {n_devices} devices")
        return sizes


def make_mesh(
    spec: Optional[MeshSpec] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    **sizes: int,
) -> Mesh:
    """Build a Mesh. `make_mesh(dp=-1)` = pure data parallel over all devices.

    Uses `jax.experimental.mesh_utils` device ordering on real TPUs so that
    innermost axes land on physically adjacent chips (ICI neighbors).
    """
    if spec is None:
        spec = MeshSpec.make(**(sizes or {"dp": -1}))
    devs = list(devices if devices is not None else _all_devices())
    sizes_r = spec.resolve(len(devs))
    names = tuple(sizes_r)
    shape = tuple(sizes_r[a] for a in names)
    if devices is None and jax.default_backend() == "tpu":
        from jax.experimental import mesh_utils

        # a topology the chips cannot form must fail here, not be papered
        # over with an arbitrary device order
        arr = mesh_utils.create_device_mesh(shape)
    else:
        arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, names)


def make_hierarchical_mesh(
    n_hosts: int,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """("dcn", "ici") mesh: outer axis across hosts, inner within a host.

    The analog of the reference's hierarchical allreduce split — local reduce,
    cross-host allreduce, local broadcast (srcs/cpp/src/nccl/controller.cpp:8-40,
    session/strategy.go:188-210).  Collectives over "ici" stay on the fast
    interconnect; collectives over "dcn" cross hosts.
    """
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) % n_hosts:
        raise ValueError(f"{len(devs)} devices not divisible by {n_hosts} hosts")
    per_host = len(devs) // n_hosts
    arr = np.asarray(devs).reshape(n_hosts, per_host)
    return Mesh(arr, ("dcn", "ici"))


def data_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """Shard leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_digest(mesh: Mesh) -> str:
    """Stable digest of mesh shape+device ids for membership consensus."""
    import hashlib

    ids = ",".join(str(d.id) for d in mesh.devices.flat)
    desc = f"{dict(mesh.shape)}|{ids}"
    return hashlib.sha256(desc.encode()).hexdigest()[:16]
