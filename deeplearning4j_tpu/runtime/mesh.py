"""Device and mesh discovery.

TPU-native replacement for the reference's device management (upstream
``CudaEnvironment`` device affinity and ``ParallelWrapper`` worker placement):
on TPU, placement is a `jax.sharding.Mesh` + named shardings, and XLA inserts
the collectives. This module is the single place the rest of the framework asks
"what devices exist and what mesh should I use".

Mesh axis conventions used throughout the framework:

- ``data``   — data parallelism (batch sharding; psum of grads over ICI)
- ``fsdp``   — parameter/optimizer sharding (ZeRO-3) in a composed plan;
  single-axis FSDP reuses ``data`` (batch AND params shard together there)
- ``model``  — tensor parallelism (weight sharding)
- ``pipe``   — pipeline stage axis
- ``seq``    — sequence/context parallelism (ring attention)
- ``expert`` — expert parallelism (MoE)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"


def devices(backend: Optional[str] = None):
    """All addressable devices (this process)."""
    return jax.devices(backend) if backend else jax.devices()


def device_count(backend: Optional[str] = None) -> int:
    return len(devices(backend))


def global_device_count() -> int:
    return jax.device_count()


def process_count() -> int:
    return jax.process_count()


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape: ordered mapping of axis name -> size.

    ``size == -1`` on at most one axis means "whatever is left over", like a
    reshape wildcard. ``MeshSpec({'data': -1})`` is pure DP over all devices.
    """

    axes: Tuple[Tuple[str, int], ...]

    def __init__(self, axes: Dict[str, int] | Sequence[Tuple[str, int]]):
        items = tuple(axes.items()) if isinstance(axes, dict) else tuple(axes)
        object.__setattr__(self, "axes", items)

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = dict(self.axes)
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("At most one mesh axis may be -1")
        fixed = int(np.prod([v for v in sizes.values() if v != -1])) if sizes else 1
        if wild:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"Mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


@dataclasses.dataclass(frozen=True)
class HostSpec:
    """One machine in a multi-host bring-up (ISSUE 12): the declarative
    twin of :class:`MeshSpec` for the HOST axis. ``name`` is what a
    :class:`~deeplearning4j_tpu.serving.fleet.WorkerSpec.host` (or a
    training worker placement) references, ``address`` is where that
    host's processes are reachable, and ``spawn`` selects the process
    adapter (``"local"`` = this machine, ``"loopback"`` = a named
    same-machine stand-in for tests/drills, ``"ssh"``/other = a remote
    transport an adapter must implement). The serving fleet resolves
    these through ``serving.fleet.resolve_host_adapters``; the training
    side feeds the same roster into :func:`initialize_multihost`
    (coordinator + process ids per host)."""

    name: str
    address: str = "127.0.0.1"
    spawn: str = "local"
    #: how many worker processes this host is expected to carry (a
    #: placement hint; 0 = unconstrained)
    processes: int = 0


def loopback_hosts(n: int, prefix: str = "host") -> Tuple[HostSpec, ...]:
    """``n`` named loopback hosts — the serving twin of the ``local[N]``
    Spark-master trick: every "host" is this machine, but specs, spawn
    adapters, endpoints and placement all flow through the real
    multi-host paths, so tests and drills exercise a fleet that spans
    machines without owning any."""
    return tuple(HostSpec(name=f"{prefix}{i}", address="127.0.0.1",
                          spawn="loopback") for i in range(int(n)))


def create_mesh(
    spec: MeshSpec | Dict[str, int] | None = None,
    devices_: Optional[Sequence] = None,
) -> Mesh:
    """Build a `jax.sharding.Mesh` from a :class:`MeshSpec`.

    Defaults to pure data parallelism over every addressable device. Device
    order is preserved so that, on real hardware, neighbouring mesh positions
    are ICI neighbours (jax returns devices in torus order).
    """
    devs = list(devices_ if devices_ is not None else jax.devices())
    if spec is None:
        spec = MeshSpec({DATA_AXIS: -1})
    elif isinstance(spec, dict):
        spec = MeshSpec(spec)
    sizes = spec.resolve(len(devs))
    names = tuple(sizes.keys())
    shape = tuple(sizes[n] for n in names)
    mesh_devices = np.asarray(devs).reshape(shape)
    return Mesh(mesh_devices, names)


def local_mesh() -> Mesh:
    """1-axis DP mesh over local devices — the single-chip/dev default."""
    return create_mesh(MeshSpec({DATA_AXIS: -1}))


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up: the replacement for the reference's Spark driver +
    Aeron mesh join (upstream ``SharedTrainingMaster`` / ``MeshOrganizer``).

    On TPU pods this is one call per host; XLA then routes collectives over
    ICI within a slice and DCN across slices. Safe to call with no arguments
    under TPU metadata-provided environments.

    On the CPU backend (the ``local[N]``-style multi-process smoke path)
    cross-process collectives ride jax's gloo TCP implementation, which is
    the installed default (``jax_cpu_collectives_implementation``).
    """
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
