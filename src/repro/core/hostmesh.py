"""Multi-host (multi-controller) mesh bootstrap and data placement.

One process per host, every process running the same SPMD program — the
standard jax multi-controller model.  On CPU (the CI container) cross-host
collectives go through gloo over TCP, which must be selected *before* the
backend initializes; :func:`initialize` owns that ordering, and
:func:`initialize_from_env` makes it a one-liner for subprocess-simulated
hosts (the chaos harness and ``launch/fit.py --hosts`` both launch children
with the ``REPRO_*`` variables below).

After initialization the existing single-process code is almost unchanged:
``jax.devices()`` spans every host, :func:`repro.core.compat.make_mesh`
builds the global mesh, and ``shard_map`` collectives lower to real
cross-host wire traffic.  The two genuinely multi-host concerns live here:

  * **placement** — a host can only ``device_put`` to its own devices, so
    globally-sharded arrays are assembled from per-process row slices with
    :func:`place_global_rows` (each host contributes exactly the rows its
    local devices own — no scatter through a driver, same property as the
    single-host ``shard_batch``);
  * **fetching** — fully-replicated outputs (the runner's combines produce
    them) are addressable everywhere, :func:`fetch` asserts that before
    converting so a non-replicated array fails loudly instead of hanging.

Environment contract (set by the launcher/harness for every host process):

    REPRO_COORDINATOR   host:port of process 0's coordination service
    REPRO_NUM_HOSTS     total host processes in the mesh
    REPRO_HOST_ID       this process's id in [0, num_hosts)
"""
from __future__ import annotations

import dataclasses
import os
import socket
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core import partition as pt

__all__ = [
    "HostInfo",
    "free_port",
    "initialize",
    "initialize_from_env",
    "is_multihost",
    "host_id",
    "num_hosts",
    "local_row_slice",
    "place_global_rows",
    "fetch",
]


@dataclasses.dataclass(frozen=True)
class HostInfo:
    """What a host process knows about its place in the mesh."""

    host_id: int
    num_hosts: int
    coordinator: Optional[str] = None

    @property
    def multihost(self) -> bool:
        return self.num_hosts > 1


def free_port() -> int:
    """An OS-assigned free TCP port (for a generation's coordinator)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def initialize(coordinator: str, num_hosts: int, host_id: int) -> HostInfo:
    """Join the multi-controller mesh.  Must run before anything touches the
    jax backend (device queries included) — gloo collectives can only be
    selected pre-initialization.
    """
    if num_hosts < 2:
        return HostInfo(host_id=0, num_hosts=1)
    # CPU cross-process collectives need the gloo implementation; it must
    # be selected before the backend initializes.  TPU/GPU ignore it in
    # favor of the native interconnect.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=int(num_hosts),
                               process_id=int(host_id))
    return HostInfo(host_id=int(host_id), num_hosts=int(num_hosts),
                    coordinator=coordinator)


def initialize_from_env() -> HostInfo:
    """Bootstrap from the ``REPRO_*`` launcher contract; a no-op single-host
    :class:`HostInfo` when the variables are absent, so programs can call
    this unconditionally as their first line.

    ``REPRO_COORDINATOR`` is deliberately separate from ``REPRO_NUM_HOSTS``:
    the SSP exchange lane launches N *independent* hosts (id + world size,
    no global mesh), so its launcher sets the ids but no coordinator and
    this stays a no-op — only the BSP gang, which needs real cross-host
    collectives, gets a coordinator."""
    n = int(os.environ.get("REPRO_NUM_HOSTS", "1"))
    coordinator = os.environ.get("REPRO_COORDINATOR")
    if n < 2 or not coordinator:
        return HostInfo(host_id=int(os.environ.get("REPRO_HOST_ID", "0")),
                        num_hosts=1)
    return initialize(coordinator, n, int(os.environ["REPRO_HOST_ID"]))


def is_multihost() -> bool:
    return jax.process_count() > 1


def host_id() -> int:
    return jax.process_index()


def num_hosts() -> int:
    return jax.process_count()


def local_row_slice(num_rows: int, mesh: Mesh,
                    data_axes: Tuple[str, ...]) -> slice:
    """The contiguous row range of a ``(num_rows, ...)`` globally-sharded
    array owned by this process's devices.

    Row partitions follow global device order (process-major), so process
    ``p`` of ``P`` owns rows ``[p * num_rows / P, (p + 1) * num_rows / P)``
    — every process must hold equally many of the mesh's data shards
    (true for subprocess-simulated hosts and for real pods).
    """
    procs = jax.process_count()
    shards = pt.num_data_shards(mesh, data_axes)
    if shards % procs != 0:
        raise ValueError(
            f"{shards} data shards do not divide over {procs} host "
            f"processes — every host must carry equally many shards")
    pt.check_rows_divisible(num_rows, shards, what="global row partitions")
    per = num_rows // procs
    p = jax.process_index()
    return slice(p * per, (p + 1) * per)


def place_global_rows(host_rows: np.ndarray, num_rows: int, mesh: Mesh,
                      data_axes: Tuple[str, ...]) -> jax.Array:
    """Assemble a globally row-sharded array from this process's row slice.

    ``host_rows`` is exactly the slice :func:`local_row_slice` describes;
    every process calls this with its own slice and receives a handle on
    the one global array.  The multi-host twin of
    :func:`repro.core.partition.place_rows`.
    """
    sharding = NamedSharding(mesh, pt.data_spec(data_axes))
    global_shape = (num_rows,) + tuple(host_rows.shape[1:])
    return jax.make_array_from_process_local_data(
        sharding, np.asarray(host_rows), global_shape)


def fetch(array) -> np.ndarray:
    """Bring a fully-replicated global array to the host as numpy.

    Every combine the runner performs produces replicated outputs
    (``out_specs=P()``), so results are addressable on every host; anything
    else reaching here is a programming error worth failing loudly on
    (converting a non-replicated global array would otherwise hang or
    fetch garbage on a multi-host mesh).
    """
    if isinstance(array, jax.Array) and not array.is_fully_replicated:
        raise ValueError(
            f"array with sharding {array.sharding} is not fully replicated "
            f"— only replicated results can be fetched on every host")
    return np.asarray(jax.device_get(array))
