"""Multi-host federation: jax.distributed bootstrap + global mesh + feeds.

The reference's "multi-node" story is three processes on one laptop joined
by hand-rolled TCP with a polling rendezvous (reference client1.py:276-336,
server.py:116-137). The TPU-native equivalent is the JAX runtime's own
bootstrap: every process calls :func:`initialize` (coordinator address +
process id), after which ``jax.devices()`` spans all hosts and ONE SPMD
program runs across them — FedAvg rides DCN between hosts and ICI within,
with no application-level sockets at all.

Topology: :func:`make_global_mesh` lays the ``clients`` axis process-major,
so each host holds a contiguous block of client replicas. Cross-client
collectives (the FedAvg pmean) cross DCN once per round; the per-client
``data``-axis gradient psum stays inside a host's ICI domain. Data feeding
follows the same split: each process tokenizes only its own clients' shards
(:func:`local_client_slice`) and assembles global arrays with
:func:`global_batch`.

Single-process runs degrade to the ordinary mesh/arrays — every function
here is a no-op wrapper in that case, so the federated trainer has one code
path.
"""

from __future__ import annotations

import os
from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from .mesh import make_mesh


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """``jax.distributed.initialize`` with env fallbacks; returns whether a
    multi-process runtime is active afterwards.

    Env fallbacks (the standard JAX names): ``JAX_COORDINATOR_ADDRESS``,
    ``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``. A ``num_processes`` of 1 (or
    nothing configured) is the single-process case: no-op, returns False.
    On TPU pods the runtime can discover everything itself — then call with
    no arguments and let ``jax.distributed.initialize()`` autodetect.
    """
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    # NOTE: no jax.devices()/process_count() before jax.distributed
    # initializes — any backend touch would lock in a single-process runtime.
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    configured = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
    )
    if not configured:
        return False  # nothing requested: ordinary single-process run
    if num_processes == 1 and coordinator_address is None:
        return False  # explicitly single-process
    # Partial configuration (e.g. a coordinator with no process id) is
    # deliberately passed through: jax.distributed.initialize either
    # autodetects the rest (TPU pods, Slurm) or raises its own precise
    # error — silently falling back to single-process would mask a typo'd
    # launch as a working run.
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_count() > 1


def make_global_mesh(
    clients: int = 1,
    data: int = 1,
    *,
    axis_names: tuple[str, str] = ("clients", "data"),
) -> Mesh:
    """``clients x data`` mesh over ALL processes' devices, clients-major by
    process: client c's submesh lives entirely on process
    ``c // (clients / process_count)``. Requires ``clients`` to be a
    multiple of the process count and ``clients*data`` devices total.

    Single-process: identical to :func:`..mesh.make_mesh`.
    """
    if jax.process_count() == 1:
        return make_mesh(clients, data, axis_names=axis_names)
    return Mesh(_global_grid((clients, data)), axis_names)


def _global_grid(dims: tuple[int, ...]) -> np.ndarray:
    """Process-major device grid for a clients-leading global mesh: the
    one layout/validation pipeline under :func:`make_global_mesh` and
    :func:`make_global_seq_mesh`. Client c's trailing-axes block lives
    entirely on process ``c // (clients / process_count)``: within-client
    collectives (data psum, seq ring) stay on-host; only the clients-axis
    FedAvg crosses DCN."""
    P = jax.process_count()
    clients = dims[0]
    shape = "x".join(map(str, dims))
    if clients % P:
        raise ValueError(
            f"clients={clients} must be a multiple of process_count={P} so "
            "each host owns whole client replicas (within-client axes stay "
            "on-host; only FedAvg crosses DCN)"
        )
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    need = int(np.prod(dims))
    if len(devs) != need:
        raise ValueError(
            f"global mesh {shape} needs exactly {need} devices across "
            f"{P} processes, have {len(devs)}"
        )
    per_client = need // clients
    if (clients // P) * per_client != len(devs) // P:
        raise ValueError(
            f"each process must contribute (clients/P) client blocks = "
            f"{(clients // P) * per_client} devices, has {len(devs) // P}"
        )
    grid = np.array(devs).reshape(dims)
    # Backstop the layout math (e.g. heterogeneous per-host device counts
    # that pass the average check above): no client's within-client block
    # may span processes — a cross-DCN ring/psum would silently serialize
    # on the slowest link.
    for c in range(clients):
        block_procs = {d.process_index for d in grid[c].ravel()}
        if len(block_procs) != 1:
            raise ValueError(
                f"client {c}'s within-client device block spans processes "
                f"{sorted(block_procs)}; each client must stay on one host"
            )
    return grid


def make_global_seq_mesh(
    clients: int,
    data: int,
    seq: int,
    *,
    axis_names: tuple[str, str, str] = ("clients", "data", "seq"),
) -> Mesh:
    """``clients x data x seq`` mesh over ALL processes' devices, clients
    process-major: each host owns whole client replicas, so every seq ring
    (the latency-critical ppermute loop of ring attention) and every
    data-axis gradient psum stay INSIDE one host's ICI domain — only the
    FedAvg pmean over ``clients`` crosses DCN, once per round. This is the
    flagship composition on the BASELINE north-star hardware (a v4-64:
    multi-host by definition): clients over DCN x seq ring on ICI.

    Single-process: identical to :func:`..fedseq.make_seq_mesh`.
    """
    if jax.process_count() == 1:
        from .fedseq import make_seq_mesh

        return make_seq_mesh(clients, data, seq, axis_names=axis_names)
    return Mesh(_global_grid((clients, data, seq)), axis_names)


def local_client_slice(mesh: Mesh) -> slice:
    """Which block of the stacked ``[C, ...]`` client axis this process
    feeds. With the process-major layout of :func:`make_global_mesh` /
    :func:`make_global_seq_mesh`, that is one contiguous slice. Works for
    any mesh whose FIRST axis is ``clients`` (2-axis and 3-axis alike)."""
    C = mesh.devices.shape[0]
    lead = mesh.devices.reshape(C, -1)[:, 0]
    procs = [d.process_index for d in lead]
    mine = [c for c, p in enumerate(procs) if p == jax.process_index()]
    if not mine:  # a process holding no client shards feeds nothing
        return slice(0, 0)
    lo, hi = mine[0], mine[-1] + 1
    if mine != list(range(lo, hi)):
        raise ValueError(
            "client axis is not process-contiguous; build the mesh with "
            "make_global_mesh"
        )
    return slice(lo, hi)


def global_rows(
    sharding: NamedSharding, arr: np.ndarray, num_clients: int
) -> jax.Array:
    """One global ``[C, ...]`` array from this process's local client block
    ``[C_local, ...]`` (the :func:`local_client_slice` rows). The single
    assembly primitive under :func:`global_batch` and the fedseq feed
    (train/seqfed.py), whose per-key shardings differ.

    Single-process: plain ``device_put`` (local IS global)."""
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    global_shape = (num_clients, *arr.shape[1:])
    return jax.make_array_from_process_local_data(
        sharding, np.ascontiguousarray(arr), global_shape
    )


def global_batch(
    sharding: NamedSharding, local: Mapping[str, np.ndarray], num_clients: int
) -> dict[str, jax.Array]:
    """Assemble global ``[C, ...]`` arrays from this process's local client
    block ``[C_local, ...]`` (the :func:`local_client_slice` rows)."""
    return {k: global_rows(sharding, v, num_clients) for k, v in local.items()}


def allgather_hosts(value: int) -> np.ndarray:
    """Every process's value of a host int scalar, as a numpy array.

    THE primitive for cross-host agreement (batch counts, eval row counts,
    warm-start decisions): every process must call it at the same program
    point. Single-process: the value alone, no collective."""
    if jax.process_count() == 1:
        return np.asarray([value], np.int64)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(np.int64(value)))


def global_array_from_replicated(
    sharding: NamedSharding, value: np.ndarray
) -> jax.Array:
    """Build a (possibly cross-process) sharded array from a host value that
    every process holds in full — used for initial stacked params, where all
    replicas start identical (the reference's shared-pretrained-start,
    client1.py:56)."""
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    return jax.make_array_from_callback(
        np.shape(value), sharding, lambda idx: value[idx]
    )
