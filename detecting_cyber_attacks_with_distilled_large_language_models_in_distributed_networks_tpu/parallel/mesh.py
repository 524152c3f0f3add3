"""Device-mesh construction for federated SPMD.

The reference's "cluster" is three OS processes on one laptop joined by
hand-rolled TCP (reference server.py:116-137). Here the cluster is a
``jax.sharding.Mesh`` with two axes:

* ``clients`` — federated replicas. Each shard of this axis holds a set of
  client model replicas + their private data shards; the FedAvg collective
  rides this axis (ICI within a slice, DCN across slices).
* ``data``    — per-client batch parallelism. Gradients sync over this axis:
  the mesh tier's lockstep step takes their mean itself, per shard
  (train/fedsteps.py ``_step_body``); in the programs left to the
  partitioner (the ragged step, the TCP client's mesh step) XLA inserts the
  psum when batch is sharded and params are replicated along it.

For multi-host TPU pods, call ``jax.distributed.initialize()`` before
building the mesh — ``jax.devices()`` then spans all hosts and the same
code scales out; this replaces the reference's socket rendezvous
(client1.py:276-336) with the TPU runtime's own bootstrap.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(
    clients: int = 1,
    data: int = 1,
    *,
    seq: int | None = None,
    devices: list | None = None,
    axis_names: tuple[str, ...] | None = None,
) -> Mesh:
    """A ``clients x data`` mesh over the first ``clients*data`` devices;
    ``seq`` adds the third (ring attention) axis for the fedseq
    composition (parallel/fedseq.py)."""
    dims = (clients, data) if seq is None else (clients, data, seq)
    if axis_names is None:
        axis_names = ("clients", "data", "seq")[: len(dims)]
    devs = list(jax.devices() if devices is None else devices)
    need = 1
    for d in dims:
        need *= d
    if len(devs) < need:
        raise ValueError(
            f"mesh {'x'.join(map(str, dims))} needs {need} devices, have "
            f"{len(devs)} (a virtual CPU mesh: JAX_PLATFORMS=cpu plus "
            "jax.config.update('jax_num_cpu_devices', N) before first use)"
        )
    grid = np.array(devs[:need]).reshape(dims)
    return Mesh(grid, axis_names)


def make_host_mesh(
    data: int = 1, *, seq: int | None = None, devices: list | None = None
) -> Mesh:
    """A single-host ``data`` (optionally ``data x seq``) mesh over this
    process's LOCAL devices — the separate-process TCP client's view of its
    own chips (cli/comm.py ``client --data-parallel N [--seq-parallel M]``).

    Unlike :func:`make_mesh` (global devices, ``clients`` leading axis),
    there is no federation axis here: federation happens over the wire, and
    every local chip serves one client's batch (and sequence) shards."""
    if data < 1 or (seq is not None and seq < 1):
        raise ValueError(f"host mesh axes must be >= 1 (data={data}, seq={seq})")
    devs = list(jax.local_devices() if devices is None else devices)
    dims = (data,) if seq is None else (data, seq)
    need = data * (seq or 1)
    if len(devs) < need:
        raise ValueError(
            f"host mesh {'x'.join(map(str, dims))} needs {need} local "
            f"devices, have {len(devs)}"
        )
    grid = np.array(devs[:need]).reshape(dims)
    return Mesh(grid, ("data",) if seq is None else ("data", "seq"))


def fit_clients_axis(num_clients: int, data: int, n_devices: int) -> int:
    """Largest clients-axis size that (a) divides the logical client count
    (several replicas may stack per mesh row) and (b) fits the hardware
    alongside the ``data`` axis. Raises when even one row doesn't fit."""
    rows = max(
        (
            r
            for r in range(1, num_clients + 1)
            if num_clients % r == 0 and r * data <= n_devices
        ),
        default=None,
    )
    if rows is None:
        raise ValueError(
            f"mesh data axis {data} alone exceeds the {n_devices} available "
            "devices"
        )
    return rows


@dataclass(frozen=True)
class FedShardings:
    """The three shardings federated training needs."""

    mesh: Mesh

    @property
    def client(self) -> NamedSharding:
        """Leading axis = clients: params/opt-state stacks ``[C, ...]``."""
        return NamedSharding(self.mesh, P("clients"))

    @property
    def batch(self) -> NamedSharding:
        """``[C, B, ...]``: clients on axis 0, per-client batch on axis 1."""
        return NamedSharding(self.mesh, P("clients", "data"))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


# ---------------------------------------------------------------- FSDP specs
def fsdp_dim(shape: tuple[int, ...], n_shards: int) -> int | None:
    """The dimension index FSDP shards ``shape`` over ``n_shards``, or
    None when the leaf stays replicated (scalar, or no dimension divides
    the axis). Deterministic and a pure function of (shape, n_shards) —
    the SAME choice on every process/round, which is what lets the wire
    tier scatter a decoded reply leaf straight onto its shard
    (train/client_mesh.py ``reply_leaf_sink``) without a negotiated
    layout. Largest divisible dimension wins (most bytes saved per
    shard); ties break to the lowest index."""
    if n_shards <= 1:
        return None
    best: int | None = None
    for i, d in enumerate(shape):
        if d % n_shards:
            continue
        if best is None or d > shape[best]:
            best = i
    return best


def fsdp_spec(
    shape: tuple[int, ...], n_shards: int, *, axis: str = "data"
) -> P:
    """Per-leaf FSDP ``PartitionSpec``: the chosen dimension (see
    :func:`fsdp_dim`) shards over ``axis``; everything else replicates."""
    dim = fsdp_dim(tuple(int(d) for d in shape), n_shards)
    if dim is None:
        return P()
    spec = [None] * len(shape)
    spec[dim] = axis
    return P(*spec)


def fsdp_sharding(
    mesh: Mesh, shape: tuple[int, ...], *, axis: str = "data"
) -> NamedSharding:
    """``NamedSharding`` form of :func:`fsdp_spec` for ``mesh``."""
    return NamedSharding(
        mesh, fsdp_spec(shape, int(mesh.shape[axis]), axis=axis)
    )


def fsdp_tree_shardings(tree, mesh: Mesh, *, axis: str = "data"):
    """Per-leaf shard-at-rest placement for an arbitrary state pytree:
    float/int array leaves get their :func:`fsdp_spec`; scalars, PRNG
    keys, and undividable leaves replicate. Works on concrete arrays and
    on ``ShapeDtypeStruct`` templates (only ``.shape`` is read)."""
    replicated = NamedSharding(mesh, P())

    def _leaf(x):
        shape = tuple(int(d) for d in np.shape(x))
        if not shape:
            return replicated
        dtype = getattr(x, "dtype", None)
        if dtype is not None:
            try:
                ok = np.issubdtype(np.dtype(dtype), np.floating) or (
                    np.issubdtype(np.dtype(dtype), np.integer)
                )
            except TypeError:
                # Typed PRNG keys (extended dtypes np.dtype can't parse)
                # and anything exotic replicate — bytes-trivial next to
                # params/moments.
                ok = False
            if not ok:
                return replicated
        return fsdp_sharding(mesh, shape, axis=axis)

    return jax.tree.map(_leaf, tree)


def shard_template(template, mesh: Mesh, *, axis: str = "data"):
    """Attach each leaf's FSDP ``NamedSharding`` to a ``ShapeDtypeStruct``
    restore template, so a sharding-aware checkpoint restore (orbax honors
    template shardings — train/checkpoint.py ``_abstract``) scatters every
    leaf straight onto its shard: the full-size array never materializes
    on any single chip, which is the whole point of serving a model bigger
    than one chip's memory."""
    import jax

    shardings = fsdp_tree_shardings(template, mesh, axis=axis)
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(
            tuple(int(d) for d in np.shape(t)),
            getattr(t, "dtype", np.float32),
            sharding=s,
        ),
        template,
        shardings,
    )


def fsdp_gather(mesh: Mesh):
    """The gather-AT-USE callable (the ``gather=`` side of the
    ``make_packed_step`` parameterization): constrain every leaf of a
    sharded tree to replicated, so XLA inserts the all-gather inside the
    jitted program right where the weights are consumed — full-size
    weights exist only transiently, never at rest."""
    replicated = NamedSharding(mesh, P())

    def gather(tree):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, replicated), tree
        )

    return gather


def fsdp_gather_program(tree, mesh: Mesh, *, note=None):
    """A SEPARATE jitted all-gather program: identity over ``tree`` with
    replicated ``out_shardings``, so executing it reconstructs every
    sharded leaf's exact full-size bytes on each chip.

    Why a second program instead of :func:`fsdp_gather`'s in-body
    constraint: a constraint gather splices 100+ all-gather ops into the
    consumer's HLO module, and XLA's fusion/layout choices around those
    collectives differ from the module it builds for the same math over
    replicated inputs — a data-dependent 1-ulp drift, with zero
    all-reduces or partitioned contractions in sight. The serving crc
    contract (sharded probs bit-identical to the replicated engine's,
    tests/test_serving_fsdp.py) needs the CONSUMER program compiled
    clean; splitting the gather out gives it byte-exact replicated
    inputs and an HLO module free of collectives. Gather-at-use
    semantics are unchanged — the program runs per dispatch and its
    output is dropped with the forward, so full-size weights still never
    exist at rest. The train step keeps the constraint form (its
    contract is replaying ITSELF, where one fused module is its own
    baseline).

    ``note``: optional trace-time callable (a
    ``CompileLedger.hook`` note) — runs once per compilation, so the
    caller's ledger flags a retrace of the gather program the same way
    it flags a bucket retrace."""
    replicated = NamedSharding(mesh, P())
    out = jax.tree.map(lambda _: replicated, tree)

    def _identity(t):
        if note is not None:
            note(("gather",))
        return t

    return jax.jit(_identity, out_shardings=out)


def fsdp_constrain(mesh: Mesh, *, axis: str = "data"):
    """The shard-at-rest callable (the ``constrain=`` side): pin every
    leaf of a tree back onto its :func:`fsdp_spec` shard, so step outputs
    (new params, optimizer moments, grads) land sharded instead of
    inheriting the gathered replicated layout."""

    def constrain(tree):
        shardings = fsdp_tree_shardings(tree, mesh, axis=axis)
        return jax.tree.map(
            jax.lax.with_sharding_constraint, tree, shardings
        )

    return constrain


def device_tree_bytes(tree) -> int:
    """Bytes ``tree``'s leaves occupy on ONE device (per leaf: the
    lowest-id device holding a shard of it) — the per-chip static-state
    accounting behind the ``fedtpu_fsdp_static_state_bytes`` gauge.
    Exact (addressable-shard nbytes, not an estimate) and backend-
    independent: it works on CPU virtual devices where
    ``device.memory_stats()`` is unavailable. A replicated leaf counts
    its full size (every chip holds a copy); a sharded leaf counts one
    shard."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += int(getattr(leaf, "nbytes", 0))
            continue
        first = min(shards, key=lambda s: s.device.id)
        total += sum(
            int(s.data.nbytes)
            for s in shards
            if s.device.id == first.device.id
        )
    return total
