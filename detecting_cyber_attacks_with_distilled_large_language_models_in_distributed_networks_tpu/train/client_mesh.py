"""Meshed local training for the separate-process TCP client.

The reference's real deployment shape is independent client processes
talking TCP to an aggregation server (reference client1.py:276-336); until
this module, our client on that tier trained its local phase on ONE device
no matter how many chips its host had. ``fedtpu client --data-parallel N
[--seq-parallel M]`` drives the local phase over the host's own device
mesh instead, reusing the existing meshed machinery:

* ``--data-parallel N`` alone -> :class:`MeshTrainer`: the single-client
  engine's OWN jitted programs (train/engine.py), dispatched with batch
  rows sharded over a per-host ``data`` mesh axis and params replicated —
  XLA inserts the gradient psum. Same math, same PRNG streams, same
  shuffles: the trajectory is threefry-identical to the single-device
  client (params agree to float32 reduction-order ulps — the per-shard
  partial sums round differently than one sequential reduction — which is
  below every metric's resolution).
* ``--seq-parallel M`` (with or without data shards) ->
  :class:`FedSeqClientTrainer`: a C=1 FedSeqTrainer over a local
  ``1 x data x seq`` mesh — ring attention over the sequence axis, the
  long-context composition (parallel/fedseq.py) behind the single-client
  surface the TCP round loop drives.

Both trainers keep the wire tier untouched: params gather to host as one
replica readback for the upload, and a received aggregate is scattered
straight onto the mesh by ``init_state`` (``adopt_aggregate``) — no
intermediate full-replica state on the host beyond the wire buffer
itself. Secure aggregation and central DP therefore compose unchanged:
masking and noising operate on the host-gathered flat vector exactly as
for the single-device client.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config import ExperimentConfig, ModelConfig, TrainConfig
from ..data.pipeline import TokenizedSplit, shard_rows, stack_clients
from ..parallel.mesh import (
    device_tree_bytes,
    fsdp_sharding,
    fsdp_tree_shardings,
    make_host_mesh,
)
from ..utils.logging import get_logger
from .engine import (
    Trainer,
    TrainState,
    make_fsdp_eval_step,
    make_fsdp_train_step,
)

log = get_logger()


class MeshTrainer(Trainer):
    """The single-client engine over a per-host ``data`` mesh axis.

    Reuses the engine's cached jitted programs verbatim; only placement
    changes — batch rows shard over ``data``, state replicates. A batch
    whose row count doesn't divide the axis (the final short batch under
    ``drop_remainder=False``) is placed replicated, keeping the math (and
    so the trajectory) identical to the single-device engine.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        *,
        mesh,
        pad_id: int = 0,
        drop_remainder: bool = True,
    ):
        super().__init__(
            model_cfg, train_cfg, pad_id=pad_id, drop_remainder=drop_remainder
        )
        self.mesh = mesh
        self.batch_sharding = NamedSharding(mesh, P("data"))
        self.replicated = NamedSharding(mesh, P())
        self._install_steps(
            self.train_step,
            self.eval_step,
            lambda p: jax.device_put(p, self.replicated),
        )

    def _install_steps(self, base_train, base_eval, place_params) -> None:
        """Wrap base jitted steps with the mesh tier's batch placement
        (rows over ``data``; a short batch that doesn't divide goes
        replicated, keeping the math identical) and ``place_params`` for
        the eval path — the ONE wrapper shape shared by the replicated
        and FSDP trainers, so batch-placement fixes can't drift apart."""

        def train_step(state, batch, *extra):
            # *extra: the FedProx anchor when TrainConfig.prox_mu > 0 —
            # it is already placed (a copy of live params, so it carries
            # their sharding); only the batch needs row placement.
            return base_train(
                state,
                shard_rows(batch, self.batch_sharding, self.replicated),
                *extra,
            )

        def eval_step(params, batch, valid):
            placed = shard_rows(
                {**batch, "valid": valid},
                self.batch_sharding,
                self.replicated,
            )
            return base_eval(
                params=place_params(params),
                batch={k: v for k, v in placed.items() if k != "valid"},
                valid=placed["valid"],
            )

        self.train_step = train_step
        self.eval_step = eval_step

    def init_state(
        self, seed: int | None = None, params: Any | None = None
    ) -> TrainState:
        """Build the engine state, then scatter it onto the mesh
        (replicated) — also the aggregate-adoption path, so a received
        round reply lands on every local device in one placement."""
        state = super().init_state(seed=seed, params=params)
        return jax.device_put(state, self.replicated)

    def evaluate(self, params: Any, split, **kw: Any) -> dict:
        """Place host params on the mesh ONCE before the batch sweep (the
        per-batch wrapper's device_put is then a no-op short-circuit —
        without this, a host aggregate would re-cross the device boundary
        on every eval batch)."""
        return super().evaluate(
            jax.device_put(params, self.replicated), split, **kw
        )

    def reply_leaf_sink(self, key: str, arr: np.ndarray) -> Any:
        """Streamed-reply leaf placement (comm/client.py
        ``reply_leaf_sink``): scatter one decoded aggregate leaf onto the
        local mesh (replicated) the moment its chunk bytes land, so the
        host->device transfer of leaf k overlaps the wire transfer of
        leaf k+1 and ``adopt_aggregate`` starts from device-backed
        buffers instead of a full host-side tree. ``init_state``'s
        later device_put of an already-placed leaf is a no-op, and the
        values are bit-identical to the host-tree path (placement only,
        no arithmetic)."""
        return jax.device_put(arr, self.replicated)


class FsdpMeshTrainer(MeshTrainer):
    """FSDP shard-at-rest over the per-host ``data`` mesh axis
    (``client --data-parallel N --fsdp``).

    :class:`MeshTrainer` buys batch throughput but replicates params AND
    Adam moments on every chip — the multi-chip tier stays memory-bound
    at the single-chip model ceiling. Here the static state shards at
    rest (per-leaf specs from ``parallel/mesh.fsdp_spec``: the largest
    axis-divisible dimension of each leaf over ``data``; undividable
    leaves replicate) and the jitted train step all-gathers params AT
    USE inside a remat region tagged so the backward RE-GATHERS instead
    of retaining full-size weights; gradients reduce-scatter back onto
    the shards and Adam updates run shard-local. Per-chip static bytes
    scale ~1/N (tests/test_mesh_fsdp.py holds the ratio to 0.6 at N=2).

    Contracts carried over from the replicated mesh:

    * trajectory: same threefry PRNG streams, same shuffles, same update
      arithmetic — params agree with the replicated/single-device client
      to fp32 reduction-order ulps (reduce-scatter may sum grad partials
      in a different order than the all-reduce; allclose-pinned, the
      PR-2/PR-7 documented class), metrics equal.
    * wire tier untouched: ``host_params`` gathers one full tree at the
      exchange/checkpoint boundary ONLY (``comm/client.py`` keeps the
      gather lazy via ``flatten_lazy`` — leaf k+1 gathers while chunk k
      streams), ``reply_leaf_sink`` scatters each decoded reply leaf
      straight onto its shard, so secure-agg/DP/streamed uploads compose
      unchanged.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        *,
        mesh,
        pad_id: int = 0,
        drop_remainder: bool = True,
    ):
        super().__init__(
            model_cfg,
            train_cfg,
            mesh=mesh,
            pad_id=pad_id,
            drop_remainder=drop_remainder,
        )
        self.n_shards = int(mesh.shape["data"])
        # Per-trainer memo of the jitted sharded optimizer.init (see
        # _init_opt_state — adopt_aggregate hits it every round).
        self._opt_init_jit = None
        # Replace the replicated base steps MeshTrainer installed with
        # the spec-parameterized FSDP programs; the batch-placement
        # wrapper shape is shared (_install_steps), only the base steps
        # and the eval params placement differ. The programs are
        # process-wide memoized on (configs, mesh) like the engine's —
        # same-config trainers (multi-round flows, the test suite) share
        # one set of compiled executables.
        from .engine import step_key_cfg

        base_train, base_eval = _fsdp_steps(
            model_cfg, step_key_cfg(train_cfg), mesh
        )
        # Eval params placement is identity per batch: evaluate() below
        # owns the ONE host->shard placement before the batch sweep, and
        # evaluate_state feeds the live (already sharded) state — a
        # per-batch place_state_tree would rebuild the whole per-leaf
        # sharding tree on every metrics batch for a guaranteed no-op.
        self._install_steps(base_train, base_eval, lambda params: params)

    # ------------------------------------------------------------ placement
    def leaf_sharding(self, shape) -> NamedSharding:
        """The shard-at-rest placement of one leaf — shape-deterministic
        (parallel/mesh.fsdp_spec), so the wire tier can place a decoded
        reply leaf with no layout negotiation."""
        return fsdp_sharding(self.mesh, tuple(int(d) for d in shape))

    def place_state_tree(self, tree: Any) -> Any:
        """Scatter a host (or replicated) tree onto its per-leaf shards;
        a leaf already living on its shard spec is a no-op."""
        return jax.device_put(tree, fsdp_tree_shardings(tree, self.mesh))

    def init_state(
        self, seed: int | None = None, params: Any | None = None
    ) -> TrainState:
        """Engine state scattered shard-at-rest — also the
        aggregate-adoption path: a received round reply lands directly on
        its shards (leaves the streamed-reply sink already placed pass
        through untouched), and fresh Adam moments materialize SHARDED
        (zeros_like of sharded params), never full-size per chip.
        The seed/PRNG/param-init sequence is the base Trainer's (the
        trajectory contract lives in ONE place); only placement differs,
        via the _place_init_params/_init_opt_state hooks below —
        MeshTrainer's replicated placement is deliberately skipped."""
        state = Trainer.init_state(self, seed=seed, params=params)
        # params are shard-at-rest via _place_init_params and the
        # moments via the jitted init's out_shardings — one placement
        # mechanism, nothing to re-place here (step/rng are scalar/key
        # leaves the first jitted step commits).
        self._note_static_bytes(state)
        return state

    def _place_init_params(self, params: Any) -> Any:
        return self.place_state_tree(params)

    def _init_opt_state(self, params: Any) -> Any:
        # Jitted init with EXPLICIT out_shardings: zeros_like moments
        # materialize directly ON their shards — never full-size per
        # chip. Propagation from the sharded params alone is not enough
        # (measured: it replicates the moments), so the at-rest layout
        # is pinned from the eval_shape template. The wrapper is cached
        # per trainer — init_state runs on EVERY round's aggregate
        # adoption, and a fresh jax.jit per call would re-trace there.
        fn = self._opt_init_jit
        if fn is None:
            template = jax.eval_shape(self.optimizer.init, params)
            fn = self._opt_init_jit = jax.jit(
                self.optimizer.init,
                out_shardings=fsdp_tree_shardings(template, self.mesh),
            )
        return fn(params)

    def _note_static_bytes(self, state: TrainState) -> None:
        """Per-chip static-state accounting gauge
        (``fedtpu_fsdp_static_state_bytes``): exact addressable-shard
        bytes of params + optimizer state on one device — the number
        tests/test_mesh_fsdp.py's ratio is built from, exported so a live client
        shows its sharding actually engaged."""
        from ..obs.metrics import default_registry

        default_registry().gauge(
            "fedtpu_fsdp_static_state_bytes",
            help="per-device bytes of FSDP shard-at-rest params + "
            "optimizer state",
        ).set(
            float(
                device_tree_bytes((state.params, state.opt_state))
            )
        )

    # ----------------------------------------------------------- wire tier
    def evaluate(self, params: Any, split, **kw: Any) -> dict:
        """Place host params onto their shards ONCE before the batch
        sweep (the per-batch wrapper's placement is then a no-op).
        Skips MeshTrainer.evaluate — its replicated device_put would
        un-shard the tree (a full copy per chip, exactly what FSDP
        exists to avoid)."""
        return Trainer.evaluate(
            self, self.place_state_tree(params), split, **kw
        )

    def host_params(self, state) -> Any:
        """The wire-upload form WITHOUT an eager device->host gather:
        leaves stay device-backed on their shards, so the streamed
        upload's packer (comm/client.py: ``wire.flatten_lazy`` plans
        from shape/dtype metadata, ``_stream_upload`` np.asarray's one
        leaf at a time) gathers leaf k+1 off its shards while chunk k
        is already on the wire — at no point does a full host-side tree
        exist beyond the in-flight leaf. The dense/DP/secure paths call
        ``_host_params`` on this tree themselves (one gather per
        exchange); values are identical either way."""
        return state.params

    def reply_leaf_sink(self, key: str, arr: np.ndarray) -> Any:
        """Streamed-reply leaf placement: scatter one decoded aggregate
        leaf DIRECTLY ONTO ITS SHARD the moment its chunk bytes land —
        the FSDP twin of MeshTrainer's replicated sink, so adoption
        never materializes a full host-side tree AND never replicates a
        leaf that is about to live sharded anyway. Values bit-identical
        to the host-tree path (placement only, no arithmetic)."""
        return jax.device_put(arr, self.leaf_sharding(np.shape(arr)))


@lru_cache(maxsize=None)
def _fsdp_steps(model_cfg: ModelConfig, key_cfg: TrainConfig, mesh):
    """Process-wide memo of the FSDP jitted programs, keyed on the
    frozen configs + the mesh they are pure functions of (the caller
    canonicalizes step-irrelevant TrainConfig fields out, exactly like
    engine._engine_steps — and two ``make_host_mesh(N)`` calls over the
    same devices compare equal, so same-shape trainers share one set of
    compiled executables). gather/constrain are pure functions of the
    mesh: gather places every leaf replicated (the all-gather-at-use);
    constrain pins a tree back onto its shard-at-rest specs
    (the reduce-scatter / shard-at-rest layout)."""
    from .engine import _engine_steps

    model, optimizer, _, _ = _engine_steps(model_cfg, key_cfg)
    replicated = NamedSharding(mesh, P())

    def gather(params):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, replicated),
            params,
        )

    def constrain(tree):
        # fsdp_tree_shardings is the ONE layout definition (dtype-guarded:
        # non-float/int leaves replicate) — the same call init_state/
        # place_state_tree place at-rest state with, so the in-step
        # constraint can never disagree with the adoption path's layout.
        # Works on tracers too (only .shape/.dtype are read).
        return jax.tree.map(
            jax.lax.with_sharding_constraint,
            tree,
            fsdp_tree_shardings(tree, mesh),
        )

    return (
        make_fsdp_train_step(
            model,
            optimizer,
            key_cfg.warmup_steps,
            prox_mu=key_cfg.prox_mu,
            gather=gather,
            constrain=constrain,
        ),
        make_fsdp_eval_step(model, gather=gather),
    )


class FedSeqClientTrainer:
    """C=1 FedSeqTrainer behind the TCP client's single-client surface.

    The sequence-parallel composition (ring attention over a ``seq`` mesh
    axis, optional batch shards over ``data``) already exists as the
    3-axis federated trainer; a fleet of one reuses it wholesale. The
    trajectory is the fedseq one (hash-keyed dropout, federated batch
    permutations) — shard-count-invariant on its own terms, but distinct
    from the single-device engine's; use plain ``--data-parallel`` when
    byte-level parity with the single-device client matters.
    """

    def __init__(self, cfg: ExperimentConfig, *, pad_id: int = 0):
        from ..parallel.fedseq import make_seq_mesh
        from .seqfed import FedSeqTrainer

        self.cfg = dataclasses.replace(
            cfg,
            fed=dataclasses.replace(cfg.fed, num_clients=1),
            mesh=dataclasses.replace(cfg.mesh, clients=1),
        )
        mesh = make_seq_mesh(
            1, cfg.mesh.data, cfg.mesh.seq, devices=jax.local_devices()
        )
        self.inner = FedSeqTrainer(self.cfg, pad_id=pad_id, mesh=mesh)
        self.mesh = mesh
        self.pad_id = pad_id
        # Single-entry caches keyed on split identity: the TCP round loop
        # feeds the SAME split objects every round, and re-stacking the
        # full train set (or re-padding the eval set, twice per round)
        # is pure wasted host memory traffic (prepare_eval's own contract
        # is pad once, reuse across rounds).
        self._train_cache: tuple[Any, Any] | None = None
        self._eval_cache: tuple[Any, int | None, Any] | None = None

    def init_state(self, seed: int | None = None, params: Any | None = None):
        return self.inner.init_state(seed=seed, params=params)

    def fit(
        self,
        state,
        split: TokenizedSplit,
        *,
        batch_size: int = 16,
        epochs: int | None = None,
        epoch_offset: int = 0,
        tag: str = "",
    ):
        """E local epochs over the dense [1, N, ...] stack; returns the
        engine-shaped per-epoch loss list. ``tag`` (the TCP round loop's
        ``[CLIENT n]`` prefix) rides the inner trainer's step telemetry so
        mixed-fleet logs stay attributable."""
        if tag:
            self.inner.telemetry_prefix = tag
        if self._train_cache is None or self._train_cache[0] is not split:
            self._train_cache = (split, stack_clients([split]))
        stacked = self._train_cache[1]
        state, losses = self.inner.fit_local(
            state,
            stacked,
            batch_size=batch_size,
            epochs=epochs,
            epoch_offset=epoch_offset,
        )
        return state, [float(e[0]) for e in losses]

    def evaluate(
        self,
        params: Any,
        split: TokenizedSplit,
        *,
        batch_size: int | None = None,
        collect_probs: bool = True,
    ) -> dict:
        """Five reference metrics for UNSTACKED params (e.g. a received
        aggregate): stack to [1, ...], run the 3-axis eval sweep."""
        from ..parallel.fedavg import stack_params

        stacked = jax.device_put(
            stack_params(jax.tree.map(np.asarray, params), 1),
            self.inner.sh.client,
        )
        return self._evaluate_stacked(
            stacked, split, batch_size=batch_size, collect_probs=collect_probs
        )

    def evaluate_state(
        self, state, split: TokenizedSplit, *, collect_probs: bool = True
    ) -> dict:
        """Metrics straight from the (already stacked) live state."""
        return self._evaluate_stacked(
            state.params, split, collect_probs=collect_probs
        )

    def _evaluate_stacked(
        self,
        stacked_params,
        split: TokenizedSplit,
        *,
        batch_size: int | None = None,
        collect_probs: bool = True,
    ) -> dict:
        # Normalize the default BEFORE keying the cache: the round loop's
        # local eval (evaluate_state, batch_size=None) and aggregated eval
        # (evaluate) must share one prepared entry, and both default to
        # the config's eval batch size.
        if batch_size is None:
            batch_size = self.inner.cfg.data.eval_batch_size
        cache = self._eval_cache
        if cache is None or cache[0] is not split or cache[1] != batch_size:
            cache = self._eval_cache = (
                split,
                batch_size,
                self.inner.prepare_eval([split], batch_size=batch_size),
            )
        return self.inner.evaluate_clients(
            stacked_params, prepared=cache[2], collect_probs=collect_probs
        )[0]

    def prefetch_epoch(
        self, split: TokenizedSplit, epoch: int, batch_size: int, *, k: int = 2
    ):
        """Arm the inner fedseq trainer's epoch prefetch for the stacked
        form of ``split`` (the same cached stack ``fit`` trains on), so
        the TCP round loop can hide reply latency behind the next round's
        first batch gathers — mirroring engine.Trainer.prefetch_epoch."""
        if self._train_cache is None or self._train_cache[0] is not split:
            self._train_cache = (split, stack_clients([split]))
        return self.inner.prefetch_epoch(
            self._train_cache[1], epoch, batch_size, k=k
        )

    def step_profile_attrs(self) -> dict:
        """The inner fedseq trainer's sampled step attrs (obs/profile.py)
        — the TCP round loop stamps them on the client-local span."""
        prof = self.inner.step_profiler
        return prof.span_attrs() if prof is not None else {}

    def host_params(self, state) -> Any:
        """One replica of the single client's params, unstacked, on host —
        the wire-upload form."""
        return jax.tree.map(lambda x: np.asarray(x)[0], state.params)

    def adopt_aggregate(self, state, aggregated: Any):
        """Fresh Adam from the received aggregate, continuing step counter
        — the shared adoption semantics (engine.py); init_state scatters
        the aggregate onto the 3-axis mesh."""
        from .engine import adopt_aggregate_with_fresh_opt

        return adopt_aggregate_with_fresh_opt(self, state, aggregated)


def make_client_trainer(
    cfg: ExperimentConfig, *, pad_id: int = 0
) -> Trainer | FedSeqClientTrainer:
    """The TCP client's local-phase trainer for the resolved mesh config:
    plain engine (1x1), data-parallel meshed engine (Nx1) — replicated or
    FSDP shard-at-rest (``--fsdp``) — or the C=1 sequence-parallel
    composition (NxM, M > 1)."""
    data, seq = cfg.mesh.data, cfg.mesh.seq
    if data > 1 and cfg.data.batch_size % data:
        # Both branches: fail at construction with an operator-readable
        # message, not mid-round with an XLA sharding traceback.
        raise ValueError(
            f"batch_size={cfg.data.batch_size} must divide over "
            f"--data-parallel {data} (row shards)"
        )
    if cfg.mesh.fsdp:
        # (MeshConfig validates fsdp needs data >= 2 and no seq axis;
        # make_host_mesh validates the local device count.)
        if cfg.train.prng_impl != "threefry2x32":
            log.warning(
                f"[CLIENT-FSDP] prng_impl={cfg.train.prng_impl!r}: dropout "
                "masks are not shard-invariant under this impl; set "
                "train.prng_impl='threefry2x32' for replicated-mesh parity"
            )
        return FsdpMeshTrainer(
            cfg.model,
            cfg.train,
            mesh=make_host_mesh(data),
            pad_id=pad_id,
            drop_remainder=cfg.data.drop_remainder,
        )
    if seq > 1:
        # (FedSeqTrainer's own __init__ validates max_len % seq and the
        # local device count, also as ValueError.)
        return FedSeqClientTrainer(cfg, pad_id=pad_id)
    if data > 1:
        if cfg.train.prng_impl != "threefry2x32":
            # rbg/unsafe_rbg bits are NOT guaranteed identical across
            # shardings of one computation (JAX PRNG docs), so dropout
            # masks — and with them the trajectory — can diverge from the
            # single-device client. Training is still correct; only the
            # strict single-device parity needs threefry.
            log.warning(
                f"[CLIENT-MESH] prng_impl={cfg.train.prng_impl!r}: dropout "
                "masks are not shard-invariant under this impl, so the "
                "--data-parallel trajectory may diverge from the "
                "single-device client's; set train.prng_impl="
                "'threefry2x32' for threefry-identical parity"
            )
        return MeshTrainer(
            cfg.model,
            cfg.train,
            mesh=make_host_mesh(data),
            pad_id=pad_id,
            drop_remainder=cfg.data.drop_remainder,
        )
    return Trainer(
        cfg.model,
        cfg.train,
        pad_id=pad_id,
        drop_remainder=cfg.data.drop_remainder,
    )
