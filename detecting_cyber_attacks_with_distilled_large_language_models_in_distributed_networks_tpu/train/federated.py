"""Federated multi-round training driver — SPMD over a ``clients`` mesh axis.

Replaces the reference's entire process topology (client1.py + client2.py +
server.py: N near-identical scripts, a threaded TCP server, gzip-pickled
state dicts, two ports, retry budgets) with:

* one stacked parameter pytree ``[C, ...]`` sharded over the ``clients`` mesh
  axis — client c's replica lives on its own submesh;
* one jitted train step, per shard — every client advances in lockstep, each
  on its private data shard; within a client, batch rows shard over the
  ``data`` axis and the step takes the mean of the shards' gradients
  (train/fedsteps.py ``_step_body``);
* the round boundary is ``fedavg`` (parallel/fedavg.py) — a single collective,
  no server process, no serialization, no sockets;
* per-client local-vs-aggregated evaluation identical in shape to the
  reference flow (train -> local eval -> aggregate -> aggregated eval,
  client1.py:379-404).

The reference achieves multi-round FL only by re-running processes with
warm-start .pth files (client1.py:375-377); here rounds are a loop, with
optimizer state optionally reset per round to mirror the reference's
fresh-Adam-per-run semantics (FedConfig.reset_optimizer_each_round).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ExperimentConfig
from ..data.pipeline import StackedClients, TokenizedSplit
from ..models import build_classifier, init_params
from ..obs.profile import maybe_step_profiler, note_memory, profiled_step_iter
from ..obs.trace import annotate, annotate_iter
from ..parallel.fedavg import stack_params
from ..parallel.mesh import FedShardings, make_mesh
from ..parallel.multihost import global_array_from_replicated
from ..train.engine import make_optimizer
from ..utils.logging import get_logger, phase

# Re-exports: batch iterators, eval plumbing, and jitted-step builders
# split out of this file; importing them from here keeps the historical API.
from .batches import (  # noqa: F401
    PrefetchSlot,
    federated_batches,
    federated_batches_ragged,
)
from .fedeval import (  # noqa: F401
    PreparedEval,
    evaluate_stacked,
    stack_eval_splits,
)
from .fedsteps import (  # noqa: F401
    FedState,
    aggregate_round,
    cached_federated_steps,
)

log = get_logger()


@dataclass
class RoundRecord:
    round: int
    epoch_losses: np.ndarray  # [E, C]
    local_metrics: list[dict]
    aggregated_metrics: list[dict] = field(default_factory=list)


class FederatedTrainer:
    """N-client FedAvg on a ``clients x data`` mesh."""

    def __init__(self, cfg: ExperimentConfig, *, pad_id: int = 0, mesh=None):
        self.cfg = cfg
        self.C = cfg.fed.num_clients
        self.pad_id = pad_id
        # Multi-host: the caller bootstraps jax.distributed (multihost.py
        # initialize) and passes a global mesh (make_global_mesh); each
        # process then feeds only its own client rows. Single process is the
        # degenerate case of the same code path.
        self.P = jax.process_count()
        if mesh is not None:
            self.mesh = mesh
        else:
            rows = cfg.mesh.clients
            n_dev = len(jax.devices())
            if self.P == 1 and rows * cfg.mesh.data > n_dev:
                # Fit the mesh to the hardware: stack several logical client
                # replicas per row rather than refusing to run (tested up to
                # 64 logical clients on 8 rows).
                from ..parallel.mesh import fit_clients_axis

                rows = fit_clients_axis(self.C, cfg.mesh.data, n_dev)
                log.info(
                    f"[FED] {self.C} clients on {n_dev} device(s): mesh "
                    f"{cfg.mesh.clients}x{cfg.mesh.data} -> "
                    f"{rows}x{cfg.mesh.data} "
                    f"({self.C // rows} client replicas per row)"
                )
            self.mesh = make_mesh(
                rows, cfg.mesh.data, axis_names=cfg.mesh.axis_names
            )
        if self.P > 1:
            from ..parallel.multihost import local_client_slice

            mesh_rows = self.mesh.devices.shape[0]
            self.client_offset = local_client_slice(self.mesh).start * (
                self.C // mesh_rows
            )
        else:
            self.client_offset = 0
        self.sh = FedShardings(self.mesh)
        self.model = build_classifier(cfg.model)
        self.optimizer = make_optimizer(cfg.train)
        # Observability (obs/trace.py): set by the CLI (or any caller) to
        # emit per-round client-local/agg phase spans; None by default —
        # the global tracer (set_global_tracer) is the fallback so
        # embedded constructions need no plumbing.
        self.tracer = None
        # Step-time attribution (obs/profile.py): None unless profiling
        # is armed process-wide; re-checked at fit time because the CLI
        # installs the stride after trainers are built.
        self.step_profiler = maybe_step_profiler("train")
        # One-slot epoch prefetch (train/batches.PrefetchSlot), armed
        # by prefetch_epoch while the round's wire exchange is in flight;
        # _epoch_batches consumes a matching key, so the batch sequence
        # is identical prefetched or not.
        self._prefetch = PrefetchSlot()
        self._build_steps()

    # ---------------------------------------------------------- jitted steps
    def _build_steps(self) -> None:
        """Delegates jitted-program construction to fedsteps (pure function
        of config/model/optimizer/shardings); keeps only the lifecycle
        state this trainer owns — lazy ragged compilation and the DP noise
        seed (OS entropy + multi-host agreement)."""
        steps = cached_federated_steps(self.cfg, self.mesh)
        self.train_step = steps.train_step
        self.eval_step = steps.eval_step
        self.fedavg_step = steps.fedavg_step
        self.server_tx = steps.server_tx
        self.server_agg_step = steps.server_agg_step
        self.dp_fedavg_step = steps.dp_fedavg_step
        self._opt_init = steps.opt_init
        self._replicate = steps.replicate
        # Built on first ragged fit_local (equal-client runs never pay the
        # extra compilation).
        self._build_ragged_step = steps.build_ragged_step
        self._ragged_train_step = None
        # Client-packing fast path (single-device mesh): built lazily on
        # the first eligible fit_local.
        self._build_packed_step = steps.build_packed_step
        self._packed_step = None
        if self.dp_fedavg_step is not None:
            # Noise seed: fresh OS entropy (the training seed is public
            # config — noise derived from it could be regenerated and
            # subtracted, voiding the guarantee). dp_seed overrides for
            # reproducible tests. Multi-host: everyone adopts process 0's
            # draw so the SPMD noise is globally consistent.
            seed = self.cfg.fed.dp_seed
            if seed is None:
                import os as _os

                seed = int.from_bytes(_os.urandom(8), "little") >> 1
            if self.P > 1:
                from ..parallel.multihost import allgather_hosts

                seed = int(allgather_hosts(seed)[0])
            self._dp_seed = seed

    def _host(self, tree: Any) -> Any:
        """np.asarray over a (possibly clients-sharded) pytree."""
        if self.P > 1:
            tree = self._replicate(tree)
        return jax.tree.map(np.asarray, tree)

    def _feed(self, batch: dict[str, np.ndarray]) -> dict[str, Any]:
        """Process-local [C_local, B, ...] host batch -> global sharded feed."""
        from ..parallel.multihost import global_batch

        return global_batch(self.sh.batch, batch, self.C)

    # -------------------------------------------------------------- lifecycle
    def init_state(self, seed: int | None = None, params: Any | None = None) -> FedState:
        """All clients start from the same initial params — the reference's
        condition (every client loads the same pretrained DistilBERT,
        client1.py:56)."""
        seed = self.cfg.train.seed if seed is None else seed
        impl = self.cfg.train.prng_impl
        rng = jax.random.key(seed, impl=impl)
        if params is None:
            params = init_params(self.model, self.cfg.model, rng)
        C = self.C

        rngs = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.fold_in(rng, 7), jnp.arange(C)
        )
        # Every leaf goes out committed where the jitted steps return it
        # (params, optimizer state and keys by clients, the counter
        # replicated): a first call on other placements than the second
        # call's would trace the step twice.
        step = global_array_from_replicated(
            self.sh.replicated, np.zeros((), np.int32)
        )
        if self.P == 1:
            stacked_params = jax.device_put(
                stack_params(params, C), self.sh.client
            )
            rngs = jax.device_put(rngs, self.sh.client)
        else:
            # Every process computed identical params from the same seed
            # (the reference's shared-pretrained-start, client1.py:56);
            # assemble the global [C, ...] stack from those replicas.
            stacked_params = jax.tree.map(
                lambda x: global_array_from_replicated(
                    self.sh.client,
                    np.broadcast_to(np.asarray(x)[None], (C, *np.shape(x))),
                ),
                params,
            )
            rngs = jax.random.wrap_key_data(
                global_array_from_replicated(
                    self.sh.client, np.asarray(jax.random.key_data(rngs))
                ),
                impl=impl,
            )
        opt_state = self._opt_init(stacked_params)
        server_opt = None
        if self.server_tx is not None:
            # Single-model fp32 state (replicated); every host computes the
            # identical init from the identical starting params.
            server_opt = self.server_tx.init(
                jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
            )
            if self.P > 1:
                # Like params/rngs above: promote host-local replicas to
                # global replicated arrays, or the jitted steps reject the
                # process-local device placement.
                server_opt = jax.tree.map(
                    lambda x: global_array_from_replicated(
                        self.sh.replicated, np.asarray(x)
                    ),
                    server_opt,
                )
        return FedState(
            params=stacked_params,
            opt_state=opt_state,
            step=step,
            rngs=rngs,
            server_opt=server_opt,
        )

    def reset_optimizer(self, state: FedState) -> FedState:
        with annotate("reset"):
            return state._replace(opt_state=self._opt_init(state.params))

    def personalize(
        self,
        state: FedState,
        stacked_train,
        *,
        epochs: int | None = None,
        scope: str | None = None,
    ) -> tuple[FedState, "np.ndarray"]:
        """FedAvg + local fine-tuning: train each client's replica on its
        own shard from the current (typically just-aggregated) params,
        WITHOUT a closing aggregate — the result is per-client
        personalized models, the third evaluation phase next to the
        reference's local/aggregated pair. ``scope="head"`` freezes the
        shared encoder and adapts only the classifier (FedPer); ``"full"``
        fine-tunes everything (FedAvg+FT). Runs the same SPMD fit as a
        round, so it composes with ragged stacks and multi-host meshes."""
        from dataclasses import replace as dc_replace

        epochs = self.cfg.fed.personalize_epochs if epochs is None else epochs
        scope = self.cfg.fed.personalize_scope if scope is None else scope
        if epochs <= 0:
            raise ValueError("personalize needs epochs > 0")
        if scope not in ("full", "head"):
            raise ValueError(f"personalize scope {scope!r} must be full|head")
        # Build a scope-matched trainer in EITHER direction: head scope on
        # an all-params config, or full scope on a linear-probing
        # (trainable='head') base config. type(self) keeps the subclass'
        # step builders — a FedSeqTrainer personalizes with the same
        # 3-axis sequence-parallel programs it trained with.
        want_trainable = "head" if scope == "head" else "all"
        if self.cfg.train.trainable != want_trainable:
            ptrainer = type(self)(
                dc_replace(
                    self.cfg,
                    train=dc_replace(self.cfg.train, trainable=want_trainable),
                ),
                pad_id=self.pad_id,
                mesh=self.mesh,
            )
        else:
            ptrainer = self
        # Personalization is a SIDE BRANCH: the jitted steps donate their
        # input buffers, so train on copies of the leaves that survive
        # into the branch (params/rngs/step/server state) — the caller's
        # aggregate state stays alive for reporting/checkpointing. The
        # optimizer state is NOT copied: it is rebuilt fresh under the
        # (possibly masked) personal optimizer (same policy as the
        # per-round reset), and copying the stacked Adam moments first
        # would transiently double the largest allocation on the mesh.
        import jax.numpy as jnp

        params = jax.tree.map(jnp.copy, state.params)
        state = state._replace(
            params=params,
            opt_state=ptrainer._opt_init(params),
            step=jnp.copy(state.step),
            rngs=jnp.copy(state.rngs),
            server_opt=jax.tree.map(jnp.copy, state.server_opt),
        )
        return ptrainer.fit_local(state, stacked_train, epochs=epochs)

    # ---------------------------------------------------------------- phases
    def _epoch_batches(self, stacked_train, bs: int, epoch: int):
        """One epoch's ``[C, B, ...]`` iterator, served from an armed
        matching prefetch when available (same permutation keying, so
        the sequence is identical either way)."""
        it = self._prefetch.consume((id(stacked_train), int(epoch), bs))
        if it is not None:
            return it
        return self._epoch_iterator(stacked_train, bs, epoch)

    def _epoch_iterator(self, stacked_train, bs: int, epoch: int):
        """The epoch's lockstep iterator — the SINGLE derivation of its
        permutation keying, shared by the live path and the armed
        prefetch so a prefetched head can never train on different
        batches."""
        return federated_batches(
            stacked_train,
            bs,
            seed=self.cfg.train.seed,
            epoch=epoch,
            client_offset=self.client_offset,
        )

    def prefetch_epoch(
        self, stacked_train, epoch: int, batch_size: int | None = None,
        *, k: int = 2,
    ):
        """Arm the one-slot background prefetch for ``epoch``'s first
        ``k`` lockstep batches (permutation + row gathers) — called by
        round loops right before blocking on a wire exchange, so reply
        latency hides input-pipeline work. Dense stacks only; a ragged
        (StackedClients) input is ignored (its iterator is built per
        epoch inside the ragged path). Returns the EpochPrefetcher (or
        None when ignored) so the caller can report its measured span."""
        from ..data.pipeline import StackedClients as _SC

        if isinstance(stacked_train, _SC):
            return None
        bs = self.cfg.data.batch_size if batch_size is None else int(batch_size)
        return self._prefetch.arm(
            (id(stacked_train), int(epoch), bs),
            lambda: self._epoch_iterator(stacked_train, bs, epoch),
            k=k,
        )

    def _armed_profiler(self):
        """The fit loops' shared step profiler: the one built at
        construction, or a late arm when the CLI installed the stride
        afterwards, with a fresh reporting window either way (the same
        helper shape as engine.Trainer._armed_profiler — the dense and
        packed loops must not drift). None = profiling off."""
        prof = self.step_profiler
        if prof is None:
            prof = self.step_profiler = maybe_step_profiler("train")
        if prof is not None:
            prof.begin_window()
        return prof

    def fit_local(
        self,
        state: FedState,
        stacked_train: TokenizedSplit | StackedClients,
        *,
        batch_size: int | None = None,
        epochs: int | None = None,
        epoch_offset: int = 0,
    ) -> tuple[FedState, np.ndarray]:
        """E local epochs for all clients in lockstep; returns ``[E, C]``
        per-client average losses.

        A :class:`StackedClients` input takes the ragged path: every
        client's full split trains each epoch (row-masked batches, gated
        updates); a plain :class:`TokenizedSplit` takes the dense path
        (all clients share one row count).

        Instrumented at THIS entry (not in run()): both round-loop owners
        — run() and the CLI's own loop — emit one ``client-local`` obs
        span per call, with the round derived from ``epoch_offset`` (the
        loops pass ``r * epochs_per_round``)."""
        # Arm (and window-reset) the profiler HERE, once per fit — the
        # dense and packed loops below read the armed instance, and a
        # ragged fit (unprofiled) still resets the window so its span
        # never carries a previous fit's samples.
        prof = self._armed_profiler()
        t_unix = time.time()
        t0 = time.monotonic()
        with annotate("fit"):
            out = self._fit_local_impl(
                state,
                stacked_train,
                batch_size=batch_size,
                epochs=epochs,
                epoch_offset=epoch_offset,
            )
        self._trace_phase(
            "client-local",
            t_unix,
            time.monotonic() - t0,
            epoch_offset // max(self.cfg.train.epochs_per_round, 1),
            # Sampled step-time attribution (obs/profile.py): host vs
            # dispatch vs device p50/p95 ride the span so the timeline
            # can render the device-vs-host row. {} when profiling off.
            **(prof.span_attrs() if prof is not None else {}),
        )
        return out

    def _fit_local_impl(
        self,
        state: FedState,
        stacked_train: TokenizedSplit | StackedClients,
        *,
        batch_size: int | None = None,
        epochs: int | None = None,
        epoch_offset: int = 0,
    ) -> tuple[FedState, np.ndarray]:
        if isinstance(stacked_train, StackedClients):
            return self._fit_local_ragged(
                state,
                stacked_train,
                batch_size=batch_size,
                epochs=epochs,
                epoch_offset=epoch_offset,
            )
        bs = self.cfg.data.batch_size if batch_size is None else batch_size
        E = self.cfg.train.epochs_per_round if epochs is None else epochs
        # Hosts must execute identical train-step counts (each step is a
        # collective); bound every epoch by the global minimum batch count.
        # The zero-batch check runs AFTER the allgather so an undersized
        # host raises on every process instead of deadlocking the others
        # inside the collective.
        n_batches = stacked_train.labels.shape[1] // bs
        if self.P > 1:
            n_batches = int(self._allgather(n_batches).min())
        if n_batches == 0:
            raise ValueError(
                f"common per-client train rows ({stacked_train.labels.shape[1]}) "
                f"< batch_size ({bs}) on at least one host: zero batches per "
                "epoch. Stack with stack_clients_ragged to train tiny "
                "clients without dragging the fleet down."
            )
        if self._packed_eligible():
            return self._fit_local_packed(
                state,
                stacked_train,
                bs=bs,
                E=E,
                epoch_offset=epoch_offset,
                n_batches=n_batches,
            )
        if self.cfg.fed.prox_mu > 0.0:
            # FedProx anchor: the round-start params, copied so the donated
            # state buffers never alias it.
            anchor = jax.tree.map(jnp.copy, state.params)
            step = lambda s, b: self.train_step(s, b, anchor)  # noqa: E731
        else:
            step = self.train_step
        out = []
        telemetry = self._step_telemetry()
        prof = self.step_profiler  # armed + window-reset by fit_local
        first_memory = prof is not None
        last_loss = None  # carried ACROSS epochs: the drain fence target
        for epoch in range(epoch_offset, epoch_offset + E):
            losses = []
            batches = self._epoch_batches(stacked_train, bs, epoch)
            fed = (
                (b, self._feed(b)) for _, b in zip(range(n_batches), batches)
            )
            for (batch, feed), sampled in profiled_step_iter(
                prof, annotate_iter("fit/next_batch", fed)
            ):
                if sampled:
                    # Fenced sampled step (obs/profile.py): drain the
                    # async backlog, then split dispatch from device.
                    prof.drain(last_loss)
                    t_d = prof.clock()
                    state, loss = step(state, feed)
                    prof.note_dispatch(prof.clock() - t_d)
                    prof.fence(loss)
                else:
                    state, loss = step(state, feed)
                losses.append(loss)
                last_loss = loss
                telemetry(loss, batch["labels"].size)
                if first_memory:
                    first_memory = False
                    note_memory("post-first-step")
            epoch_avg = jnp.stack(losses).mean(axis=0) if losses else jnp.zeros(self.C)
            with annotate("fit/loss_read"):
                out.append(self._host(epoch_avg))
            for c in range(self.C):
                log.info(
                    f"Client {c} Epoch [{epoch - epoch_offset + 1}/{E}], "
                    f"Average Loss: {out[-1][c]:.4f}"
                )
        return state, np.stack(out) if out else np.zeros((0, self.C))

    @property
    def _slice_client(self):
        """Jitted per-client tree slicer (memoized on the trainer)."""
        fn = getattr(self, "_slice_client_fn", None)
        if fn is None:

            def slice_client(tree, c):
                return jax.tree.map(lambda x: x[c], tree)

            fn = jax.jit(slice_client, static_argnums=1)
            self._slice_client_fn = fn
        return fn

    @property
    def _unstack_fn(self):
        """Jitted, memoized stacked->per-client splitter. NOT donated:
        a stacked ``[C, ...]`` input buffer can never alias its per-client
        output slices (each is 1/C the bytes), so a declared donation is
        structurally unusable — XLA copies anyway and warns "Some donated
        buffers were not usable" on every packed fit (VERDICT r5
        weak #2). The eager-free contract the donation was buying (the
        packed fit must not pin the stacked originals alongside the
        per-client copies; Python references in caller frames keep the
        FedState alive) is enforced in :meth:`_unstack_cstates` by
        explicitly deleting the stacked buffers after the split — same
        invalidation semantics the donation had, zero warnings."""
        fn = getattr(self, "_unstack_fn_cache", None)
        if fn is None:
            C = self.C

            def unstack_clients(params, opt_state):
                return (
                    [jax.tree.map(lambda x: x[c], params) for c in range(C)],
                    [
                        jax.tree.map(lambda x: x[c], opt_state)
                        for c in range(C)
                    ],
                )

            fn = jax.jit(unstack_clients)
            self._unstack_fn_cache = fn
        return fn

    @property
    def _restack_fn(self):
        """Jitted, memoized per-client->stacked assembler (a fresh jit
        per fit would re-trace the full params+opt stacking program every
        round)."""
        fn = getattr(self, "_restack_fn_cache", None)
        if fn is None:

            def restack_clients(*trees):
                return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

            fn = jax.jit(restack_clients, out_shardings=self.sh.client)
            self._restack_fn_cache = fn
        return fn

    def _unstack_cstates(self, state: FedState) -> list:
        """FedState -> per-client ``(params, opt_state, step, rng)``
        tuples for the packed step. CONSUMES the stacked params/opt
        buffers (explicit delete after the split — see :attr:`_unstack_fn`
        for why this is a delete, not a donation). Every leaf is this
        client's OWN fresh buffer — the packed step donates its cstate,
        so a buffer shared across clients (state.step) would be dead by
        client 1's first dispatch. The fit loop is its one caller in
        the program."""
        pcs, ocs = self._unstack_fn(state.params, state.opt_state)
        for leaf in jax.tree.leaves((state.params, state.opt_state)):
            if isinstance(leaf, jax.Array):
                leaf.delete()
        return [
            (
                pcs[c],
                ocs[c],
                jnp.copy(state.step),
                jnp.copy(state.rngs[c]),
            )
            for c in range(self.C)
        ]

    def _packed_eligible(self) -> bool:
        """The client-packing fast path applies when every logical client
        lives on ONE device (single-process, single-device mesh — logical
        replicas packed per row): there the identical math is dispatched
        as independent per-client steps, without the stacked vmapped
        step's batched-weight GEMMs (`mfu` 49.3%, `distilbert-fed-round-c8`,
        ledger PR 29). Multi-device meshes shard the clients axis and
        run the per-shard lockstep step (fedsteps ``_step_body``), which
        still vmaps the clients of one mesh row."""
        return (
            self.P == 1
            and self.mesh.devices.size == 1
            and self._build_packed_step is not None
        )

    def _fit_local_packed(
        self,
        state: FedState,
        stacked_train: TokenizedSplit,
        *,
        bs: int,
        E: int,
        epoch_offset: int,
        n_batches: int,
    ) -> tuple[FedState, np.ndarray]:
        """Dense lockstep epochs on the client-packing fast path: unstack
        the FedState once, advance each client through its OWN jitted
        engine-style step (unbatched GEMMs, donated buffers), restack
        once at the end. Per-client rng folds and the lockstep counter
        match the vmapped step exactly
        (test_federated.py::test_packed_fit_matches_vmapped)."""
        if self._packed_step is None:
            self._packed_step = self._build_packed_step()
        step_fn = self._packed_step
        C = self.C
        mu = self.cfg.fed.prox_mu
        slice_c = self._slice_client
        # FedProx anchors: fresh round-start slices, taken BEFORE the
        # unstack below donates (consumes) the stacked params.
        with annotate("fit/unstack"):
            anchors = (
                [slice_c(state.params, c) for c in range(C)]
                if mu > 0.0
                else None
            )
            cstates = self._unstack_cstates(state)
        out = []
        telemetry = self._step_telemetry()
        prof = self.step_profiler  # armed + window-reset by fit_local
        first_memory = prof is not None
        last_loss = None  # carried ACROSS epochs: the drain fence target
        for epoch in range(epoch_offset, epoch_offset + E):
            losses = []
            batches = self._epoch_batches(stacked_train, bs, epoch)
            sliced = (
                (b, [{k: v[c] for k, v in b.items()} for c in range(C)])
                for _, b in zip(range(n_batches), batches)
            )
            for (batch, per_client), sampled in profiled_step_iter(
                prof, annotate_iter("fit/next_batch", sliced)
            ):
                # A "step" here is one full lockstep batch: C per-client
                # dispatches. A sampled one fences the previous batch's
                # losses first, then splits dispatch from device.
                if sampled:
                    prof.drain(last_loss)
                    t_d = prof.clock()
                per = []
                for c, cb in enumerate(per_client):
                    if anchors is not None:
                        cstates[c], task = step_fn(
                            cstates[c], cb, anchors[c]
                        )
                    else:
                        cstates[c], task = step_fn(cstates[c], cb)
                    per.append(task)
                loss_vec = jnp.stack(per)
                if sampled:
                    prof.note_dispatch(prof.clock() - t_d)
                    prof.fence(loss_vec)
                losses.append(loss_vec)
                last_loss = loss_vec
                telemetry(loss_vec, batch["labels"].size)
                if first_memory:
                    first_memory = False
                    note_memory("post-first-step")
            epoch_avg = (
                jnp.stack(losses).mean(axis=0) if losses else jnp.zeros(C)
            )
            with annotate("fit/loss_read"):
                out.append(self._host(epoch_avg))
            for c in range(C):
                log.info(
                    f"Client {c} Epoch [{epoch - epoch_offset + 1}/{E}], "
                    f"Average Loss: {out[-1][c]:.4f}"
                )
        restack = self._restack_fn
        with annotate("fit/restack"):
            state = state._replace(
                params=restack(*[cs[0] for cs in cstates]),
                opt_state=restack(*[cs[1] for cs in cstates]),
                step=cstates[0][2],
            )
        return state, np.stack(out) if out else np.zeros((0, C))

    def _fit_local_ragged(
        self,
        state: FedState,
        stacked_train: StackedClients,
        *,
        batch_size: int | None = None,
        epochs: int | None = None,
        epoch_offset: int = 0,
    ) -> tuple[FedState, np.ndarray]:
        """Ragged lockstep epochs: the per-epoch step count is the fleet
        MAX batch count (ceil — the final short batch trains too), clients
        that exhaust their rows idle behind valid==0 masks, and reported
        per-client epoch losses average over each client's own real
        batches — the numbers an independent per-client run would log."""
        bs = self.cfg.data.batch_size if batch_size is None else batch_size
        E = self.cfg.train.epochs_per_round if epochs is None else epochs
        n_batches = max(
            (-(-int(n) // bs) for n in stacked_train.n_rows), default=0
        )
        if self.P > 1:
            # Every host runs the GLOBAL max step count (each step is a
            # collective); short hosts contribute all-masked batches.
            n_batches = int(self._allgather(n_batches).max())
        if n_batches == 0:
            raise ValueError(
                "every client's train split is empty: nothing to fit"
            )
        if self._ragged_train_step is None:
            self._ragged_train_step = self._build_ragged_step()
        if self.cfg.fed.prox_mu > 0.0:
            anchor = jax.tree.map(jnp.copy, state.params)
            step = lambda s, b: self._ragged_train_step(s, b, anchor)  # noqa: E731
        else:
            step = self._ragged_train_step
        out = []
        telemetry = self._step_telemetry()
        for epoch in range(epoch_offset, epoch_offset + E):
            losses, had = [], []
            batches = federated_batches_ragged(
                stacked_train,
                bs,
                seed=self.cfg.train.seed,
                epoch=epoch,
                client_offset=self.client_offset,
                n_batches=n_batches,
            )
            fed = ((b, self._feed(b)) for b in batches)
            for batch, feed in annotate_iter("fit/next_batch", fed):
                state, (loss, has) = step(state, feed)
                losses.append(loss)
                had.append(has)
                # Mean over ACTIVE clients only — idle clients' masked loss
                # of 0 must not understate the fleet mean.
                telemetry(loss, int(batch["valid"].sum()), active=has)
            # Per-client mean over ITS OWN batches: masked-off lockstep
            # steps carry loss 0 and has 0, so they vanish from both sums.
            total = jnp.stack(losses).sum(axis=0)
            count = jnp.stack(had).sum(axis=0)
            epoch_avg = total / jnp.maximum(count, 1.0)
            with annotate("fit/loss_read"):
                out.append(self._host(epoch_avg))
            for c in range(self.C):
                log.info(
                    f"Client {c} Epoch [{epoch - epoch_offset + 1}/{E}], "
                    f"Average Loss: {out[-1][c]:.4f}"
                )
        return state, np.stack(out) if out else np.zeros((0, self.C))

    def prepare_eval(
        self,
        splits: Sequence[TokenizedSplit],
        *,
        batch_size: int | None = None,
        target_rows: int | None = None,
    ) -> "PreparedEval":
        """Pad/stack eval splits once; reuse across rounds (re-stacking every
        evaluation would repeat the host-side concat of the full eval set).
        Multi-host callers pass only their LOCAL clients' splits plus the
        global max split length as ``target_rows``."""
        bs = self.cfg.data.eval_batch_size if batch_size is None else batch_size
        if target_rows is None and self.P > 1:
            # Hosts must agree on M (the eval loop is a sequence of
            # collectives); default to the global max split length.
            target_rows = int(
                self._allgather(max(len(s) for s in splits)).max()
            )
        stacked, valid = stack_eval_splits(
            splits, bs, pad_id=self.pad_id, target_rows=target_rows
        )
        return PreparedEval(stacked, valid, bs)

    def _step_telemetry(self):
        """Shared per-step logging closure (engine.make_step_telemetry)
        with the fleet-mean loss label. ``telemetry_prefix`` overrides the
        default tag — the C=1 TCP client adapter sets its ``[CLIENT n]``
        prefix there so mixed-fleet step logs stay attributable."""
        from ..train.engine import make_step_telemetry

        return make_step_telemetry(
            self.cfg.train.log_every,
            prefix=getattr(self, "telemetry_prefix", "[FED] "),
            label="mean loss",
        )

    @staticmethod
    def _allgather(value: int) -> np.ndarray:
        from ..parallel.multihost import allgather_hosts

        return allgather_hosts(value)

    # ------------------------------------------------------- observability
    def _trace_attrs(self) -> dict:
        """Span attributes identifying this trainer's product path (the
        3-axis fedseq subclass overrides with its seq layout)."""
        return {"path": "fed2", "clients": self.C}

    def _obs_tracer(self):
        from ..obs.trace import get_global_tracer

        return self.tracer if self.tracer is not None else get_global_tracer()

    def _trace_phase(
        self,
        name: str,
        t_start: float,
        dur_s: float,
        round_index: int,
        **extra: Any,
    ) -> None:
        tracer = self._obs_tracer()
        if tracer is not None:
            tracer.record(
                name,
                t_start=t_start,
                dur_s=dur_s,
                round=round_index,
                **self._trace_attrs(),
                **extra,
            )

    def evaluate_clients(
        self,
        stacked_params: Any,
        splits: Sequence[TokenizedSplit] | None = None,
        *,
        prepared: "PreparedEval | None" = None,
        batch_size: int | None = None,
        collect_probs: bool = False,
    ) -> list[dict]:
        """Per-client metrics dicts (reference five-metric schema)."""
        if prepared is None:
            if splits is None:
                raise ValueError("pass either splits or prepared")
            prepared = self.prepare_eval(splits, batch_size=batch_size)
        elif splits is not None or batch_size is not None:
            raise ValueError(
                "prepared already fixes the eval data and batch size; "
                "do not also pass splits/batch_size"
            )
        return evaluate_stacked(
            self, stacked_params, prepared, collect_probs=collect_probs
        )

    def participation_mask(self, round_index: int) -> np.ndarray | None:
        """Per-round participant sampling (FedConfig.participation < 1):
        a seeded 0/1 mask over clients, identical on every host. None when
        everyone participates (the reference's behavior).

        Two samplers (FedConfig.participation_mode): "fixed" draws exactly
        ``cohort_size()`` clients without replacement; "poisson" draws
        each client independently with probability ``participation`` —
        the sampler the DP accountant's subsampled-Gaussian bound assumes,
        making the reported epsilon exact (the default whenever DP is on).
        A Poisson cohort may be empty; ``run`` treats such a round as a
        no-op instead of failing (skipping on this data-INDEPENDENT event
        does not weaken the accountant's bound — both branches are
        identically distributed under adjacent datasets)."""
        if self.cfg.fed.participation >= 1.0:
            return None
        rng = np.random.default_rng(self.cfg.train.seed * 7919 + round_index)
        if self.cfg.fed.resolve_participation_mode() == "poisson":
            return (
                rng.random(self.C) < self.cfg.fed.participation
            ).astype(np.float64)
        # FedConfig.cohort_size is the single source of truth for k — the
        # DP accountant derives its effective sampling rate from the same
        # number (ceil keeps the sampled round above min_client_fraction).
        k = self.cfg.fed.cohort_size()
        mask = np.zeros(self.C, np.float64)
        mask[rng.choice(self.C, size=k, replace=False)] = 1.0
        return mask

    def round_aggregate(
        self,
        state: FedState,
        *,
        round_index: int,
        weights: np.ndarray | None = None,
        base_mask: np.ndarray | None = None,
        faults: np.ndarray | None = None,
        anchor: Any | None = None,
    ) -> FedState:
        """One round's participation sampling + gating + aggregation,
        shared by :meth:`run` and the CLI round loop.

        min_client_fraction gates CRASHED/empty clients (``base_mask``
        and ``faults``) — never the Poisson draw: a small (even empty)
        Poisson cohort is a legitimate sample the DP accountant's bound
        already covers, and gating on it would condition the sampler and
        un-exact the reported epsilon. An empty Poisson round is a no-op
        (skipping on this data-INDEPENDENT event costs no privacy — both
        branches are identically distributed under adjacent datasets)."""
        from .fedsteps import check_survivors

        mask = self.participation_mask(round_index)
        poisson = (
            mask is not None
            and self.cfg.fed.resolve_participation_mode() == "poisson"
        )
        gate = base_mask
        if base_mask is not None:
            mask = base_mask if mask is None else mask * base_mask
        # The no-op branch keys on the draw gated by the STRUCTURAL
        # base_mask (the product just computed): clients with empty
        # shards (ragged fleets) never participate, which is a fixed,
        # data-independent property — a draw landing only on them is the
        # same benign sampling event as an empty draw. A non-empty
        # effective draw whose every member then CRASHED (faults, below)
        # is a fault event and must abort loudly (same as the fixed
        # sampler), not read as a benign sampler outcome.
        draw_empty = poisson and float(mask.sum()) == 0.0
        if faults is not None:
            faults = np.asarray(faults, np.float64)
            mask = faults if mask is None else mask * faults
            gate = faults if gate is None else gate * faults
        if poisson and gate is not None:
            check_survivors(
                float(gate.sum()), self.C, self.cfg.fed.min_client_fraction
            )
        if draw_empty:
            log.info(
                f"[FED] round {round_index + 1}: empty effective Poisson "
                "cohort (no sampled client holds data) — aggregation "
                "skipped (no-op round; the DP accountant already covers "
                "this branch)"
            )
            return state
        t_unix = time.time()
        t0 = time.monotonic()
        with annotate("agg"):
            state = self.aggregate(
                state,
                weights=weights,
                client_mask=mask,
                anchor=anchor,
                round_index=round_index,
                enforce_min_fraction=not poisson,
            )
        self._trace_phase("agg", t_unix, time.monotonic() - t0, round_index)
        # Memory watermark at the round's aggregation boundary
        # (obs/profile.py — graceful no-op on stats-less backends).
        note_memory("post-aggregate")
        return state

    def round_anchor(self, state: FedState) -> Any | None:
        """Round-start params snapshot for DP and/or FedOpt aggregation —
        capture BEFORE ``fit_local`` (a copy, so donated train-step buffers
        never alias it). None when neither needs it."""
        if self.dp_fedavg_step is None and self.server_agg_step is None:
            return None
        with annotate("round_anchor"):
            return jax.tree.map(jnp.copy, state.params)

    def _dp_key(self, round_index: int) -> jax.Array:
        """Per-round noise key from the run's private DP seed (fresh OS
        entropy unless FedConfig.dp_seed pins it for tests)."""
        base = jax.random.key(self._dp_seed, impl=self.cfg.train.prng_impl)
        return jax.random.fold_in(base, round_index)

    def aggregate(
        self,
        state: FedState,
        *,
        weights: np.ndarray | None = None,
        client_mask: np.ndarray | None = None,
        anchor: Any | None = None,
        round_index: int = 0,
        enforce_min_fraction: bool = True,
    ) -> FedState:
        """The FedAvg round boundary — dispatch in fedsteps.aggregate_round
        (plain/weighted/masked FedAvg, DP-FedAvg, FedOpt).
        ``enforce_min_fraction=False``: the Poisson-sampled path, where the
        run loop gates faults itself and a small cohort is legitimate."""
        return aggregate_round(
            self,
            state,
            weights=weights,
            client_mask=client_mask,
            anchor=anchor,
            round_index=round_index,
            enforce_min_fraction=enforce_min_fraction,
        )

    # ------------------------------------------------------------------- run
    def run(
        self,
        state: FedState,
        stacked_train: TokenizedSplit | StackedClients,
        eval_splits: Sequence[TokenizedSplit],
        *,
        rounds: int | None = None,
        weights: np.ndarray | None = None,
        fault_mask_fn: Callable[[int], np.ndarray | None] | None = None,
    ) -> tuple[FedState, list[RoundRecord]]:
        """The full federated flow, per round: local epochs -> local eval ->
        FedAvg -> aggregated eval (the reference's one-shot flow,
        client1.py:379-404, looped).

        ``fault_mask_fn(round) -> [C] 0/1 mask | None`` injects deterministic
        client failures for a round (a dropped client is excluded from the
        masked mean, exactly as a crashed client would be — the reference
        instead hangs its accept loop, server.py:69-71,124-132). Composes
        with partial participation: a client aggregates only if both masks
        keep it. ``min_client_fraction`` still gates the round.
        """
        R = self.cfg.fed.rounds if rounds is None else rounds
        E = self.cfg.train.epochs_per_round
        if weights is None and self.cfg.fed.resolve_weighted():
            if isinstance(stacked_train, StackedClients):
                if self.P > 1:
                    # The local ragged stack covers only this process's
                    # clients; silently falling back to a uniform mean here
                    # would make the same config aggregate differently on
                    # 1 vs N hosts. The caller must supply the GLOBAL
                    # n_train weights (cmd_federated does).
                    raise ValueError(
                        "multi-host run() cannot derive global sample-count "
                        "weights from the process-local ragged stack — pass "
                        "weights=[global n_train per client], or set "
                        "fed.weighted=False for the uniform mean"
                    )
                # The ragged stack carries true per-client sample counts —
                # the auto (weighted=None) default weights by them.
                weights = np.asarray(stacked_train.n_rows, np.float64)
            elif self.cfg.fed.weighted:
                # Explicit weighted=True without recoverable counts: the
                # fleet-min-truncated dense stack loses them — the caller
                # must supply the true n_train weights.
                raise ValueError(
                    "fed.weighted=True requires explicit per-client weights "
                    "(pass weights=[n_train per client])"
                )
        # Under a uniform mean (explicit weighted=False, or DP's forced
        # uniform), a zero-row client would average its never-trained
        # round-start params into the aggregate with full 1/C weight every
        # round; mask it out as a permanently dropped client instead (it
        # still receives the aggregate — the masked mean's output is
        # broadcast to every row). min_client_fraction applies as usual.
        base_mask: np.ndarray | None = None
        if weights is None and isinstance(stacked_train, StackedClients):
            local_empty = (np.asarray(stacked_train.n_rows) == 0).astype(np.int64)
            if self.P == 1:
                empty = local_empty > 0
            else:
                # Every host must apply the SAME mask (the aggregate is one
                # collective); clients lay process-major over the mesh, so
                # the allgather's flattened order IS the global client order.
                from jax.experimental import multihost_utils

                empty = (
                    np.asarray(
                        multihost_utils.process_allgather(local_empty)
                    ).reshape(-1)
                    > 0
                )
            if empty.any():
                base_mask = (~empty).astype(np.float64)
                log.warning(
                    f"[FED] clients {np.flatnonzero(empty).tolist()} have "
                    "zero train rows; excluding them from the uniform mean"
                )
        history: list[RoundRecord] = []
        prepared = self.prepare_eval(eval_splits)
        for r in range(R):
            anchor = self.round_anchor(state)
            with phase(f"round {r + 1}/{R} local training", tag="FED"):
                state, losses = self.fit_local(
                    state, stacked_train, epoch_offset=r * E
                )
            local = self.evaluate_clients(state.params, prepared=prepared)
            faults = None
            if fault_mask_fn is not None:
                faults = fault_mask_fn(r)
                if faults is not None:
                    faults = np.asarray(faults, np.float64)
                    dropped = [c for c in range(self.C) if faults[c] == 0]
                    if dropped:
                        log.info(
                            f"[FED] round {r + 1}: injected faults drop "
                            f"clients {dropped}"
                        )
            with phase(f"round {r + 1}/{R} FedAvg", tag="FED"):
                state = self.round_aggregate(
                    state,
                    round_index=r,
                    weights=weights,
                    base_mask=base_mask,
                    faults=faults,
                    anchor=anchor,
                )
            aggregated = self.evaluate_clients(state.params, prepared=prepared)
            history.append(RoundRecord(r, losses, local, aggregated))
            for c in range(self.C):
                log.info(
                    f"Round {r + 1} client {c}: local acc "
                    f"{local[c]['Accuracy']:.4f} -> aggregated "
                    f"{aggregated[c]['Accuracy']:.4f}"
                )
            if r + 1 < R and self.cfg.fed.reset_optimizer_each_round:
                state = self.reset_optimizer(state)
        return state, history
