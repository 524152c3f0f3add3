"""Jitted federated step construction: the SPMD programs the trainer runs.

One stacked ``[C, ...]`` parameter tree sharded over the ``clients`` mesh
axis; one train step advances every client in lockstep on its private
shard, each device stepping the clients of its mesh row on its own rows of
their batches (the reference instead runs N separate OS processes,
client1.py:96-115 per process). ``build_federated_steps`` is a pure
function of (config, model, optimizer, shardings); ``aggregate_round`` is
the round-boundary dispatch over those steps — it takes the trainer as a
facade (cfg/steps/_host/_dp_key) and is called only through
``FederatedTrainer.aggregate``. Lifecycle and multi-host sync stay in
train/federated.py.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

import numpy as np

from ..obs.profile import default_ledger
from ..parallel.fedavg import make_fedavg_step
from ..train.engine import (
    apply_warmup,
    eval_counts,
    loss_fn,
    masked_loss_fn,
    prox_sq,
)
from ..utils.logging import get_logger

log = get_logger()


def _signature(body: Callable, mu: float) -> Callable:
    """The step's call signature from its ``(state, batch, anchor)`` body.
    FedProx (``mu > 0``): the body itself — the anchor is the round-start
    params, a separate buffer, NOT the donated state. Plain FedAvg:
    ``(state, batch)``, no anchor transfer."""
    if mu > 0.0:
        return body
    return lambda state, batch: body(state, batch, None)


def shard_step_keys(rngs, step, split_rows: bool):
    """This step's dropout keys for the clients a chip holds, inside the
    mesh step's ``shard_map``: the lockstep counter folded into each
    client's key, as the packed and the ragged steps fold it. Where a
    client's batch is split over ``data`` (``split_rows``) the shard's
    index is folded in as well, so the shards draw independent masks for
    their own rows; with one shard nothing more is folded in and the keys
    are those of the packed step."""

    def key(k):
        k = jax.random.fold_in(k, step)
        if split_rows:
            k = jax.random.fold_in(k, jax.lax.axis_index("data"))
        return k

    return jax.vmap(key)(rngs)


def make_packed_step(
    objective,
    optimizer,
    wsteps: int,
    mu: float,
    *,
    gather: Callable | None = None,
    constrain: Callable | None = None,
) -> Callable:
    """The SINGLE per-client packed step builder (shared by the dense and
    3-axis fedseq paths — their update math must never diverge).

    ``objective(params, batch, step_rng, anchor) -> (objective, task)``
    supplies the loss; everything else — the per-step rng fold off the
    lockstep counter, Adam, warmup, donation — is identical to one lane
    of the stacked vmapped step. Signature of the returned program:
    ``(cstate, batch[, anchor]) -> (cstate, task_loss)`` with
    ``cstate = (params, opt_state, step, rng)`` (one client's buffers,
    donated).

    ``gather``/``constrain`` spec-parameterize the step for FSDP
    shard-at-rest state (train/engine.py's contract: gather runs inside
    a remat region so the backward re-gathers; constrain reduce-scatters
    grads and pins the updated params/opt leaves back onto their
    shards). None/None (the default) is the literal replicated step."""

    ledger = default_ledger()
    note_compile = ledger.hook("fed.packed_step")
    if gather is not None:
        from .engine import _tag_gather, fsdp_remat_loss

        # The remat wraps the WHOLE objective with the tagged gather
        # inside (engine.fsdp_remat_loss): wrapping only the gather
        # would save its full-size outputs as residuals anyway.
        base_objective, tagged = objective, _tag_gather(gather)
        objective = fsdp_remat_loss(
            lambda p, b, r, a: base_objective(tagged(p), b, r, a)
        )

    def body(cstate, batch, anchor):
        note_compile(tuple(batch["input_ids"].shape))
        params, opt_state, step, rng = cstate
        step_rng = jax.random.fold_in(rng, step)
        (_, task), grads = jax.value_and_grad(
            lambda p: objective(p, batch, step_rng, anchor),
            has_aux=True,
        )(params)
        if constrain is not None:
            grads = constrain(grads)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, opt_state, params)
            updates = apply_warmup(updates, step, wsteps)
            new_params = optax.apply_updates(params, updates)
        if constrain is not None:
            new_params = constrain(new_params)
            new_opt = constrain(new_opt)
        return ((new_params, new_opt, step + 1, rng), task)

    return ledger.jit(
        "fed.packed_step", _signature(body, mu), donate_argnums=(0,)
    )


class FedState(NamedTuple):
    """Stacked per-client training state; every leaf's axis 0 is clients."""

    params: Any  # [C, ...]
    opt_state: Any  # [C, ...]
    step: jnp.ndarray  # scalar int32 — lockstep across clients
    rngs: jax.Array  # [C] dropout keys
    # FedOpt server-optimizer state (single-model shaped, replicated);
    # None under plain FedAvg. Persists across rounds — the per-round
    # client optimizer reset does not touch it.
    server_opt: Any = None


class FedSteps(NamedTuple):
    """The jitted programs + lazy builders behind a FederatedTrainer."""

    train_step: Callable  # (state, batch[, anchor]) -> (state, [C] losses)
    build_ragged_step: Callable  # () -> ragged train step (compiled on demand)
    eval_step: Callable  # (params, batch, valid) -> (BinaryCounts, probs)
    fedavg_step: Callable
    server_tx: Any  # optax server optimizer | None
    server_agg_step: Callable | None
    dp_fedavg_step: Callable | None
    opt_init: Callable  # stacked params -> stacked opt state
    replicate: Callable  # clients-sharded tree -> replicated tree
    # () -> per-client PACKED step (compiled on demand): the client-packing
    # fast path for a single-device mesh — see build_packed_step below.
    build_packed_step: Callable = None


def build_federated_steps(
    cfg,
    model,
    optimizer,
    sh,
    *,
    gather: Callable | None = None,
    constrain: Callable | None = None,
) -> FedSteps:
    """Compile-ready step closures for one experiment configuration.

    ``sh``: parallel.mesh.FedShardings — fixes how every input/output lays
    over the ``clients x data`` mesh. The train step is written per shard
    (one ``shard_map`` over that mesh; autodiff sums the gradients over
    ``data``, see ``per_client_step``); for the other programs — the
    ragged step, evaluation, FedAvg — jit inserts the collectives (the
    reference's entire TCP protocol, client1.py:246-336) at trace time.

    ``gather``/``constrain`` spec-parameterize the STACKED steps for FSDP
    shard-at-rest state — the same callable contract ``make_packed_step``
    takes, lifted to the ``[C, ...]`` trees: ``gather(stacked_params)``
    replicates every leaf over the fsdp axis (the all-gather AT USE,
    tagged + rematted so the backward re-gathers instead of retaining
    full-size weights), ``constrain(stacked_tree)`` pins grads and the
    updated params/opt leaves back onto their shards. Both callables see
    STACKED trees (they run outside the client vmap — per-lane sharding
    constraints cannot express the stacked layout), so callers build them
    from the stacked specs. None/None is the literal replicated program
    — byte-identical construction to the pre-parameterized builder."""
    csh, bsh = sh.client, sh.batch
    if (gather is None) != (constrain is None):
        raise ValueError(
            "gather and constrain parameterize the same FSDP layout — "
            "pass both or neither"
        )
    if gather is not None:
        from .engine import _tag_gather, fsdp_remat_loss

        tagged = _tag_gather(gather)
    mu = float(cfg.fed.prox_mu)
    wsteps = cfg.train.warmup_steps

    def local_loss(p, batch, rng, anchor):
        """Returns (training objective, task loss): gradients flow from
        the first, logs/round records report the second so FedProx and
        FedAvg loss curves stay comparable."""
        task = loss_fn(model, p, batch, rng)
        total = task
        if mu > 0.0:
            # FedProx proximal term vs the round-start globals —
            # trace-time constant, zero cost at mu=0 (plain FedAvg).
            total = task + 0.5 * mu * prox_sq(p, anchor)
        return total, task

    def per_client_step(params, opt_state, batch, rng, anchor, step):
        """One client's step on one chip's rows of its batch (inside the
        ``shard_map`` of ``_step_body``): the chips of a mesh row hold the
        same params and equal shares of the batch, so the mean of their
        gradients and losses over ``data`` is the whole batch's."""
        shards = sh.mesh.shape["data"]

        def objective(p):
            total, task = local_loss(p, batch, rng, anchor)
            return total / shards, task

        # Params enter replicated over ``data`` and the objective varies
        # with the shard's rows, so autodiff sums the shards' gradients
        # itself, where a weight meets the rows: in the encoder's compute
        # dtype (bf16), as the partitioner reduced them in the stacked
        # program, and half the bytes of a mean of the fp32 gradients.
        # The 1/shards above makes that sum the mean.
        (_, task), grads = jax.value_and_grad(objective, has_aux=True)(params)
        task = jax.lax.pmean(task, "data")
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            updates = apply_warmup(updates, step, wsteps)
            return optax.apply_updates(params, updates), opt_state, task

    state_sh = FedState(csh, csh, sh.replicated, csh, sh.replicated)
    batch_sh = {"input_ids": bsh, "attention_mask": bsh, "labels": bsh}
    ledger = default_ledger()
    note_train = ledger.hook("fed.train_step")

    def _step_body(state: FedState, batch, anchor):
        """The lockstep step, per shard: each chip steps the clients of
        its mesh row on its own rows of their batches, and draws dropout
        bits for those rows only (a generator XLA cannot partition, as
        ``rbg``, would otherwise draw the whole fleet's on every chip)."""
        note_train(tuple(batch["input_ids"].shape))
        split_rows = sh.mesh.shape["data"] > 1

        def shard_step(params, opt_state, rngs, step, batch, anchor):
            return jax.vmap(
                per_client_step,
                in_axes=(0, 0, 0, 0, 0 if mu > 0.0 else None, None),
            )(
                params,
                opt_state,
                batch,
                shard_step_keys(rngs, step, split_rows),
                anchor,
                step,
            )

        cspec, bspec = csh.spec, bsh.spec
        params, opt_state, losses = jax.shard_map(
            shard_step,
            mesh=sh.mesh,
            in_specs=(cspec, cspec, cspec, sh.replicated.spec, bspec, cspec),
            out_specs=cspec,
        )(
            state.params,
            state.opt_state,
            state.rngs,
            state.step,
            batch,
            anchor,
        )
        return (
            state._replace(
                params=params, opt_state=opt_state, step=state.step + 1
            ),
            losses,  # [C]
        )

    def _fsdp_step_body(state: FedState, batch, anchor):
        """The gather/constrain-parameterized stacked step: grads come
        from ONE rematted stacked objective (per-client losses depend
        only on their own lane, so grad of the sum IS the stacked
        per-client grads), gathered at use and reduce-scattered back,
        with the optimizer update vmapped over the constrained grads —
        the same math as ``_step_body``, laid out for shard-at-rest."""
        note_train(tuple(batch["input_ids"].shape))
        step_rngs = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            state.rngs, state.step
        )

        def stacked_objective(sp, b, r, a):
            totals, tasks = jax.vmap(
                local_loss, in_axes=(0, 0, 0, 0 if mu > 0.0 else None)
            )(tagged(sp), b, r, a)
            return totals.sum(), tasks

        (_, losses), grads = jax.value_and_grad(
            fsdp_remat_loss(stacked_objective), has_aux=True
        )(state.params, batch, step_rngs, anchor)
        grads = constrain(grads)
        updates, opt_state = jax.vmap(optimizer.update)(
            grads, state.opt_state, state.params
        )
        updates = apply_warmup(updates, state.step, wsteps)
        params = optax.apply_updates(state.params, updates)
        params = constrain(params)
        opt_state = constrain(opt_state)
        return (
            state._replace(
                params=params, opt_state=opt_state, step=state.step + 1
            ),
            losses,  # [C]
        )

    # The FedProx anchor is one more clients-sharded argument.
    anchor_sh = (csh,) if mu > 0.0 else ()

    if gather is not None:
        # No explicit in/out shardings: the constrain calls pin the FSDP
        # layout inside the program and inputs carry the caller's
        # placements — an out_shardings of ``csh`` here would force a
        # full re-gather at every step boundary.
        train_step = ledger.jit(
            "fed.train_step",
            _signature(_fsdp_step_body, mu),
            donate_argnums=(0,),
        )
    else:
        train_step = ledger.jit(
            "fed.train_step",
            _signature(_step_body, mu),
            donate_argnums=(0,),
            in_shardings=(state_sh, batch_sh, *anchor_sh),
            out_shardings=(state_sh, csh),
        )

    def per_client_step_masked(params, opt_state, batch, rng, anchor):
        """Row-masked variant for the ragged stacked path: the loss
        averages over the batch's valid rows only, and a client whose
        lockstep batch is ALL padding keeps its params/optimizer state
        untouched (zero grads through Adam would still move the moments
        — a phantom update an independent run never takes)."""

        def obj(p):
            task = masked_loss_fn(model, p, batch, rng)
            total = task
            if mu > 0.0:
                total = task + 0.5 * mu * prox_sq(p, anchor)
            return total, task

        (_, task), grads = jax.value_and_grad(obj, has_aux=True)(params)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        # Warmup rides the client's OWN executed-step count (see
        # train/batches.py federated_batches_ragged), not the shared
        # lockstep counter — an idling client's ramp must not advance.
        updates = apply_warmup(updates, batch["warmup_step"][0], wsteps)
        new_params = optax.apply_updates(params, updates)
        has = batch["valid"].sum() > 0
        params = jax.tree.map(
            lambda n, o: jnp.where(has, n, o), new_params, params
        )
        opt_state = jax.tree.map(
            lambda n, o: jnp.where(has, n, o), new_opt, opt_state
        )
        return params, opt_state, task, has.astype(jnp.float32)

    ragged_batch_sh = dict(batch_sh, valid=bsh, warmup_step=bsh)
    note_ragged = ledger.hook("fed.ragged_step")

    def _ragged_body(state: FedState, batch, anchor):
        note_ragged(tuple(batch["input_ids"].shape))
        step_rngs = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            state.rngs, state.step
        )
        params, opt_state, losses, has = jax.vmap(
            per_client_step_masked,
            in_axes=(0, 0, 0, 0, 0 if mu > 0.0 else None),
        )(state.params, state.opt_state, batch, step_rngs, anchor)
        return (
            state._replace(
                params=params, opt_state=opt_state, step=state.step + 1
            ),
            (losses, has),  # [C] masked losses, [C] 0/1 batch-had-rows
        )

    @lru_cache(maxsize=1)
    def build_packed_step():
        """Per-client PACKED train step — the client-packing fast path.

        On a single-device mesh the stacked vmapped program pays for its
        layout: every GEMM carries a client batch dim and each step
        re-slices/re-stacks nothing but still runs batched-weight
        kernels, where the SAME math dispatched as independent
        per-client engine steps does not. On the chip this program runs
        at `mfu` 49.3% (cell `distilbert-fed-round-c8`, ledger PR 29);
        the stacked step on one chip is in no cell (PERF.md). The fit
        loop unstacks once per fit, steps each client's state
        through this program, and restacks at the end. Semantically
        identical to the vmapped step (same per-client rng fold, same
        lockstep counter, same Adam); bit-level trajectory parity holds
        under threefry dropout keys (pinned by
        test_federated.py::test_packed_fit_matches_vmapped) — the default
        rbg impl generates layout-dependent bitstreams, so there the two
        paths draw different, equally distributed dropout masks.

        NOTE: the packed step runs SINGLE-client state — the stacked
        gather/constrain callables do not apply to its lane-shaped trees,
        so the FSDP-parameterized builder keeps the packed path
        replicated (single-device packing and shard-at-rest are disjoint
        deployments; a packed FSDP step is built directly via
        ``make_packed_step(gather=, constrain=)`` with lane-level
        callables)."""
        return make_packed_step(local_loss, optimizer, wsteps, mu)

    def _fsdp_ragged_body(state: FedState, batch, anchor):
        """Row-masked stacked step under gather/constrain: same sum-trick
        stacked objective as ``_fsdp_step_body`` over the masked loss,
        with the all-padding-client freeze (where-merge) riding inside
        the vmapped update and the outputs pinned back onto shards."""
        note_ragged(tuple(batch["input_ids"].shape))
        step_rngs = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            state.rngs, state.step
        )

        def lane_loss(p, b, r, a):
            task = masked_loss_fn(model, p, b, r)
            total = task
            if mu > 0.0:
                total = task + 0.5 * mu * prox_sq(p, a)
            return total, task

        def stacked_objective(sp, b, r, a):
            totals, tasks = jax.vmap(
                lane_loss, in_axes=(0, 0, 0, 0 if mu > 0.0 else None)
            )(tagged(sp), b, r, a)
            return totals.sum(), tasks

        (_, losses), grads = jax.value_and_grad(
            fsdp_remat_loss(stacked_objective), has_aux=True
        )(state.params, batch, step_rngs, anchor)
        grads = constrain(grads)

        def upd(g, o, p, b):
            updates, new_opt = optimizer.update(g, o, p)
            updates = apply_warmup(updates, b["warmup_step"][0], wsteps)
            new_params = optax.apply_updates(p, updates)
            has = b["valid"].sum() > 0
            new_params = jax.tree.map(
                lambda n, old: jnp.where(has, n, old), new_params, p
            )
            new_opt = jax.tree.map(
                lambda n, old: jnp.where(has, n, old), new_opt, o
            )
            return new_params, new_opt, has.astype(jnp.float32)

        params, opt_state, has = jax.vmap(upd)(
            grads, state.opt_state, state.params, batch
        )
        params = constrain(params)
        opt_state = constrain(opt_state)
        return (
            state._replace(
                params=params, opt_state=opt_state, step=state.step + 1
            ),
            (losses, has),
        )

    @lru_cache(maxsize=1)
    def build_ragged_step():
        """Built on first ragged fit_local (equal-client runs never pay
        the extra compilation); memoized so same-config trainers share the
        compiled executable."""
        if gather is not None:
            return ledger.jit(
                "fed.ragged_step",
                _signature(_fsdp_ragged_body, mu),
                donate_argnums=(0,),
            )
        return ledger.jit(
            "fed.ragged_step",
            _signature(_ragged_body, mu),
            donate_argnums=(0,),
            in_shardings=(state_sh, ragged_batch_sh, *anchor_sh),
            out_shardings=(state_sh, (csh, csh)),
        )

    note_eval = ledger.hook("fed.eval_step")

    def eval_step(stacked_params, batch, valid):
        note_eval(tuple(batch["input_ids"].shape))
        return jax.vmap(lambda p, b, v: eval_counts(model, p, b, v))(
            stacked_params, batch, valid
        )

    eval_step = ledger.jit(
        "fed.eval_step", eval_step, in_shardings=(csh, batch_sh, bsh)
    )

    if cfg.fed.server_opt_enabled():
        from ..parallel.fedavg import make_server_optimizer, weighted_mean

        server_tx = make_server_optimizer(cfg.fed)

        @partial(
            jax.jit,
            in_shardings=(csh, csh, None, None, sh.replicated),
            out_shardings=(csh, sh.replicated),
        )
        def server_agg_step(stacked_params, anchor, w, m, server_state):
            """FedOpt round boundary: pseudo-gradient = anchor - mean
            of (possibly weighted/masked) client params; the server
            optimizer turns it into the global step, broadcast back to
            every client shard. All server math in fp32."""
            mean = weighted_mean(stacked_params, w, m)
            # Anchor rows are identical (previous round's replicated
            # output); the mean over axis 0 IS the single-model value.
            anchor1 = weighted_mean(anchor)
            g = jax.tree.map(lambda a, mn: a - mn, anchor1, mean)
            updates, new_state = server_tx.update(g, server_state, anchor1)
            new1 = optax.apply_updates(anchor1, updates)
            stacked = jax.tree.map(
                lambda n, ref: jnp.broadcast_to(n.astype(ref.dtype), ref.shape),
                new1,
                stacked_params,
            )
            return stacked, new_state

    else:
        server_tx = None
        server_agg_step = None

    if cfg.fed.dp_clip > 0.0:
        from ..parallel.dp import make_dp_fedavg_step

        dp_fedavg_step = make_dp_fedavg_step(
            sh,
            clip=float(cfg.fed.dp_clip),
            noise_multiplier=float(cfg.fed.dp_noise_multiplier),
        )
    else:
        dp_fedavg_step = None

    # vmapped optimizer init, compiled once (reset_optimizer runs it
    # every round — a fresh jit lambda per call would recompile).
    def opt_init(stacked_params):
        return jax.vmap(optimizer.init)(stacked_params)

    opt_init = jax.jit(opt_init, in_shardings=(csh,), out_shardings=csh)
    # Host-sync path for clients-sharded values: under multi-process,
    # shards on other hosts are not addressable — replicate first (an
    # all-gather over DCN), then np.asarray is local. Single process
    # short-circuits in the trainer's _host().
    def replicate(tree):
        return tree

    replicate = jax.jit(replicate, out_shardings=sh.replicated)

    return FedSteps(
        train_step=train_step,
        build_ragged_step=build_ragged_step,
        eval_step=eval_step,
        fedavg_step=make_fedavg_step(sh),
        server_tx=server_tx,
        server_agg_step=server_agg_step,
        dp_fedavg_step=dp_fedavg_step,
        opt_init=opt_init,
        replicate=replicate,
        build_packed_step=build_packed_step,
    )


@lru_cache(maxsize=None)
def _cached_federated_steps(cfg, mesh) -> FedSteps:
    from ..models import build_classifier
    from ..parallel.mesh import FedShardings
    from .engine import make_optimizer

    return build_federated_steps(
        cfg, build_classifier(cfg.model), make_optimizer(cfg.train), FedShardings(mesh)
    )


def cached_federated_steps(cfg, mesh) -> FedSteps:
    """Process-wide memo of ``build_federated_steps`` keyed on the inputs
    it is a pure function of: every FederatedTrainer built with an
    equivalent (config, mesh) pair — CLI resume paths, multi-round
    drivers, the test suite — shares one set of compiled executables
    instead of re-tracing identical programs.

    The key canonicalizes the config fields the compiled programs never
    read (data pipeline, distill, output paths, host-side round/epoch/
    telemetry counts), so runs differing only in e.g. --output-dir still
    share. Conservative direction: a newly added field defaults to being
    part of the key — worst case a lost share, never wrong sharing. The
    mesh *config* stays in the key only because ExperimentConfig
    validation couples it to fed.num_clients; the mesh object itself is
    what the shardings derive from."""
    from dataclasses import replace

    from ..config import DataConfig, DistillConfig

    key_cfg = replace(
        cfg,
        # max_len rides along: ExperimentConfig validates it against the
        # model's position table.
        data=DataConfig(max_len=cfg.model.max_len),
        distill=DistillConfig(),
        train=replace(cfg.train, seed=0, epochs_per_round=1, log_every=0),
        fed=replace(cfg.fed, rounds=1),
        output_dir="outputs",
        checkpoint_dir=None,
    )
    return _cached_federated_steps(key_cfg, mesh)


def check_survivors(surviving: float, C: int, min_frac: float) -> None:
    """Single enforcement of the survivor floor (zero survivors always
    abort — a zero-mask mean would silently zero or NaN the params)."""
    if surviving == 0.0 or surviving < min_frac * C:
        raise RuntimeError(
            f"only {int(surviving)}/{C} clients survived the round "
            f"(min_client_fraction={min_frac})"
        )


def aggregate_round(
    trainer,
    state: FedState,
    *,
    weights: np.ndarray | None = None,
    client_mask: np.ndarray | None = None,
    anchor: Any | None = None,
    round_index: int = 0,
    enforce_min_fraction: bool = True,
) -> FedState:
    """The FedAvg round boundary. Enforces min_client_fraction (the
    reference instead refuses unless exactly N models arrived,
    server.py:69-71) unless ``enforce_min_fraction=False`` (the Poisson
    participation path — the caller gates faults itself and a small
    sampled cohort must not abort). With ``fed.dp_clip > 0`` the boundary
    runs DP-FedAvg (parallel/dp.py): pass the ``round_anchor`` captured
    before local training plus the round index (noise key)."""
    cfg = trainer.cfg
    C = trainer.C
    if client_mask is not None:
        check_survivors(
            float(np.asarray(client_mask).sum()),
            C,
            cfg.fed.min_client_fraction if enforce_min_fraction else 0.0,
        )
    if weights is not None:
        eff = np.asarray(weights, dtype=np.float64)
        if client_mask is not None:
            eff = eff * np.asarray(client_mask, dtype=np.float64)
        if eff.sum() <= 0.0:
            # fedavg's jitted mean clamps the divisor; a zero weight sum
            # would silently zero every parameter.
            raise ValueError(
                "effective FedAvg weight sum is zero (all-zero weights, "
                "or every weighted client masked out)"
            )
    w = None if weights is None else jnp.asarray(weights)
    m = None if client_mask is None else jnp.asarray(client_mask)
    needs_anchor = (
        trainer.dp_fedavg_step is not None or trainer.server_agg_step is not None
    )
    if needs_anchor and anchor is None:
        raise ValueError(
            "DP and/or FedOpt aggregation needs the round-start anchor "
            "— capture it with round_anchor(state) before fit_local"
        )
    if trainer.dp_fedavg_step is not None:
        if w is not None:
            raise ValueError(
                "DP aggregation is a uniform mean (FedConfig forbids "
                "weighted=True with dp_clip); do not pass weights"
            )
        base, norms = trainer.dp_fedavg_step(
            state.params, anchor, trainer._dp_key(round_index), m
        )
        # DP output is already the (uniform, noised) aggregate
        # replicated across rows; any server step consumes it as-is.
        w_srv = m_srv = None
        # Log stats over PARTICIPANTS only — masked-out clients' norms
        # never touched the aggregate and would skew clip-rate tuning.
        hn = np.asarray(trainer._host(norms))
        if client_mask is not None:
            hn = hn[np.asarray(client_mask) > 0]
        clipped = int((hn > cfg.fed.dp_clip).sum())
        log.info(
            f"[DP] round {round_index}: participant update norms "
            f"median {np.median(hn):.4g} max {hn.max():.4g}; "
            f"{clipped}/{hn.size} participants clipped at "
            f"{cfg.fed.dp_clip}"
        )
    else:
        base, w_srv, m_srv = state.params, w, m
    already_aggregated = trainer.dp_fedavg_step is not None
    if trainer.server_agg_step is not None:
        params, server_state = trainer.server_agg_step(
            base, anchor, w_srv, m_srv, state.server_opt
        )
        return state._replace(params=params, server_opt=server_state)
    if already_aggregated:
        return state._replace(params=base)
    return state._replace(params=trainer.fedavg_step(base, w_srv, m_srv))
