"""Federated long-context training: one ``clients × data × seq`` mesh.

Composes the two parallelism stories that previously ran separately:

* the federated axis — stacked ``[C, ...]`` per-client params sharded over
  ``clients``, FedAvg as a collective (parallel/fedavg.py);
* sequence parallelism — the encoder forward runs inside ``shard_map``
  with the sequence dimension sharded over ``seq``, ring attention
  rotating K/V chunks by ``ppermute`` (parallel/ring_attention.py), plus
  per-client batch parallelism over ``data``.

Layout of one train step for batch ``[C, B, L]``:

* ``input_ids`` / ``attention_mask``: ``P('clients', 'data', 'seq')`` —
  every device holds one client's batch-shard of one sequence chunk;
* ``labels``: ``P('clients', 'data')``;
* params / optimizer state: ``P('clients')`` (replicated over data+seq).

The loss runs under ONE ``shard_map`` over all three axes: a local vmap
covers the device's client replicas, the model's ring path handles
shard-offset position embeddings and global-CLS pooling over ``seq``, and
a ``pmean`` over ``data`` merges batch shards. Autodiff is taken OUTSIDE
the shard_map (shard_map is transparent to it), so the ppermute ring's
reverse path and the data-axis gradient reduction come out correct by
construction instead of by hand-placed collectives.

The reference has neither axis (three laptop processes, L=128,
client1.py:27); this is the framework's "long sequences on a federated
fleet" scaling story (SURVEY.md §5 long-context + §2.11 comm backend).

Dropout: ON in this path (the reference trains with head dropout 0.3,
client1.py:57). Per-client keys enter the shard_map sharded over
``clients``; inside, the model's ring path draws hash-based masks keyed on
GLOBAL element coordinates (ops/hash_dropout.py, models/distilbert.py
``_seq_dropout``, parallel/ring_attention.py), so the sampled masks — and
therefore the training trajectory — are invariant to the seq-axis shard
count.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..train.engine import apply_warmup, prox_sq
from .fedavg import stack_params


def make_seq_mesh(
    clients: int,
    data: int,
    seq: int,
    *,
    devices: list | None = None,
    axis_names: tuple[str, str, str] = ("clients", "data", "seq"),
) -> Mesh:
    """A ``clients x data x seq`` mesh — parallel/mesh.py's make_mesh with
    the third (ring attention) axis."""
    from .mesh import make_mesh

    return make_mesh(
        clients, data, seq=seq, devices=devices, axis_names=axis_names
    )


def make_fedseq_loss(
    model,
    mesh: Mesh,
    *,
    clients_axis: str = "clients",
    data_axis: str = "data",
    seq_axis: str = "seq",
    dropout: bool = False,
    prox_mu: float = 0.0,
) -> Callable:
    """``(stacked_params, ids [C,B,L], mask [C,B,L], labels [C,B][, rngs
    [C]]) -> [C]`` per-client mean losses, computed sequence- and
    batch-parallel. The model must be built with ``attention_impl="ring"``
    and ``ring_axis=seq_axis``. With ``dropout=True`` the call takes
    per-client keys (sharded over ``clients``) and runs the model
    stochastic — masks are seq-shard-invariant (module docstring).

    With ``prox_mu > 0`` (FedProx) the call takes a stacked ``anchor``
    (the round-start params, sharded over ``clients``) right after the
    params and returns ``(objective [C], task [C])``: gradients flow from
    the objective (task + mu/2 ||p - anchor||^2, the dense path's exact
    term), logs report the task loss so FedProx and FedAvg curves stay
    comparable."""

    def local_losses(params_l, *rest):
        if prox_mu > 0.0:
            anchor_l, rest = rest[0], rest[1:]
        ids_l, mask_l, labels_l, *rngs_l = rest

        def one(p, ids, mask, labels, *key):
            if dropout:
                logits = model.apply(
                    {"params": p}, ids, mask, False,
                    rngs={"dropout": key[0]},
                )
            else:
                logits = model.apply({"params": p}, ids, mask, True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()

        losses = jax.vmap(one)(params_l, ids_l, mask_l, labels_l, *rngs_l)
        # Merge batch shards: each data instance saw B/data rows.
        task = jax.lax.pmean(losses, data_axis)
        if prox_mu == 0.0:
            return task
        # Params (and the anchor) are replicated over data/seq, so the
        # prox term needs no collective.
        sq = jax.vmap(prox_sq)(params_l, anchor_l)
        return task + 0.5 * prox_mu * sq, task

    batch_spec = P(clients_axis, data_axis, seq_axis)
    in_specs = [P(clients_axis)]
    if prox_mu > 0.0:
        in_specs.append(P(clients_axis))
    in_specs += [
        batch_spec,
        batch_spec,
        P(clients_axis, data_axis),
    ]
    if dropout:
        in_specs.append(P(clients_axis))
    return jax.shard_map(
        local_losses,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            P(clients_axis)
            if prox_mu == 0.0
            else (P(clients_axis), P(clients_axis))
        ),
    )


def make_fedseq_masked_loss(
    model,
    mesh: Mesh,
    *,
    clients_axis: str = "clients",
    data_axis: str = "data",
    seq_axis: str = "seq",
    dropout: bool = False,
    prox_mu: float = 0.0,
) -> Callable:
    """Ragged-stack variant: ``(stacked_params, ids, mask, labels, valid
    [C,B][, rngs [C]]) -> ([C] masked mean losses, [C] 0/1 had-rows)``.
    The per-client loss averages over the batch's valid rows only (global
    across data shards — per-shard sums psum'd before the divide), so a
    padded lockstep batch contributes loss 0 / has 0 exactly like the
    dense ragged path (train/fedsteps.py per_client_step_masked).

    With ``prox_mu > 0`` a stacked ``anchor`` follows the params and the
    return is ``(objective [C], task [C], has [C])`` — see
    :func:`make_fedseq_loss`."""

    def local_losses(params_l, *rest):
        if prox_mu > 0.0:
            anchor_l, rest = rest[0], rest[1:]
        ids_l, mask_l, labels_l, valid_l, *rngs_l = rest
        def one(p, ids, mask, labels, valid, *key):
            if dropout:
                logits = model.apply(
                    {"params": p}, ids, mask, False,
                    rngs={"dropout": key[0]},
                )
            else:
                logits = model.apply({"params": p}, ids, mask, True)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
            v = valid.astype(jnp.float32)
            return (ce * v).sum(), v.sum()

        s_loss, s_cnt = jax.vmap(one)(
            params_l, ids_l, mask_l, labels_l, valid_l, *rngs_l
        )  # [C_l] per-shard sums
        s_loss = jax.lax.psum(s_loss, data_axis)
        s_cnt = jax.lax.psum(s_cnt, data_axis)
        losses = s_loss / jnp.maximum(s_cnt, 1.0)
        has = (s_cnt > 0).astype(jnp.float32)
        if prox_mu == 0.0:
            return losses, has
        sq = jax.vmap(prox_sq)(params_l, anchor_l)
        # A no-row client's objective still carries the prox term, like
        # the dense masked step; its update is gated away on `has` anyway.
        return losses + 0.5 * prox_mu * sq, losses, has

    batch_spec = P(clients_axis, data_axis, seq_axis)
    in_specs = [P(clients_axis)]
    if prox_mu > 0.0:
        in_specs.append(P(clients_axis))
    in_specs += [
        batch_spec,
        batch_spec,
        P(clients_axis, data_axis),
        P(clients_axis, data_axis),
    ]
    if dropout:
        in_specs.append(P(clients_axis))
    return jax.shard_map(
        local_losses,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(
            (P(clients_axis),) * (2 if prox_mu == 0.0 else 3)
        ),
    )


def make_fedseq_train_step(
    model,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    *,
    warmup_steps: int = 0,
    clients_axis: str = "clients",
    data_axis: str = "data",
    seq_axis: str = "seq",
) -> Callable:
    """Jitted ``(stacked_params, stacked_opt_state, step, batch) ->
    (params, opt_state, losses [C])`` — one lockstep local step for every
    client, sequence-parallel inside, donated buffers."""
    loss_fn = make_fedseq_loss(
        model,
        mesh,
        clients_axis=clients_axis,
        data_axis=data_axis,
        seq_axis=seq_axis,
    )
    csh = NamedSharding(mesh, P(clients_axis))
    batch_sh = NamedSharding(mesh, P(clients_axis, data_axis, seq_axis))
    labels_sh = NamedSharding(mesh, P(clients_axis, data_axis))

    @partial(
        jax.jit,
        donate_argnums=(0, 1),
        in_shardings=(
            csh,
            csh,
            None,
            {
                "input_ids": batch_sh,
                "attention_mask": batch_sh,
                "labels": labels_sh,
            },
        ),
        out_shardings=(csh, csh, None),
    )
    def step(stacked_params, opt_state, step_idx, batch):
        def total(p):
            losses = loss_fn(
                p,
                batch["input_ids"],
                batch["attention_mask"],
                batch["labels"],
            )
            # Clients are independent: d(sum)/d(params[c]) touches only
            # client c's row, so one grad call yields every per-client grad.
            return losses.sum(), losses

        (_, losses), grads = jax.value_and_grad(total, has_aux=True)(
            stacked_params
        )
        updates, opt_state = jax.vmap(optimizer.update)(
            grads, opt_state, stacked_params
        )
        updates = apply_warmup(updates, step_idx, warmup_steps)
        params = optax.apply_updates(stacked_params, updates)
        return params, opt_state, losses

    return step


def make_fedseq_packed_loss(
    model,
    mesh: Mesh,
    *,
    data_axis: str = "data",
    seq_axis: str = "seq",
    dropout: bool = False,
    prox_mu: float = 0.0,
) -> Callable:
    """ONE client's sequence-parallel loss with NO client axis and NO
    vmap — the client-packing fast path's inner program (see
    train/fedsteps.py build_packed_step for the measured rationale; the
    3-axis variant additionally drops the inner unit vmap that the
    stacked program carries even at one local client). Signature:
    ``(params, [anchor,] ids [B,L], mask [B,L], labels [B][, key]) ->
    scalar mean loss`` (``(objective, task)`` under FedProx)."""

    def local_loss(p_l, *rest):
        if prox_mu > 0.0:
            anchor_l, rest = rest[0], rest[1:]
        ids_l, mask_l, labels_l, *key_l = rest
        if dropout:
            logits = model.apply(
                {"params": p_l}, ids_l, mask_l, False,
                rngs={"dropout": key_l[0]},
            )
        else:
            logits = model.apply({"params": p_l}, ids_l, mask_l, True)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels_l
        ).mean()
        task = jax.lax.pmean(loss, data_axis)
        if prox_mu == 0.0:
            return task
        return task + 0.5 * prox_mu * prox_sq(p_l, anchor_l), task

    in_specs = [P()]
    if prox_mu > 0.0:
        in_specs.append(P())
    in_specs += [P(data_axis, seq_axis), P(data_axis, seq_axis), P(data_axis)]
    if dropout:
        in_specs.append(P())
    return jax.shard_map(
        local_loss,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P() if prox_mu == 0.0 else (P(), P()),
    )


class FedSeqSteps(NamedTuple):
    """FedState-compatible jitted programs for the 3-axis composition —
    the same call signatures as train/fedsteps.py's FedSteps train/eval
    members, so FederatedTrainer's fit/eval loops drive either."""

    train_step: Callable  # (FedState, batch) -> (FedState, [C] losses)
    build_ragged_step: Callable  # () -> (FedState, batch) -> (FedState, ([C], [C]))
    eval_step: Callable  # (params, batch, valid) -> (BinaryCounts [C], probs [C,B])
    # () -> per-client packed step (client-packing fast path; see
    # train/fedsteps.py build_packed_step)
    build_packed_step: Callable = None


def build_fedseq_steps(cfg, model, optimizer, mesh: Mesh) -> FedSeqSteps:
    """Step closures over a ``clients x data x seq`` mesh. Dropout is ON
    whenever the model config carries any (the reference's 0.3 head
    dropout, client1.py:57): per-client keys fold (client rng, lockstep
    step) exactly like the dense path (train/fedsteps.py), and the
    model-side masks are seq-shard-invariant (module docstring)."""
    from ..ops.metrics import binary_counts
    from ..train.fedsteps import FedState

    mcfg = model.cfg
    dropout = (
        float(mcfg.dropout) > 0.0
        or float(mcfg.head_dropout) > 0.0
        or float(mcfg.attention_dropout) > 0.0
    )
    wsteps = cfg.train.warmup_steps
    mu = float(cfg.fed.prox_mu)
    csh = NamedSharding(mesh, P("clients"))
    repl = NamedSharding(mesh, P())
    seq_sh = NamedSharding(mesh, P("clients", "data", "seq"))
    row_sh = NamedSharding(mesh, P("clients", "data"))
    state_sh = FedState(csh, csh, repl, csh, repl)

    loss = make_fedseq_loss(model, mesh, dropout=dropout, prox_mu=mu)
    batch_sh = {"input_ids": seq_sh, "attention_mask": seq_sh, "labels": row_sh}
    from ..obs.profile import default_ledger

    ledger = default_ledger()
    note_train = ledger.hook("fedseq.train_step")

    def _train_body(state: FedState, batch, anchor):
        note_train(tuple(batch["input_ids"].shape))
        keys = (
            (jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                state.rngs, state.step
            ),)
            if dropout
            else ()
        )

        def total(p):
            args = (p,) if mu == 0.0 else (p, anchor)
            out = loss(
                *args, batch["input_ids"], batch["attention_mask"],
                batch["labels"], *keys,
            )
            # Clients are independent: d(sum)/d(params[c]) touches only
            # client c's row — one grad call yields every per-client grad.
            # Under FedProx the objective carries the prox term; the task
            # loss is what gets reported (dense-path parity).
            obj, task = out if mu > 0.0 else (out, out)
            return obj.sum(), task

        (_, losses), grads = jax.value_and_grad(total, has_aux=True)(
            state.params
        )
        updates, opt_state = jax.vmap(optimizer.update)(
            grads, state.opt_state, state.params
        )
        updates = apply_warmup(updates, state.step, wsteps)
        params = optax.apply_updates(state.params, updates)
        return (
            state._replace(
                params=params, opt_state=opt_state, step=state.step + 1
            ),
            losses,
        )

    # FedProx signature: (state, batch, anchor) — the same contract
    # FederatedTrainer.fit_local drives on the dense path.
    train_step = ledger.jit(
        "fedseq.train_step",
        _train_body
        if mu > 0.0
        else lambda state, batch: _train_body(state, batch, None),
        donate_argnums=(0,),
        in_shardings=(state_sh, batch_sh) + ((csh,) if mu > 0.0 else ()),
        out_shardings=(state_sh, csh),
    )

    ragged_batch_sh = dict(batch_sh, valid=row_sh, warmup_step=row_sh)
    masked_loss = make_fedseq_masked_loss(
        model, mesh, dropout=dropout, prox_mu=mu
    )

    def build_ragged_step():
        def ragged_body(state: FedState, batch, anchor):
            keys = (
                (jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    state.rngs, state.step
                ),)
                if dropout
                else ()
            )

            def total(p):
                args = (p,) if mu == 0.0 else (p, anchor)
                out = masked_loss(
                    *args, batch["input_ids"], batch["attention_mask"],
                    batch["labels"], batch["valid"], *keys,
                )
                obj, losses, has = out if mu > 0.0 else (out[0], *out)
                return obj.sum(), (losses, has)

            (_, (losses, has)), grads = jax.value_and_grad(
                total, has_aux=True
            )(state.params)
            updates, new_opt = jax.vmap(optimizer.update)(
                grads, state.opt_state, state.params
            )
            # Warmup rides each client's OWN executed-step count
            # (train/batches.py federated_batches_ragged), like the dense
            # ragged path.
            updates = jax.vmap(
                lambda u, s: apply_warmup(u, s, wsteps)
            )(updates, batch["warmup_step"][:, 0])
            new_params = optax.apply_updates(state.params, updates)
            gate = lambda n, o, h: jax.tree.map(  # noqa: E731
                lambda a, b: jnp.where(h, a, b), n, o
            )
            params = jax.vmap(gate)(new_params, state.params, has > 0)
            opt_state = jax.vmap(gate)(new_opt, state.opt_state, has > 0)
            return (
                state._replace(
                    params=params, opt_state=opt_state, step=state.step + 1
                ),
                (losses, has),
            )

        if mu > 0.0:
            return partial(
                jax.jit,
                donate_argnums=(0,),
                in_shardings=(state_sh, ragged_batch_sh, csh),
                out_shardings=(state_sh, (csh, csh)),
            )(ragged_body)
        return partial(
            jax.jit,
            donate_argnums=(0,),
            in_shardings=(state_sh, ragged_batch_sh),
            out_shardings=(state_sh, (csh, csh)),
        )(lambda state, batch: ragged_body(state, batch, None))

    def local_eval(params_l, ids_l, mask_l, labels_l, valid_l):
        def one(p, ids, mask, labels, valid):
            logits = model.apply({"params": p}, ids, mask, True)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
            probs = jax.nn.softmax(logits, axis=-1)[:, 1]
            return ce, logits, probs

        ce, logits, probs = jax.vmap(one)(
            params_l, ids_l, mask_l, labels_l, valid_l
        )

        def counts_one(ce_c, logits_c, labels_c, valid_c):
            v = valid_c.astype(jnp.float32)
            # Batch-mean loss over GLOBAL valid rows: per-shard sums merged
            # over the data axis before the divide (engine.eval_counts
            # computes the same mean unsharded).
            s_loss = jax.lax.psum((ce_c * v).sum(), "data")
            s_cnt = jax.lax.psum(v.sum(), "data")
            loss_c = s_loss / jnp.maximum(s_cnt, 1.0)
            local = binary_counts(logits_c, labels_c, loss_c, valid_c)
            # Sum the count fields over data shards; loss_sum/n_batches are
            # already global (recompute them from the global mean).
            has = (s_cnt > 0).astype(jnp.float32)
            summed = jax.tree.map(lambda x: jax.lax.psum(x, "data"), local)
            return summed._replace(
                loss_sum=loss_c * has, n_batches=has
            )

        counts = jax.vmap(counts_one)(ce, logits, labels_l, valid_l)
        return counts, probs

    eval_inner = jax.shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(
            P("clients"),
            P("clients", "data", "seq"),
            P("clients", "data", "seq"),
            P("clients", "data"),
            P("clients", "data"),
        ),
        out_specs=(P("clients"), P("clients", "data")),
    )

    @partial(
        jax.jit,
        in_shardings=(csh, batch_sh, row_sh),
    )
    def eval_step(stacked_params, batch, valid):
        return eval_inner(
            stacked_params, batch["input_ids"], batch["attention_mask"],
            batch["labels"], valid,
        )

    build_packed_step = lru_cache(maxsize=1)(
        lambda: _build_fedseq_packed_step(
            model, optimizer, mesh, dropout=dropout, mu=mu, wsteps=wsteps
        )
    )

    return FedSeqSteps(
        train_step=train_step,
        build_ragged_step=build_ragged_step,
        eval_step=eval_step,
        build_packed_step=build_packed_step,
    )


def _build_fedseq_packed_step(
    model, optimizer, mesh: Mesh, *, dropout: bool, mu: float, wsteps: int
) -> Callable:
    """Jitted per-client packed fedseq step:
    ``(cstate, batch[, anchor]) -> (cstate, task)`` — the shared packed
    builder (train/fedsteps.py make_packed_step: same rng fold, Adam,
    warmup, donation as the dense path) over the 3-axis packed loss.
    Same math as the stacked 3-axis step for one client — pinned by
    tests/test_fedseq.py::test_packed_fedseq_matches_stacked."""
    from ..train.fedsteps import make_packed_step

    loss = make_fedseq_packed_loss(model, mesh, dropout=dropout, prox_mu=mu)

    def objective(p, batch, step_rng, anchor):
        keys = (step_rng,) if dropout else ()
        args = (p,) if mu == 0.0 else (p, anchor)
        out = loss(
            *args, batch["input_ids"], batch["attention_mask"],
            batch["labels"], *keys,
        )
        return out if mu > 0.0 else (out, out)

    return make_packed_step(objective, optimizer, wsteps, mu)


def init_fedseq_state(
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    params: Any,
    num_clients: int,
    *,
    clients_axis: str = "clients",
) -> tuple[Any, Any]:
    """Stack single-model ``params`` into the ``[C, ...]`` clients-sharded
    layout (every client starts identical — the reference's shared
    pretrained start, client1.py:56) plus matching optimizer state."""
    csh = NamedSharding(mesh, P(clients_axis))
    stacked = jax.device_put(stack_params(params, num_clients), csh)
    opt_state = jax.jit(
        lambda p: jax.vmap(optimizer.init)(p),
        in_shardings=(csh,),
        out_shardings=csh,
    )(stacked)
    return stacked, opt_state
