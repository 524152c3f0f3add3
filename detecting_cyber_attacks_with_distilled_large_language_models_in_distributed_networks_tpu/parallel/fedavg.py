"""FedAvg as an XLA collective.

The reference's aggregation pipeline is: each client gzip-pickles a 245 MB
state dict, ships it over TCP (client1.py:276-295), a server thread decodes it
(server.py:57-65), a Python loop computes an in-place unweighted mean
(server.py:67-79, 0.36 s host-side), and a second socket broadcasts the result
back (server.py:81-114). Total round path: minutes of serialize/transfer.

Here the whole pipeline is one jitted mean over the stacked client axis of a
``[C, ...]``-parameter pytree sharded over the ``clients`` mesh axis — XLA
lowers it to an all-reduce on ICI and the broadcast is implicit (the output is
the already-replicated mean written back to every client's shard). Weights
never leave the devices; there is no serialization step at all.

Capabilities beyond the reference:
* weighted FedAvg (weight clients by sample count),
* masked FedAvg (dropped/failed clients excluded from the mean — the
  reference instead hangs its accept loop, server.py:69-71,124-132).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .mesh import FedShardings


def stack_params(params: Any, num_clients: int) -> Any:
    """Single-model params -> the ``[C, ...]`` stacked layout (every row
    identical — the reference's shared pretrained start, client1.py:56).
    The one definition of the per-client leading axis, shared by the
    federated trainer, the fedseq composition, and tests."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_clients, *x.shape)), params
    )


def weighted_mean(
    stacked_params: Any,
    weights: jnp.ndarray | None = None,
    mask: jnp.ndarray | None = None,
) -> Any:
    """Weighted, masked mean over the leading (clients) axis — the
    single-model fp32 result, NOT broadcast back (fedavg adds that).

    ``weights``: [C] client weights (e.g. local sample counts); uniform if
    None — the reference's unweighted mean (server.py:73-76).
    ``mask``: [C] 0/1 survivors; masked-out clients contribute nothing and
    the divisor shrinks accordingly.
    """
    leaves = jax.tree.leaves(stacked_params)
    if not leaves:
        return stacked_params
    C = leaves[0].shape[0]
    w = jnp.ones((C,), jnp.float32) if weights is None else weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    denom = jnp.maximum(w.sum(), 1e-9)
    wn = w / denom

    def _avg(x: jnp.ndarray) -> jnp.ndarray:
        wshape = (C,) + (1,) * (x.ndim - 1)
        # fp32 accumulation regardless of param dtype
        return (x.astype(jnp.float32) * wn.reshape(wshape)).sum(axis=0)

    return jax.tree.map(_avg, stacked_params)


def fedavg(
    stacked_params: Any,
    weights: jnp.ndarray | None = None,
    mask: jnp.ndarray | None = None,
) -> Any:
    """:func:`weighted_mean` broadcast back to ``[C, ...]`` so each client
    shard receives the average."""
    mean = weighted_mean(stacked_params, weights, mask)
    return jax.tree.map(
        lambda m, x: jnp.broadcast_to(m.astype(x.dtype), x.shape),
        mean,
        stacked_params,
    )


def make_server_optimizer(fed_cfg) -> "optax.GradientTransformation | None":
    """The FedOpt server optimizer (Reddi et al.): applied to the round's
    mean update at the aggregation boundary. "momentum" = FedAvgM (SGD with
    heavy-ball momentum over round updates), "adam" = FedAdam, "yogi" =
    FedYogi (additive second moment — more stable under the bursty
    pseudo-gradient variance of non-IID rounds). At server_lr=1 with no
    momentum, the step reduces exactly to plain FedAvg (new global = mean).

    Shared by the SPMD mesh tier (FederatedTrainer) and the TCP tier's
    strategy registry (strategies/core.py), which wraps it around the
    streamed fold's finalize-time mean. The transform's optimizer state
    is checkpointable across server restarts via the strategy layer's
    export_state/restore_state (``serve --strategy-state-file``): optax
    states here are flat pytrees of arrays whose structure is a pure
    function of the (sorted-key) fp32 param template, which is what lets
    a restarted server rebuild the treedef and re-adopt the leaves."""
    import optax

    if fed_cfg.server_opt == "momentum":
        return optax.sgd(fed_cfg.server_lr, momentum=fed_cfg.server_momentum)
    if fed_cfg.server_opt == "adam":
        return optax.adam(fed_cfg.server_lr)
    if fed_cfg.server_opt == "yogi":
        return optax.yogi(fed_cfg.server_lr)
    return None


def make_fedavg_step(shardings: FedShardings) -> Callable:
    """Jitted FedAvg over the mesh: inputs/outputs sharded ``P('clients')``,
    so the mean lowers to a cross-client all-reduce on ICI."""

    @partial(
        jax.jit,
        in_shardings=(shardings.client, None, None),
        out_shardings=shardings.client,
        static_argnums=(),
    )
    def fedavg_step(stacked_params, weights, mask):
        return fedavg(stacked_params, weights, mask)

    return fedavg_step
