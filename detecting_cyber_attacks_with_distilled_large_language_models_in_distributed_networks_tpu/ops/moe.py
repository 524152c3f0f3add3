"""The expert layer's routed part, for a chip that holds some of the experts.

Expert parallelism gives each chip ``held`` of the layer's experts. The router
keeps its published width: every token scores ALL experts and picks its top
``k``; this chip then computes, for the token-slots that name one of ITS
experts, ``w_e Expert_e(x)``, and adds them per token. What the absent experts
would add is another chip's part (the all-to-all that would bring it is not
here: ROADMAP.md), and nothing stands in for it.

The slots that name a held expert are gathered, expert after expert, into ONE
``[capacity, D]`` buffer, and the held experts run as grouped products over it
(``lax.ragged_dot``: on the TPU a native grouped-matmul kernel whose work
follows the rows really filled; plain XLA elsewhere). The buffer is shared, so
an uneven split between the held experts costs nothing: only their TOTAL has to
fit. A token names an expert at most once, so ``capacity = T * min(k, held)``
can never overflow; a smaller capacity (a multiple of the mean total) holds
less memory, and the slots it cannot take are COUNTED and handed back
(``overflow``): ``Trainer`` reads the count after every epoch and every
evaluation, publishes it and logs an error when it is not 0, and the
benchmark's ``correct`` compares it with 0; none is dropped in silence. The
rows the buffer OFFERS are counted beside the slots that filled them
(``models/blocks.py`` sows ``rows``): what is not filled is padding that the
gather, the grouped products' operands and the scatter-add still carry.

``jax.named_scope``s (``obs/trace.py::SCOPES``; the caller opens
``moe/experts`` around :func:`held_experts_ffn`): ``dispatch`` (token-slots to
buffer rows: the one-hot, the cumulative sums, the scatters of ``token_of`` and
``weight_of``, and the rows' gather), ``grouped`` (the three grouped products
and the SwiGLU between them), ``combine`` (mask and weights on the rows, and
the scatter-add per token).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: The name the chosen experts carry (``checkpoint_name``). A caller that
#: recomputes the layer in the backward pass keeps them under it
#: (``jax.checkpoint_policies.save_only_these_names``): a top-k chosen again
#: from a residual stream that the recomputation rounds elsewhere falls
#: otherwise on some token-slots, and the backward pass then differentiates
#: experts the forward pass did not run.
ROUTE_CHOICE = "route_choice"


#: The shared buffer's rows as a multiple of the held experts' mean total
#: load. Sized on rows of one fixed flow template at random weights, where
#: the busiest of 8 held experts drew 1.6-2.2 times its mean and a buffer of
#: twice the mean for each expert apart lost 8-16% of the slots (my chip
#: runs, PR 28); trained weights may route less evenly, which is why the
#: overflow is counted wherever the layer runs.
CAPACITY_FACTOR = 4.0


def expert_capacity(tokens: int, k: int, n_experts: int, held: int) -> int:
    """Rows of the held experts' shared buffer: :data:`CAPACITY_FACTOR`
    times their mean total load ``tokens * k * held / n_experts``, to a
    multiple of 8, and never more than ``tokens * min(k, held)`` (the bound
    that holds for every routing)."""
    want = math.ceil(tokens * k * held / n_experts * CAPACITY_FACTOR)
    return min(tokens * min(k, held), -(-want // 8) * 8)


def route_topk(scores, select_bias, k: int, scale: float):
    """The chosen experts and their weights. ``scores``: ``[T, E]`` float32
    (the sigmoid of the router's logits, or their softmax); the top ``k`` by ``scores +
    select_bias`` are chosen, their weights are the plain scores divided by
    their sum, times ``scale``. Returns ``(idx [T, k] int32, w [T, k])``."""
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(select_bias), k)
    idx = checkpoint_name(idx, ROUTE_CHOICE)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True) * scale


def held_experts_ffn(
    x, idx, w, valid, w_gate, w_up, w_down, *, offset: int, capacity: int, dtype
):
    """``sum_e w_e SwiGLU_e(x)`` over the chosen experts this chip holds.

    ``x``: ``[T, D]``; ``idx``, ``w``: ``[T, k]`` from :func:`route_topk`;
    ``valid``: ``[T]`` bool (padding is routed nowhere); ``w_gate``,
    ``w_up``: ``[held, D, F]``, ``w_down``: ``[held, F, D]`` (experts
    ``offset .. offset + held``). Returns ``(y [T, D] float32, slots [held]
    int32: the slots routed to each held expert, overflow [] int32: the slots
    beyond ``capacity``, which are NOT in ``y``)``."""
    T, D = x.shape
    held = w_gate.shape[0]
    k = idx.shape[-1]
    with jax.named_scope("dispatch"):
        local = (idx - offset).reshape(T * k)
        here = ((local >= 0) & (local < held)) & jnp.repeat(valid, k)
        onehot = (here[:, None] & (local[:, None] == jnp.arange(held)[None, :])).astype(jnp.int32)
        slots = onehot.sum(0)
        # A slot's row: its expert's first row (the experts before it, summed)
        # plus how many earlier slots named the same expert.
        first = jnp.cumsum(slots) - slots
        row = ((jnp.cumsum(onehot, axis=0) - 1 + first[None, :]) * onehot).sum(-1)
        row = jnp.where(here & (row < capacity), row, capacity)  # out of range: dropped by the scatter
        groups = jnp.clip(capacity - first, 0, slots)  # the rows of each expert that the buffer holds
        overflow = slots.sum() - groups.sum()
        token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        token_of = jnp.full((capacity,), T, jnp.int32).at[row].set(token, mode="drop")
        weight_of = jnp.zeros((capacity,), jnp.float32).at[row].set(
            w.reshape(T * k).astype(jnp.float32), mode="drop"
        )
        x_pad = jnp.concatenate([x.astype(dtype), jnp.zeros((1, D), dtype)], axis=0)
        xe = x_pad[token_of]  # [capacity, D]; row T is the empty rows' zero
    with jax.named_scope("grouped"):
        h = jax.nn.silu(jax.lax.ragged_dot(xe, w_gate.astype(dtype), groups)) * jax.lax.ragged_dot(
            xe, w_up.astype(dtype), groups
        )
        ye = jax.lax.ragged_dot(h, w_down.astype(dtype), groups, preferred_element_type=jnp.float32)
    with jax.named_scope("combine"):
        # Rows past the last group belong to no expert: whatever the grouped
        # product left there is not a result.
        ye = jnp.where((jnp.arange(capacity) < groups.sum())[:, None], ye * weight_of[:, None], 0.0)
        y = jnp.zeros((T + 1, D), jnp.float32).at[token_of].add(ye)
        return y[:T], slots, overflow.astype(jnp.int32)
