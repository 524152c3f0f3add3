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
benchmark's ``correct`` compares it with 0; none is dropped in silence.

The buffer keeps that capacity; the work on it follows the fill. A call's
slots fill a PREFIX of the buffer (expert after expert, no gaps), at an even
load under a fifth of it, and a TPU gather or scatter-add takes its time by
the row, filled or not (79 ns a row of 8 KB where the memory would take 10:
ledger, PR 36). So the gather, the grouped products' operands and the
scatter-add run over the shortest of a few prefixes that holds the call's
slots (:func:`expert_rungs`: the capacity at a quarter and at the whole of
:data:`CAPACITY_FACTOR`), chosen on the device by a ``lax.switch``: no host
read, no recompile, nothing to set, and the top rung is the whole buffer. The
rows a call MOVED are counted beside the slots that filled them
(``models/blocks.py`` sows ``rows``): what is not filled is padding that the
three still carry.

``jax.named_scope``s (``obs/trace.py::SCOPES``; the caller opens
``moe/experts`` around :func:`held_experts_ffn`): ``dispatch`` (token-slots to
buffer rows: the one-hot and the cumulative sums, once a call; in a rung the
scatters of ``token_of`` and ``weight_of`` and the rows' gather), ``grouped``
(the three grouped products and the SwiGLU between them), ``combine`` (mask and
weights on the rows, and the scatter-add per token).
"""

from __future__ import annotations

import functools
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


def expert_capacity(tokens: int, k: int, n_experts: int, held: int, factor: float = CAPACITY_FACTOR) -> int:
    """Rows of the held experts' shared buffer: :data:`CAPACITY_FACTOR`
    times their mean total load ``tokens * k * held / n_experts``, to a
    multiple of 8, and never more than ``tokens * min(k, held)`` (the bound
    that holds for every routing). With another ``factor``: the length of a
    prefix of that buffer (:func:`expert_rungs`)."""
    want = math.ceil(tokens * k * held / n_experts * factor)
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


def expert_rungs(tokens: int, k: int, n_experts: int, held: int) -> tuple[int, ...]:
    """The prefixes of the shared buffer that a layer call may move, ascending:
    :func:`expert_capacity` at a quarter of :data:`CAPACITY_FACTOR` (the mean
    total load itself) and at the whole of it, which is the capacity; where
    the bound or a tiny shape makes the two equal, the ladder is one rung.

    Two rungs and not three: every rung compiles the three grouped products
    and, in the backward rule, their six transposes again, and a set-up pays
    for it in every run. With ``capacity / 2`` between them a window cell's
    two programs took 2.9 s longer to trace and to fetch from a warm compile
    cache (11.3 s against 8.4), with these two 1.6 s (my chip runs, PR 37);
    what the third would save is the rows of a call that fills between a
    quarter and a half of its buffer."""
    return tuple(sorted({expert_capacity(tokens, k, n_experts, held, CAPACITY_FACTOR / cut) for cut in (4, 1)}))


def _rung_of(ladder, groups):
    """The index of the shortest prefix of ``ladder`` that holds the rows
    ``groups`` fill: one comparison for every rung but the last."""
    filled = groups.sum()
    return sum((filled > rows for rows in ladder[:-1]), start=jnp.zeros((), jnp.int32))


def _rung(rows: int, x, w, row, groups, w_gate, w_up, w_down):
    """The pass over the buffer's first ``rows`` rows: a branch of the
    ``lax.switch`` of :func:`_buffer_pass`. A branch's instructions lose the
    scopes around the switch from their path's end, so it opens ``moe/experts``
    again for the readers of ``.../moe/experts/dispatch/...``."""
    T, D = x.shape
    with jax.named_scope("moe/experts"):
        with jax.named_scope("dispatch"):
            token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), row.shape[0] // T)
            # a row past the prefix is out of range: dropped by the scatter
            token_of = jnp.full((rows,), T, jnp.int32).at[row].set(token, mode="drop")
            weight_of = jnp.zeros((rows,), jnp.float32).at[row].set(w, mode="drop")
            x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
            xe = x_pad[token_of]  # [rows, D]; row T is the empty rows' zero
        with jax.named_scope("grouped"):
            h = jax.nn.silu(jax.lax.ragged_dot(xe, w_gate, groups)) * jax.lax.ragged_dot(xe, w_up, groups)
            ye = jax.lax.ragged_dot(h, w_down, groups, preferred_element_type=jnp.float32)
        with jax.named_scope("combine"):
            # Rows past the last group belong to no expert: whatever the grouped
            # product left there is not a result.
            ye = jnp.where((jnp.arange(rows) < groups.sum())[:, None], ye * weight_of[:, None], 0.0)
            return jnp.zeros((T + 1, D), jnp.float32).at[token_of].add(ye)[:T]


def _rung_vjp(rows: int, x, w, row, groups, weights, dy):
    """``dy``'s cotangents in ``(x, w, weights)`` through :func:`_rung`: a
    branch of the backward rule's switch, which computes the rung's forward
    again."""

    def rung(x, w, weights):
        # ``jax.vjp`` writes its ``jvp(...)`` around the first scope opened
        # under it, and a path's ``jvp(moe/experts)/dispatch`` no reader finds
        with jax.named_scope("moe/experts"):
            return _rung(rows, x, w, row, groups, *weights)

    return jax.vjp(rung, x, w, weights)[1](dy)


# Jitted, so that a step's expert layers, which differ in nothing JAX traces
# by, trace a rung's two bodies once and not once a layer (set-up pays it).
_rung_call = jax.jit(_rung, static_argnums=0)
_rung_vjp_call = jax.jit(_rung_vjp, static_argnums=0)


def _switch(ladder, groups, branch, *operands):
    """``branch(rows, *operands)`` at the shortest rung that holds ``groups``."""
    return jax.lax.switch(_rung_of(ladder, groups), [functools.partial(branch, rows) for rows in ladder], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _buffer_pass(ladder, x, w, row, groups, w_gate, w_up, w_down):
    """``y [T, D]`` float32 from the slots' rows in the buffer (``row``
    ``[T * k]``, their weights ``w``, the experts' ``groups``), over the
    shortest prefix of ``ladder`` that holds them, chosen on the device.

    Differentiated by hand, because a differentiated ``cond`` hands back the
    residuals of EVERY branch (each writes zeros for the others'): the first
    rung would write, and the backward pass hold, the full buffer's besides
    its own. The residuals here are the operands, which no rung shapes, and
    the backward rule is the same switch over a rung's own ``jax.vjp``: it
    computes the rung's forward again, which a caller that recomputes its
    block (``cfg.remat``) then does not (the recomputation's switch has no
    consumer)."""
    return _switch(ladder, groups, _rung_call, x, w, row, groups, w_gate, w_up, w_down)


def _buffer_fwd(ladder, *operands):
    return _buffer_pass(ladder, *operands), operands


def _buffer_bwd(ladder, operands, dy):
    x, w, row, groups, *weights = operands
    dx, dw, dweights = _switch(ladder, groups, _rung_vjp_call, x, w, row, groups, weights, dy)
    return (dx, dw, None, None, *dweights)


_buffer_pass.defvjp(_buffer_fwd, _buffer_bwd)


def held_experts_ffn(
    x, idx, w, valid, w_gate, w_up, w_down, *, offset: int, capacity: int, dtype, rungs: tuple[int, ...] = ()
):
    """``sum_e w_e SwiGLU_e(x)`` over the chosen experts this chip holds.

    ``x``: ``[T, D]``; ``idx``, ``w``: ``[T, k]`` from :func:`route_topk`;
    ``valid``: ``[T]`` bool (padding is routed nowhere); ``w_gate``,
    ``w_up``: ``[held, D, F]``, ``w_down``: ``[held, F, D]`` (experts
    ``offset .. offset + held``); ``rungs``: prefixes of the buffer
    (:func:`expert_rungs`) that a call may move in place of all ``capacity``
    rows. Returns ``(y [T, D] float32, slots [held] int32: the slots routed
    to each held expert, overflow [] int32: the slots beyond ``capacity``,
    which are NOT in ``y``, rows [] int32: the rows this call moved)``.

    The rung is the shortest that holds the routed slots' total, chosen on
    the device from ``idx`` alone: the forward pass, a recomputation that
    keeps :data:`ROUTE_CHOICE` and the backward pass take the same one, and
    every rung gives the top rung's result (the rows it leaves out add 0.0
    into the dump row). Under ``vmap`` a ``switch`` with a batched index runs
    every branch and selects: right, and slower than the top rung alone."""
    T, D = x.shape
    held = w_gate.shape[0]
    k = idx.shape[-1]
    ladder = tuple(sorted({r for r in rungs if r < capacity} | {capacity}))
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
        rows = jnp.asarray(ladder, jnp.int32)[_rung_of(ladder, groups)]
    y = _buffer_pass(
        ladder, x.astype(dtype), w.reshape(T * k).astype(jnp.float32), row, groups,
        w_gate.astype(dtype), w_up.astype(dtype), w_down.astype(dtype),
    )
    return y, slots, overflow.astype(jnp.int32), rows
