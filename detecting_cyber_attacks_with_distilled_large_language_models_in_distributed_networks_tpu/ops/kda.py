"""Kimi Delta Attention: the gated delta rule with a per-channel decay,
computed chunk by chunk.

Per head, with keys of width ``dk`` and values of width ``dv``, the layer is
the recurrence

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T     S_0 = 0
    o_t = S_t^T q_t

(``a_t = exp(g_t)`` in ``(0, 1]^dk`` the decay, ``b_t`` in ``(0, 1)`` the
write strength). :func:`kda_recurrent` is that recurrence token by token;
:func:`kda_chunked` gives the same result from matrix products. Writing
``u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t)`` the state is
``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, and inside a chunk that starts from
the state ``S`` (``G_t`` the running sum of ``g`` from the chunk's start):

    (I + Diag(b) strict_tril(A)) U = Diag(b) (V - (K * e^G) S)
    O = (Q * e^G) S + tril(B) U
    S' = Diag(e^{G_C}) S + (K * e^{G_C - G})^T U

with ``A[t, s] = sum_c k_tc k_sc e^{G_tc - G_sc}`` and ``B`` the same with
``q_t``. ``W`` and ``U0`` below are the triangular system's two right-hand
sides solved, so what passes from chunk to chunk is matrix products only:

    U = U0_c - W_c S        O_c = Q_c S + B_c U        S <- decay_c * S + K_c^T U

What runs where. Three Pallas kernels, each over ``(rows, heads / HEADS,
chunks)`` with the chunks last and in order, the (transposed) states of
:data:`HEADS` heads in VMEM scratch (a state never goes through HBM between two
chunks) and the operands read in place from ``[B, H, N, C, .]``; off the TPU all
three run in Pallas' interpreter. Which of them a call of
:func:`kda_chunked` runs depends on one thing, which the code observes through
a ``jax.custom_vjp``: whether JAX asks it for a gradient.

* Nobody does (a forward pass whose intermediates nothing keeps: a training
  step's first pass, ``nn.remat``'s recomputation, evaluation): ``kda_fwd``,
  one launch for the whole batch, under the scope ``chunks/fwd``. It reads
  ``q``, ``k``, ``v``, the log-decay and ``beta`` and does everything above for
  a chunk in VMEM: the running sum ``G`` (a float32 product with the triangle
  of ones), the pair matrices, the system's inverse by float32 products at full
  precision (the 16 x 16 diagonal blocks' ``(I + N)^-1 = (I - N)(I + N^2)(I +
  N^4)(I + N^8)``, then merged upwards twice), ``W`` and ``U0``, the three
  lines. It writes ``O`` only: nothing of a chunk's ``A``, ``B``, ``W``, ``U0``
  or ``e^G`` goes through HBM.
* Somebody does: the ``fwd`` rule is the same launch and keeps the five inputs
  (a checkpoint by hand); the ``bwd`` rule maps over the batch's rows the
  gradient of :func:`_kda_chunked`, which builds the pair matrices and solves
  the system by substitution in plain XLA and runs the three lines in
  ``kda_chunks_fwd`` (which writes beside ``O`` the state each chunk STARTED
  from: ``[N, dk, dv]`` float32 a row and head, alive inside that row's
  backward pass) and their transpose in ``kda_chunks_bwd`` (the chunks
  backwards, the state's cotangent in VMEM, ``U`` made again from the saved
  state), joined by a ``custom_vjp`` of their own. Row after row, so that the
  backward pass holds one row's intermediates, not the batch's.

``e^{G_t - G_s}`` cannot be split into ``e^{G_t}`` times ``e^{-G_s}`` over a
whole chunk: a fast head forgets by ``e^{-100}`` in 64 tokens and the second
factor overflows. ``A`` and ``B`` are therefore built from 16 x 16 blocks,
and no factor's exponent is positive except a diagonal block's clipped one.
In XLA (:func:`_pair_matrices`): a block below the diagonal from three factors
that are all at most 1 (the rows' decay since their block's first token, the
decay between the two blocks, the columns' decay up to their block's last
token), a block on the diagonal from the rows' factor and the columns'
inverse factor, whose exponent is at most the block's own decay and is
clipped at :data:`MAX_BLOCK_DECAY` (never reached while a channel forgets less
than ``e^{-80}`` in 16 tokens, a mean log-decay of 5 a token; the published
initialisation gives at most about 2). In ``kda_fwd``: a block of rows' factor
``x_t e^{G_t - F}`` (``F`` the running sum at the block's first token) against
every column's ``k_s e^{F - G_s}``, whose exponent is not positive for a
column of an earlier block and is the same clipped one for a column of the
block itself (later columns are masked): the same two cases, one product a
block of rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Tokens a chunk: an implementation size, not the model's (any multiple of
#: BLOCK gives the recurrence's result; 64 keeps the in-chunk matrices small
#: beside the 128-wide state products).
CHUNK = 64
#: Rows and columns of the blocks the in-chunk matrices are built from.
BLOCK = 16
#: Heads a grid step of the recurrence's kernels: an implementation size too
#: (the grid's fixed cost a step is shared by that many independent chains).
HEADS = 8
#: Heads of a grid step that ``kda_fwd`` computes side by side: a head is one
#: chain of dependent products, two chains fill each other's waits (the v5e's
#: compiler schedules 12.3 thousand bundles a grid step for 15.3 one by one;
#: four side by side spill four times the registers for 11.7).
ABREAST = 2
#: Largest exponent of a diagonal block's inverse decay (float32 holds e^88).
MAX_BLOCK_DECAY = 80.0
#: Tokens times heads that one step of the backward pass's ``lax.map`` takes:
#: a row of 4,096 tokens with its 32 heads (1.6 GB of intermediates at 128 /
#: 128). A longer row goes through in groups of heads, which are independent
#: chains: a row of 16,384 tokens whole would hold 6.4 GB.
BWD_TOKEN_HEADS = 4096 * 32


def kda_recurrent(q, k, v, g, beta):
    """The recurrence, token by token, in float32. ``q``, ``k``, ``g``:
    ``[B, H, L, dk]``; ``v``: ``[B, H, L, dv]``; ``beta``: ``[B, H, L]``.
    Returns ``o`` ``[B, H, L, dv]``."""
    q, k, v, g, beta = (jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))
    B, H, _, dk = q.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2)


def _pair_matrices(x, k, G, dtype):
    """``M[t, s] = sum_c x_tc k_sc e^{G_tc - G_sc}`` for ``t >= s`` inside
    each chunk (entries above the diagonal are not meaningful: the callers
    mask them). ``x``: ``[X, ..., C, dk]`` (a leading axis of row operands
    that share ``k`` and ``G``), ``k``, ``G``: ``[..., C, dk]``. Returns
    ``[X, ..., C, C]`` float32."""
    C, dk = k.shape[-2:]
    n = C // BLOCK
    lead = k.shape[:-2]
    blk = lambda a: a.reshape(a.shape[:-2] + (n, BLOCK, dk))  # noqa: E731
    xb, kb, Gb = blk(x), blk(k), blk(G)
    first, last = Gb[..., 0, :], Gb[..., -1, :]  # [..., n, dk]
    rows = xb * jnp.exp(Gb - first[..., None, :])  # decay since the block's first token
    # On the diagonal: rows' factor times the columns' inverse factor.
    cols_inv = kb * jnp.exp(jnp.minimum(first[..., None, :] - Gb, MAX_BLOCK_DECAY))
    diag = jnp.einsum(
        "...tc,...sc->...ts", rows.astype(dtype), cols_inv.astype(dtype),
        preferred_element_type=jnp.float32,
    )  # [X, ..., n, BLOCK, BLOCK]
    out = jnp.zeros(x.shape[:1] + lead + (n, n, BLOCK, BLOCK), jnp.float32)
    idx = np.arange(n)
    out = out.at[..., idx, idx, :, :].set(diag)
    if n > 1:
        ii, jj = np.tril_indices(n, -1)
        cols = kb * jnp.exp(last[..., None, :] - Gb)  # decay up to the block's last token
        between = jnp.exp(first[..., ii, :] - last[..., jj, :])  # [..., P, dk], at most 1
        below = jnp.einsum(
            "...ptc,...psc->...pts",
            (rows[..., ii, :, :] * between[..., None, :]).astype(dtype),
            cols[..., jj, :, :].astype(dtype),
            preferred_element_type=jnp.float32,
        )
        out = out.at[..., ii, jj, :, :].set(below)
    # [.., n_i, n_j, t, s] -> [.., (n_i t), (n_j s)]
    out = jnp.swapaxes(out, -3, -2)
    return out.reshape(x.shape[:1] + lead + (C, C))


def kda_chunked(q, k, v, g, beta, *, dtype=jnp.float32):
    """The recurrence's result from chunks of :data:`CHUNK` tokens. Shapes as
    :func:`kda_recurrent`; ``g`` and ``beta`` float32. ``dtype`` is the type
    the matrix products read (their sums, the decays, the substitution and
    the state are float32). Any length: the tail is padded with tokens that
    write nothing (``beta = 0``, ``g = 0``). Where nobody asks for a gradient
    the whole batch is one launch of ``kda_fwd``; where somebody does, that
    launch gives the result and the gradient is :func:`_kda_chunked`'s, row
    after row (see the module's docstring). Returns float32 ``[B, H, L, dv]``."""
    with jax.named_scope("chunks"):
        return _kda(q, k, v, g, beta, jnp.dtype(dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, dtype):
    return _kda_forward(q, k, v, g, beta, dtype)


def _kda_fwd(q, k, v, g, beta, dtype):
    # The residuals are the five inputs: this rule is a checkpoint by hand.
    return _kda_forward(q, k, v, g, beta, dtype), (q, k, v, g, beta)


def _kda_bwd(dtype, inputs, dO):
    def one_row(x):
        *row, dO_row = x
        _, vjp = jax.vjp(lambda *a: _kda_chunked(*(t[None] for t in a), dtype)[0], *row)
        return vjp(dO_row)

    # Row by row: the backward pass holds one row's intermediates (two dozen
    # arrays of q's size in float32, and the chunks' starting states), not
    # the batch's; and of a row longer than BWD_TOKEN_HEADS allows, one group
    # of heads' (the largest count that divides the heads and fits).
    B, H, L, _ = inputs[0].shape
    heads = max(h for h in range(1, H + 1) if H % h == 0 and (h == 1 or h * L <= BWD_TOKEN_HEADS))
    groups = lambda x: x.reshape(B * (H // heads), heads, *x.shape[2:])  # noqa: E731 (as it is where a row goes whole)
    grads = jax.lax.map(one_row, tuple(groups(x) for x in (*inputs, dO)))
    return tuple(g.reshape(x.shape) for g, x in zip(grads, inputs))


_kda.defvjp(_kda_fwd, _kda_bwd)


def _chunks(q, k, v, g, beta):
    """The operands as chunks: ``[B, H, N, C, .]`` (``beta``: ``[B, H, N, C]``),
    the tail padded with tokens that write nothing."""
    B, H, L, _ = q.shape
    pad = -L % CHUNK
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    return tuple(x.reshape(B, H, (L + pad) // CHUNK, CHUNK, *x.shape[3:]) for x in (q, k, v, g, beta))


def _kda_chunked(q, k, v, g, beta, dtype):
    """The gradient path's forward: pair matrices and substitution in XLA,
    the recurrence over chunks in ``kda_chunks_fwd`` (``kda_chunks_bwd`` is
    its transpose)."""
    chunk = CHUNK
    B, H, L, _ = q.shape
    q, k, v, g, beta = (x.astype(jnp.float32) for x in _chunks(q, k, v, g, beta))
    G = jnp.cumsum(g, axis=3)  # inclusive, from the chunk's start
    pairs = _pair_matrices(jnp.stack([k, q]), k, G, dtype)
    tri = np.tril(np.ones((chunk, chunk), np.float32))
    A = pairs[0] * (tri - np.eye(chunk, dtype=np.float32))
    Bqk = pairs[1] * tri
    b = beta[..., None]
    eG = jnp.exp(G)
    # (I + Diag(b) A) [W | U0] = Diag(b) [K e^G | V]
    rhs = jnp.concatenate([b * k * eG, b * v], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        b * A + jnp.eye(chunk, dtype=jnp.float32), rhs, lower=True, unit_diagonal=True
    )
    G_end = G[..., -1:, :]
    O = _chunk_recurrence(
        sol, (q * eG).astype(dtype), Bqk.astype(dtype), (k * jnp.exp(G_end - G)).astype(dtype), jnp.exp(G_end)
    )
    return O.reshape(B, H, -1, O.shape[-1])[:, :, :L]


# ------------------------------------------------- the recurrence over chunks
# All three kernels work on the TRANSPOSED state ``St = S^T`` (``[dv, dk]``):
# the decay then runs along the lanes and multiplies the state as the
# ``[1, dk]`` block it arrives as. The gradient path's two read ``W`` and
# ``U0`` as XLA has them, the two halves of the substitution's solution
# ``[W | U0]`` (float32; ``W`` is rounded to the products' type where it is
# read), and the reverse kernel writes their gradients in the same form: no
# slice, cast or concatenation of the solution stands beside a kernel.
_NT = (((1,), (1,)), ((), ()))  # x @ y^T
_TN = (((0,), (0,)), ((), ()))  # x^T @ y


def _dot(x, y, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(sol_ref, Q_ref, B_ref, K_ref, decay_ref, O_ref, *rest):
    """One chunk of :data:`HEADS` heads: ``U = U0 - W S``, ``O = Q S + B U``,
    ``S <- decay * S + K^T U``. Where a second result is asked for, the state
    the chunk started from goes out as the reverse kernel's residual."""
    *St0_refs, St_ref = rest  # the optional result, then the scratch

    @pl.when(pl.program_id(2) == 0)
    def _():
        St_ref[...] = jnp.zeros_like(St_ref)

    dtype, dk = Q_ref.dtype, Q_ref.shape[-1]
    for h in range(Q_ref.shape[1]):
        St = St_ref[h]
        for St0_ref in St0_refs:
            St0_ref[0, h, 0] = St
        St_in = St.astype(dtype)
        U = sol_ref[0, h, 0, :, dk:] - _dot(sol_ref[0, h, 0, :, :dk].astype(dtype), St_in, _NT)
        U_in = U.astype(dtype)
        O_ref[0, h, 0] = _dot(Q_ref[0, h, 0], St_in, _NT) + _dot(B_ref[0, h, 0], U_in)
        St_ref[h] = decay_ref[0, h, 0] * St + _dot(U_in, K_ref[0, h, 0], _TN)


def _bwd_kernel(
    sol_ref, Q_ref, B_ref, K_ref, decay_ref, St0_ref, dO_ref,
    dsol_ref, dQ_ref, dB_ref, dK_ref, ddecay_ref, dSt_ref,
):
    """The same chunk, walked from the last to the first: ``dSt_ref`` holds
    the cotangent of the state AFTER the chunk; ``U`` is made again from the
    saved starting state. Every product reads the forward's type, every sum
    is float32."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dSt_ref[...] = jnp.zeros_like(dSt_ref)

    dtype, dk = Q_ref.dtype, Q_ref.shape[-1]
    for h in range(Q_ref.shape[1]):
        W, Q, K = sol_ref[0, h, 0, :, :dk].astype(dtype), Q_ref[0, h, 0], K_ref[0, h, 0]
        St, dSt = St0_ref[0, h, 0], dSt_ref[h]
        St_in, dSt_in, dO_in = St.astype(dtype), dSt.astype(dtype), dO_ref[0, h, 0].astype(dtype)
        U_in = (sol_ref[0, h, 0, :, dk:] - _dot(W, St_in, _NT)).astype(dtype)
        dU = _dot(B_ref[0, h, 0], dO_in, _TN) + _dot(K, dSt_in, _NT)
        dU_in = dU.astype(dtype)
        dsol_ref[0, h, 0, :, :dk] = -_dot(dU_in, St_in)
        dsol_ref[0, h, 0, :, dk:] = dU
        dQ_ref[0, h, 0] = _dot(dO_in, St_in).astype(dtype)
        dB_ref[0, h, 0] = _dot(dO_in, U_in, _NT).astype(dtype)
        dK_ref[0, h, 0] = _dot(U_in, dSt_in).astype(dtype)
        ddecay_ref[0, h, 0] = (dSt * St).sum(0, keepdims=True)
        dSt_ref[h] = decay_ref[0, h, 0] * dSt + _dot(dO_in, Q, _TN) - _dot(dU_in, W, _TN)


def _dot32(x, y):
    """A float32 product at full precision (the cumulative sum, the
    substitution): it never reads the products' type."""
    return jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )


def _whole_kernel(dtype, q_ref, k_ref, v_ref, g_ref, beta_ref, O_ref, St_ref):
    """One chunk of :data:`HEADS` heads from the layer's own operands: the
    running sum ``G``, the pair matrices, the substitution and the three
    lines of ``_fwd_kernel``, all in VMEM; ``O`` is the one thing written.

    The pair matrices, a block of :data:`BLOCK` rows at a time: the rows'
    factor ``x_t e^{G_t - F_i}`` (``F_i`` the block's first ``G``: at most 1)
    against ``k_s e^{F_i - G_s}``, whose exponent is not positive for a column
    of an earlier block and is the diagonal block's clipped one for a column
    of the same; later columns are masked. The system's inverse is built by
    float32 products: the diagonal blocks' ``(I + N)^-1 = (I - N)(I + N^2)(I +
    N^4)(I + N^8)`` (``N^16 = 0``), then merged upwards twice, ``[[P, 0], [Q,
    R]]^-1 = [[P^-1, 0], [-R^-1 Q P^-1, R^-1]]``."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        St_ref[...] = jnp.zeros_like(St_ref)

    C, dk = q_ref.shape[-2:]
    f32 = jnp.float32
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tril = (row >= col).astype(f32)
    eye = row == col
    identity = eye.astype(f32)
    shift = int(math.log2(BLOCK))
    same = [(row >> s) == (col >> s) for s in range(shift, int(math.log2(C)) + 1)]  # blocks of 16, 32, 64
    merges = [(wide & ~narrow).astype(f32) for narrow, wide in zip(same, same[1:])]
    diagonal = same[0].astype(f32)
    # the largest exponent of a column's factor against block i's rows
    token_block = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0) >> shift
    limits = [jnp.where(token_block == i, MAX_BLOCK_DECAY, 0.0) for i in range(C // BLOCK)]

    def head(h):
        # A generator: each ``yield`` ends a stage of dependent products, so
        # that the heads computed abreast can be traced stage by stage.
        q, k, v, b = q_ref[0, h, 0], k_ref[0, h, 0], v_ref[0, h, 0].astype(f32), beta_ref[0, h, 0]  # b: [1, C]
        G = _dot32(tril, g_ref[0, h, 0])  # inclusive, from the chunk's start
        yield
        first = [G[i : i + 1] for i in range(0, C, BLOCK)]
        rows = jnp.exp(G - jnp.concatenate([jnp.broadcast_to(F, (BLOCK, dk)) for F in first], axis=0))
        k_rows, q_rows = (k * rows).astype(dtype), (q * rows).astype(dtype)
        A, Bqk = [], []
        for i, (F, limit) in enumerate(zip(first, limits)):
            cols = (k * jnp.exp(jnp.minimum(F - G, limit))).astype(dtype)
            at = slice(i * BLOCK, (i + 1) * BLOCK)
            A.append(_dot(k_rows[at], cols, _NT))
            Bqk.append(_dot(q_rows[at], cols, _NT))
        A = jnp.where(row > col, jnp.concatenate(A, axis=0), 0.0)
        Bqk = jnp.where(row >= col, jnp.concatenate(Bqk, axis=0), 0.0)
        # (I + Diag(b) A)^-1, from the diagonal blocks upwards
        n = jnp.sum(jnp.where(eye, b, 0.0), axis=1, keepdims=True) * A
        power = diagonal * n
        inv = identity - power
        yield
        for _ in range(shift - 1):
            power = _dot32(power, power)
            yield
            inv = inv + _dot32(inv, power)
        for below in merges:
            yield
            left = _dot32(inv, below * n)
            yield
            inv = inv - _dot32(left, inv)
        yield
        # [W | U0] = (I + Diag(b) A)^-1 Diag(b) [K e^G | V]
        inv = inv * b
        eG = jnp.exp(G)
        W, U0 = _dot32(inv, k * eG), _dot32(inv, v)
        yield
        G_end = G[C - 1 :]
        St = St_ref[h]
        St_in = St.astype(dtype)
        U_in = (U0 - _dot(W.astype(dtype), St_in, _NT)).astype(dtype)
        yield
        O_ref[0, h, 0] = _dot((q * eG).astype(dtype), St_in, _NT) + _dot(Bqk.astype(dtype), U_in)
        St_ref[h] = jnp.exp(G_end) * St + _dot(U_in, (k * jnp.exp(G_end - G)).astype(dtype), _TN)

    heads = q_ref.shape[1]
    for h in range(0, heads, ABREAST):
        abreast = [head(i) for i in range(h, min(h + ABREAST, heads))]
        while abreast:  # each in turn up to its next ``yield``
            abreast = [chain for chain in abreast if next(chain, False) is None]


@functools.partial(jax.jit, static_argnames=("kernel", "name", "results", "state", "reverse", "interpret"))
def _recurrence_call(kernel, name, operands, results, state, reverse, interpret):
    """``pallas_call`` over ``(rows, heads / HEADS, chunks)``, the chunks last
    and in order (backwards where ``reverse``). An operand or result is
    ``[B, H, N, rows, width]`` and is read or written in place, one chunk of
    :data:`HEADS` heads a grid step; ``results`` gives each result's
    ``(rows, width, dtype)``. The transposed state, ``state = (dv, dk)`` a
    head, is the one scratch. Jitted, so that a step's twenty-four launches
    of three kernels are traced and lowered three times, not twenty-four."""
    B, H, N = operands[0].shape[:3]
    heads = math.gcd(H, HEADS)

    def spec(rows, width):
        return pl.BlockSpec((1, heads, 1, rows, width), lambda b, h, c: (b, h, N - 1 - c if reverse else c, 0, 0))

    return pl.pallas_call(
        kernel,
        grid=(B, H // heads, N),
        in_specs=[spec(*x.shape[-2:]) for x in operands],
        out_specs=[spec(rows, width) for rows, width, _ in results],
        out_shape=[jax.ShapeDtypeStruct((B, H, N, rows, width), t) for rows, width, t in results],
        scratch_shapes=[pltpu.VMEM((heads, *state), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*operands)


def _lanes(width):
    """Zeros to a whole number of lanes of 128, on the TPU (a padded channel
    holds a state of 0 and adds 0 to every product)."""
    return -width % 128 if jax.default_backend() == "tpu" else 0


def _wide(x, pad):
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


# One kernel object a products' type: ``_recurrence_call`` is jitted on the
# kernel's identity, and a new ``partial`` a call would lower it a call.
_whole_kernel_reading = functools.cache(lambda dtype: functools.partial(_whole_kernel, dtype))


def _kda_forward(q, k, v, g, beta, dtype):
    """``O`` of the whole batch from one launch of ``kda_fwd``."""
    L, dk, dv = q.shape[2], q.shape[3], v.shape[3]
    pk, pv = _lanes(dk), _lanes(dv)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    with jax.named_scope("fwd"):
        q, k, v, g, beta = _chunks(_wide(f32(q), pk), _wide(f32(k), pk), _wide(v, pv), _wide(f32(g), pk), f32(beta))
        (O,) = _recurrence_call(
            _whole_kernel_reading(dtype), "kda_fwd", (q, k, v, g, beta[:, :, :, None, :]),
            ((CHUNK, dv + pv, jnp.float32),),
            state=(dv + pv, dk + pk), reverse=False, interpret=jax.default_backend() != "tpu",
        )
    return O.reshape(*O.shape[:2], -1, dv + pv)[:, :, :L, :dv]


def _recurrence_fwd(sol, Q, Bqk, K, decay, states=True):
    C, dk = Q.shape[-2:]
    dv = sol.shape[-1] - dk
    O, *St0 = _recurrence_call(
        _fwd_kernel, "kda_chunks_fwd", (sol, Q, Bqk, K, decay),
        ((C, dv, jnp.float32),) + ((dv, dk, jnp.float32),) * states,
        state=(dv, dk), reverse=False, interpret=jax.default_backend() != "tpu",
    )
    return O, (sol, Q, Bqk, K, decay, *St0)


def _recurrence_bwd(res, dO):
    sol, Q = res[:2]
    C, dk = Q.shape[-2:]
    return tuple(_recurrence_call(
        _bwd_kernel, "kda_chunks_bwd", (*res, dO),
        ((C, sol.shape[-1], jnp.float32), (C, dk, Q.dtype), (C, C, Q.dtype), (C, dk, Q.dtype), (1, dk, jnp.float32)),
        state=(sol.shape[-1] - dk, dk), reverse=True, interpret=jax.default_backend() != "tpu",
    ))


@jax.custom_vjp
def _recurrence(sol, Q, Bqk, K, decay):
    return _recurrence_fwd(sol, Q, Bqk, K, decay, states=False)[0]


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def _chunk_recurrence(sol, Q, Bqk, K, decay):
    """``O`` of the three lines above for every chunk in order, the state
    starting at 0. ``sol``: ``[B, H, N, C, dk + dv]`` float32, the
    substitution's ``[W | U0]``; ``Q``, ``K``: ``[B, H, N, C, dk]`` and ``Bqk``:
    ``[B, H, N, C, C]`` in the products' type; ``decay``: ``[B, H, N, 1, dk]``
    float32. On the TPU the head widths are padded with zeros to whole lanes
    of 128 (a padded channel holds a state of 0 and adds 0 to every
    product)."""
    dk = Q.shape[-1]
    dv = sol.shape[-1] - dk
    pk, pv = _lanes(dk), _lanes(dv)
    if not (pk or pv):
        return _recurrence(sol, Q, Bqk, K, decay)
    sol = jnp.concatenate([_wide(sol[..., :dk], pk), _wide(sol[..., dk:], pv)], axis=-1)
    return _recurrence(sol, _wide(Q, pk), Bqk, _wide(K, pk), _wide(decay, pk))[..., :dv]
