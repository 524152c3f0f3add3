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

What runs where. Two Pallas kernels, each ONE launch for the whole batch over
``(rows, heads / HEADS, chunks)`` with the chunks last and in order, the
(transposed) states of :data:`HEADS` heads, or their cotangents, in VMEM scratch
(a state never goes through HBM between two chunks) and the operands read in
place from ``[B, H, N, C, .]``; off the TPU both run in Pallas' interpreter.
Which of them a call of :func:`kda_chunked` runs depends on one thing, which the
code observes through a ``jax.custom_vjp``: whether JAX asks it for a gradient.

* Nobody does (evaluation, any forward pass outside ``jax.grad``): ``kda_fwd``,
  under the scope ``chunks/fwd``.
  It reads ``q``, ``k``, ``v``, the log-decay and ``beta`` and does everything
  above for a chunk in VMEM: the running sum ``G`` (a float32 product with the
  triangle of ones), the pair matrices, the system's inverse ``T`` by float32
  products at full precision (the 16 x 16 diagonal blocks' ``(I + N)^-1 = (I -
  N)(I + N^2)(I + N^4)(I + N^8)``, then merged upwards twice), ``W`` and
  ``U0``, the three lines. It writes ``O`` only: nothing of a chunk's ``A``,
  ``B``, ``W``, ``U0`` or ``e^G`` goes through HBM.
* Somebody does: the ``fwd`` rule is the same launch, which then also writes
  the state each chunk STARTED from (``[dv, dk]`` float32 a chunk and head) and
  ``T`` (``[C, C]``), and keeps them with the five inputs, alive until that
  layer's ``bwd`` rule has run. (Under ``nn.remat`` both of a step's forward
  passes are this launch: JAX takes a checkpointed block's first pass from the
  rule too and drops the residuals after it. A kernel cannot leave a result
  unwritten, so they are written and freed; the launch is bound by its
  products and takes the same time either way.) The
  ``bwd`` rule is one launch of ``kda_bwd``, under the scope ``chunks/bwd``: the
  chunks backwards, the state's cotangent in scratch. For a chunk it builds the
  pair matrices again with the forward's own code (:func:`_chunk_pairs`), makes
  ``U = T R`` again from the saved state and inverse (``R = Diag(b) (V - (K *
  e^G) S)``), and transposes, in VMEM: the three lines; the system, with ONE
  product with the inverse and no chain through its construction (``dR = T^T
  dU``, ``d(Diag(b) A) = -strict_tril(dR U^T)``); the pair products, by the same
  blocks of 16 rows and the same two factors (``x * dx`` of a factor is its
  exponent's cotangent, and where the clip below binds nothing passes through);
  the running sum (a float32 product with the upper triangle of ones). It
  writes the five gradients in place, in the inputs' types. No XLA operation of
  the gradient stands beside the two kernels, and nothing loops over rows or
  heads.

``e^{G_t - G_s}`` cannot be split into ``e^{G_t}`` times ``e^{-G_s}`` over a
whole chunk: a fast head forgets by ``e^{-100}`` in 64 tokens and the second
factor overflows. ``A`` and ``B`` are therefore built from blocks of 16 rows,
and no factor's exponent is positive except a diagonal block's clipped one: a
block of rows' factor ``x_t e^{G_t - F}`` (``F`` the running sum at the block's
first token) against every column's ``k_s e^{F - G_s}``, whose exponent is not
positive for a column of an earlier block and, for a column of the block itself,
is at most the block's own decay and is clipped at :data:`MAX_BLOCK_DECAY`
(never reached while a channel forgets less than ``e^{-80}`` in 16 tokens, a
mean log-decay of 5 a token; the published initialisation gives at most about
2); later columns are masked. One product a block of rows.
"""

from __future__ import annotations

import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Tokens a chunk: an implementation size, not the model's (any multiple of
#: BLOCK gives the recurrence's result; 64 keeps the in-chunk matrices small
#: beside the 128-wide state products).
CHUNK = 64
#: Rows and columns of the blocks the in-chunk matrices are built from.
BLOCK = 16
#: Heads a grid step of the two kernels: an implementation size too (the
#: grid's fixed cost a step is shared by that many independent chains).
HEADS = 8
#: Heads of a grid step that a kernel computes side by side: a head is one
#: chain of dependent products, two chains fill each other's waits (``kda_fwd``:
#: the v5e's compiler schedules 12.3 thousand bundles a grid step for 15.3 one
#: by one; four side by side spill four times the registers for 11.7).
ABREAST = 2
#: Largest exponent of a diagonal block's inverse decay (float32 holds e^88).
MAX_BLOCK_DECAY = 80.0


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


def kda_chunked(q, k, v, g, beta, *, dtype=jnp.float32):
    """The recurrence's result from chunks of :data:`CHUNK` tokens. Shapes as
    :func:`kda_recurrent`; ``g`` and ``beta`` float32. ``dtype`` is the type
    the matrix products read (their sums, the decays, the system's inverse
    and the state are float32). Any length: the tail is padded with tokens
    that write nothing (``beta = 0``, ``g = 0``). The whole batch is one
    launch of ``kda_fwd``, and where somebody asks for a gradient one of
    ``kda_bwd`` beside it (see the module's docstring). Returns float32
    ``[B, H, L, dv]``."""
    with jax.named_scope("chunks"):
        return _kda(q, k, v, g, beta, jnp.dtype(dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, dtype):
    return _kda_forward(q, k, v, g, beta, dtype, residuals=False)[0]


def _kda_fwd(q, k, v, g, beta, dtype):
    # The residuals: the five inputs (a checkpoint by hand), the state each
    # chunk started from and each chunk's inverse.
    O, *saved = _kda_forward(q, k, v, g, beta, dtype, residuals=True)
    return O, (q, k, v, g, beta, *saved)


def _chunks(q, k, v, g, beta):
    """The operands as chunks: ``[B, H, N, C, .]`` (``beta``: ``[B, H, N, C]``),
    the tail padded with tokens that write nothing."""
    B, H, L, _ = q.shape
    pad = -L % CHUNK
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    return tuple(x.reshape(B, H, (L + pad) // CHUNK, CHUNK, *x.shape[3:]) for x in (q, k, v, g, beta))


# ------------------------------------------------------------- the two kernels
# Both work on the TRANSPOSED state ``St = S^T`` (``[dv, dk]``): the decay then
# runs along the lanes and multiplies the state as the ``[1, dk]`` block it
# arrives as.
_NT = (((1,), (1,)), ((), ()))  # x @ y^T
_TN = (((0,), (0,)), ((), ()))  # x^T @ y


def _dot(x, y, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _dot32(x, y, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at full precision (the running sums, the system's
    inverse and the products with it): it never reads the products' type."""
    return jax.lax.dot_general(x, y, dims, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _masks(C, dk):
    """What both kernels need of a chunk's geometry, made once a grid step."""
    f32 = jnp.float32
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    shift = int(math.log2(BLOCK))
    same = [(row >> s) == (col >> s) for s in range(shift, int(math.log2(C)) + 1)]  # blocks of 16, 32, 64
    token = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    return SimpleNamespace(
        row=row, col=col, eye=row == col, token=token, identity=(row == col).astype(f32),
        tril=(row >= col).astype(f32), triu=(row <= col).astype(f32),
        diagonal=same[0].astype(f32), merges=[(wide & ~narrow).astype(f32) for narrow, wide in zip(same, same[1:])],
        # the largest exponent of a column's factor against block i's rows
        limits=[jnp.where(token >> shift == i, MAX_BLOCK_DECAY, 0.0) for i in range(C // BLOCK)],
    )


def _chunk_pairs(dtype, m, q, k, g, b):
    """What both kernels build of one chunk and head from the layer's own
    operands: the running sum ``G`` and the pair matrices ``A`` and ``Bqk``
    with their factors. A generator: each ``yield`` ends a stage of dependent
    products, so that the heads computed abreast can be traced stage by stage;
    what it returns is the chunk.

    The pair matrices, a block of :data:`BLOCK` rows at a time: the rows'
    factor ``x_t e^{G_t - F_i}`` (``F_i`` the block's first ``G``: at most 1)
    against ``k_s e^{F_i - G_s}``, whose exponent is not positive for a column
    of an earlier block and is the diagonal block's clipped one for a column
    of the same; later columns are masked."""
    C, dk = k.shape
    G = _dot32(m.tril, g)  # inclusive, from the chunk's start
    yield
    first = [G[i : i + 1] for i in range(0, C, BLOCK)]
    rows = jnp.exp(G - jnp.concatenate([jnp.broadcast_to(F, (BLOCK, dk)) for F in first], axis=0))
    k_rows, q_rows = (k * rows).astype(dtype), (q * rows).astype(dtype)
    A, Bqk, factors, cols = [], [], [], []
    for i, (F, limit) in enumerate(zip(first, m.limits)):
        factors.append(jnp.exp(jnp.minimum(F - G, limit)))
        cols.append((k * factors[i]).astype(dtype))
        at = slice(i * BLOCK, (i + 1) * BLOCK)
        A.append(_dot(k_rows[at], cols[i], _NT))
        Bqk.append(_dot(q_rows[at], cols[i], _NT))
    return SimpleNamespace(
        G=G, first=first, rows=rows, k_rows=k_rows, q_rows=q_rows, factors=factors, cols=cols,
        A=jnp.where(m.row > m.col, jnp.concatenate(A, axis=0), 0.0),
        Bqk=jnp.where(m.row >= m.col, jnp.concatenate(Bqk, axis=0), 0.0),
        b_col=jnp.sum(jnp.where(m.eye, b, 0.0), axis=1, keepdims=True),  # b arrives along the lanes: [1, C]
    )


def _inverse(m, n):
    """``(I + n)^-1`` of a strictly lower triangular ``n`` by float32 products
    at full precision, from the diagonal blocks upwards: a block's ``(I + N)^-1
    = (I - N)(I + N^2)(I + N^4)(I + N^8)`` (``N^16 = 0``), then merged upwards
    twice, ``[[P, 0], [Q, R]]^-1 = [[P^-1, 0], [-R^-1 Q P^-1, R^-1]]``. A
    generator, as :func:`_chunk_pairs`."""
    power = m.diagonal * n
    inv = m.identity - power
    yield
    for _ in range(int(math.log2(BLOCK)) - 1):
        power = _dot32(power, power)
        yield
        inv = inv + _dot32(inv, power)
    for below in m.merges:
        yield
        left = _dot32(inv, below * n)
        yield
        inv = inv - _dot32(left, inv)
    return inv


def _abreast(head, heads):
    """Every head of a grid step, :data:`ABREAST` at a time: each chain in
    turn up to its next ``yield``."""
    for h in range(0, heads, ABREAST):
        chains = [head(i) for i in range(h, min(h + ABREAST, heads))]
        while chains:
            chains = [chain for chain in chains if next(chain, False) is None]


def _whole_kernel(dtype, q_ref, k_ref, v_ref, g_ref, beta_ref, O_ref, *rest):
    """``kda_fwd``. One chunk of :data:`HEADS` heads from the layer's own
    operands: the pair matrices, the system's inverse ``T``, ``[W | U0]`` and
    the three lines ``U = U0 - W S``, ``O = Q S + B U``, ``S <- decay * S + K^T
    U``, all in VMEM. ``O`` is written, and where two more results are asked
    for (the ``fwd`` rule) the reverse kernel's residuals: the state the chunk
    started from, and ``T``."""
    *residual_refs, St_ref = rest  # the optional results, then the scratch

    @pl.when(pl.program_id(2) == 0)
    def _():
        St_ref[...] = jnp.zeros_like(St_ref)

    C, dk = q_ref.shape[-2:]
    m = _masks(C, dk)

    def head(h):
        q, k, v, b = q_ref[0, h, 0], k_ref[0, h, 0], v_ref[0, h, 0].astype(jnp.float32), beta_ref[0, h, 0]  # b: [1, C]
        s = yield from _chunk_pairs(dtype, m, q, k, g_ref[0, h, 0], b)
        T = yield from _inverse(m, s.b_col * s.A)
        yield
        # [W | U0] = (I + Diag(b) A)^-1 Diag(b) [K e^G | V]
        inv = T * b
        eG = jnp.exp(s.G)
        W, U0 = _dot32(inv, k * eG), _dot32(inv, v)
        yield
        G_end = s.G[C - 1 :]
        St = St_ref[h]
        for ref, residual in zip(residual_refs, (St, T)):
            ref[0, h, 0] = residual
        St_in = St.astype(dtype)
        U_in = (U0 - _dot(W.astype(dtype), St_in, _NT)).astype(dtype)
        yield
        O_ref[0, h, 0] = _dot((q * eG).astype(dtype), St_in, _NT) + _dot(s.Bqk.astype(dtype), U_in)
        St_ref[h] = jnp.exp(G_end) * St + _dot(U_in, (k * jnp.exp(G_end - s.G)).astype(dtype), _TN)

    _abreast(head, q_ref.shape[1])


def _reverse_kernel(
    dtype, q_ref, k_ref, v_ref, g_ref, beta_ref, St0_ref, T_ref, dO_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dSt_ref,
):
    """``kda_bwd``. The same chunk, walked from the last to the first:
    ``dSt_ref`` holds the cotangent of the state AFTER the chunk. The pair
    matrices are built again (:func:`_chunk_pairs`) and ``U = T R`` with ``T``
    the saved inverse and ``R = Diag(b) (V - (K e^G) S)`` from the saved
    starting state; then the three lines' transposes, the system's (``dR = T^T
    dU``: one product with the inverse, ``d(Diag(b) A) = -strict_tril(dR
    U^T)``, no chain through the inverse's construction), the pair products'
    by the same blocks and factors, and the running sum's (a product with the
    upper triangle of ones). Where the diagonal block's clip binds, nothing
    passes through the exponent. Every product reads the forward's type, every
    sum, the running sums and the two products with ``T`` are float32."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dSt_ref[...] = jnp.zeros_like(dSt_ref)

    C, dk = q_ref.shape[-2:]
    m = _masks(C, dk)
    blocks = [slice(i, i + BLOCK) for i in range(0, C, BLOCK)]

    def head(h):
        q, k, v, b = q_ref[0, h, 0], k_ref[0, h, 0], v_ref[0, h, 0].astype(jnp.float32), beta_ref[0, h, 0]  # b: [1, C]
        s = yield from _chunk_pairs(dtype, m, q, k, g_ref[0, h, 0], b)
        yield
        G, b_col, T = s.G, s.b_col, T_ref[0, h, 0]
        G_end = G[C - 1 :]
        eG, tail, decay = jnp.exp(G), jnp.exp(G_end - G), jnp.exp(G_end)
        Q, K, K_end = q * eG, k * eG, k * tail
        Q_in, K_in, K_end_in = Q.astype(dtype), K.astype(dtype), K_end.astype(dtype)
        St, dSt, dO_in = St0_ref[0, h, 0], dSt_ref[h], dO_ref[0, h, 0].astype(dtype)
        St_in, dSt_in = St.astype(dtype), dSt.astype(dtype)
        unwritten = v - _dot(K_in, St_in, _NT)  # V - (K e^G) S
        U_in = _dot32(T, b_col * unwritten).astype(dtype)
        yield
        # O = Q S + Bqk U and S' = decay * S + K_end^T U, transposed
        dU = _dot(s.Bqk.astype(dtype), dO_in, _TN) + _dot(K_end_in, dSt_in, _NT)
        dQ = _dot(dO_in, St_in)
        dBqk = jnp.where(m.row >= m.col, _dot(dO_in, U_in, _NT), 0.0)
        dK_end = _dot(U_in, dSt_in)
        yield
        # (I + Diag(b) A) U = R, transposed
        dR = _dot32(T, dU, _TN)
        bdR = b_col * dR
        bdR_in = bdR.astype(dtype)
        yield
        dM = jnp.where(m.row > m.col, -_dot(dR.astype(dtype), U_in, _NT), 0.0)
        dA = b_col * dM
        dbeta = jnp.sum(dR * unwritten, axis=1, keepdims=True) + jnp.sum(dM * s.A, axis=1, keepdims=True)
        dbeta_ref[0, h, 0] = jnp.sum(jnp.where(m.eye, dbeta, 0.0), axis=0, keepdims=True)  # along the lanes again
        dv_ref[0, h, 0] = bdR.astype(dv_ref.dtype)
        dK = -_dot(bdR_in, St_in)
        dSt_ref[h] = decay * dSt + _dot(dO_in, Q_in, _TN) - _dot(bdR_in, K_in, _TN)
        dG_end = decay * (dSt * St).sum(0, keepdims=True) + (dK_end * K_end).sum(0, keepdims=True)
        yield
        # A and Bqk back through their blocks' two factors; x * dx of a factor is its exponent's cotangent
        dA_in, dBqk_in = dA.astype(dtype), dBqk.astype(dtype)
        dk = dK * eG + dK_end * tail
        dG = dQ * Q + dK * K - dK_end * K_end + jnp.where(m.token == C - 1, dG_end, 0.0)
        dk_rows, dq_rows = [], []
        for i, (at, F, limit, factor, cols) in enumerate(zip(blocks, s.first, m.limits, s.factors, s.cols)):
            dk_rows.append(_dot(dA_in[at], cols))
            dq_rows.append(_dot(dBqk_in[at], cols))
            dcols = _dot(dA_in[at], s.k_rows[at], _TN) + _dot(dBqk_in[at], s.q_rows[at], _TN)
            dk = dk + dcols * factor
            through = jnp.where(F - G <= limit, dcols * k * factor, 0.0)
            dG = dG - through + jnp.where(m.token == i * BLOCK, through.sum(0, keepdims=True), 0.0)
        yield
        dk_rows, dq_rows = jnp.concatenate(dk_rows, axis=0), jnp.concatenate(dq_rows, axis=0)
        through = (dk_rows * k + dq_rows * q) * s.rows
        dG = dG + through
        for i, at in enumerate(blocks):
            dG = dG - jnp.where(m.token == i * BLOCK, through[at].sum(0, keepdims=True), 0.0)
        dq_ref[0, h, 0] = dQ * eG + dq_rows * s.rows
        dk_ref[0, h, 0] = dk + dk_rows * s.rows
        dg_ref[0, h, 0] = _dot32(m.triu, dG)  # the running sum, transposed

    _abreast(head, q_ref.shape[1])


@functools.partial(jax.jit, static_argnames=("kernel", "name", "results", "state", "reverse", "interpret"))
def _recurrence_call(kernel, name, operands, results, state, reverse, interpret):
    """``pallas_call`` over ``(rows, heads / HEADS, chunks)``, the chunks last
    and in order (backwards where ``reverse``). An operand or result is
    ``[B, H, N, rows, width]`` and is read or written in place, one chunk of
    :data:`HEADS` heads a grid step; ``results`` gives each result's
    ``(rows, width, dtype)``. The transposed state (or its cotangent),
    ``state = (dv, dk)`` a head, is the one scratch. Jitted, so that a step's
    launches of a kernel are traced and lowered once."""
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
_reverse_kernel_reading = functools.cache(lambda dtype: functools.partial(_reverse_kernel, dtype))


def _operands(q, k, v, g, beta):
    """The five inputs as the kernels read them: float32 but ``v``, widened to
    whole lanes, in chunks, ``beta`` along the lanes."""
    pk, pv = _lanes(q.shape[3]), _lanes(v.shape[3])
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta = _chunks(_wide(f32(q), pk), _wide(f32(k), pk), _wide(v, pv), _wide(f32(g), pk), f32(beta))
    return q, k, v, g, beta[:, :, :, None, :]


def _kda_forward(q, k, v, g, beta, dtype, residuals):
    """``O`` of the whole batch from one launch of ``kda_fwd``, and where
    ``residuals`` what the reverse kernel reads beside the inputs, as the
    kernels hold it: the state every chunk started from (``[B, H, N, dv, dk]``
    float32, widened) and every chunk's inverse (``[B, H, N, C, C]``)."""
    L, dv = q.shape[2], v.shape[3]
    with jax.named_scope("fwd"):
        operands = _operands(q, k, v, g, beta)
        width, state = operands[2].shape[-1], (operands[2].shape[-1], operands[0].shape[-1])
        O, *saved = _recurrence_call(
            _whole_kernel_reading(dtype), "kda_fwd", operands,
            ((CHUNK, width, jnp.float32),) + ((*state, jnp.float32), (CHUNK, CHUNK, jnp.float32)) * residuals,
            state=state, reverse=False, interpret=jax.default_backend() != "tpu",
        )
    return O.reshape(*O.shape[:2], -1, width)[:, :, :L, :dv], *saved


def _kda_bwd(dtype, residuals, dO):
    """The five gradients of the whole batch from one launch of ``kda_bwd``."""
    *inputs, St0, T = residuals
    L, beta = inputs[0].shape[2], inputs[4]
    with jax.named_scope("bwd"):
        operands = _operands(*inputs)
        dO = _wide(dO.astype(jnp.float32), operands[2].shape[-1] - dO.shape[-1])
        dO = jnp.pad(dO, ((0, 0), (0, 0), (0, -L % CHUNK), (0, 0))).reshape(operands[2].shape)
        grads = _recurrence_call(
            _reverse_kernel_reading(dtype), "kda_bwd", (*operands, St0, T, dO),
            tuple((*x.shape[-2:], x.dtype) for x in operands),
            state=St0.shape[-2:], reverse=True, interpret=jax.default_backend() != "tpu",
        )
        *grads, dbeta = grads
        rows = lambda x: x.reshape(*x.shape[:2], -1, x.shape[-1])[:, :, :L]  # noqa: E731
        grads = [rows(x)[..., : y.shape[3]].astype(y.dtype) for x, y in zip(grads, inputs)]
        return (*grads, dbeta.reshape(*dbeta.shape[:2], -1)[:, :, :L].astype(beta.dtype))


_kda.defvjp(_kda_fwd, _kda_bwd)
