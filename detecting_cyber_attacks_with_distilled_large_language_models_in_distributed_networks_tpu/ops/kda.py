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
``q_t``. The triangular system is solved by substitution (``W`` and ``U0``
below are its two right-hand sides, so the scan over chunks holds matrix
products only), the states pass from chunk to chunk through ``lax.scan``, and
the scan's body is checkpointed so that the backward pass keeps one state a
chunk.

``e^{G_t - G_s}`` cannot be split into ``e^{G_t}`` times ``e^{-G_s}`` over a
whole chunk: a fast head forgets by ``e^{-100}`` in 64 tokens and the second
factor overflows. ``A`` and ``B`` are therefore built from 16 x 16 blocks:
a block below the diagonal from three factors that are all at most 1 (the
rows' decay since their block's first token, the decay between the two
blocks, the columns' decay up to their block's last token), a block on the
diagonal from the rows' factor and the columns' inverse factor, whose
exponent is at most the block's own decay and is clipped at
:data:`MAX_BLOCK_DECAY` (never reached while a channel forgets less than
``e^{-80}`` in 16 tokens, a mean log-decay of 5 a token; the published
initialisation gives at most about 2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Tokens a chunk: an implementation size, not the model's (any multiple of
#: BLOCK gives the recurrence's result; 64 keeps the in-chunk matrices small
#: beside the 128-wide state products).
CHUNK = 64
#: Rows and columns of the blocks the in-chunk matrices are built from.
BLOCK = 16
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
    the matrix products read (their sums, the decays and the state are
    float32). Any length: the tail is padded with tokens that write
    nothing (``beta = 0``, ``g = 0``). The batch's rows are computed one
    after another. Returns float32 ``[B, H, L, dv]``."""
    @jax.checkpoint
    def one_row(x):
        return _kda_chunked(*(a[None] for a in x), dtype)[0]

    # Row by row, each checkpointed: the backward pass holds one row's
    # intermediates (two dozen arrays of q's size in float32), not the batch's.
    with jax.named_scope("chunks"):
        return jax.lax.map(one_row, (q, k, v, g, beta))


def _kda_chunked(q, k, v, g, beta, dtype):
    chunk = CHUNK
    B, H, L, dk = q.shape
    dv = v.shape[-1]
    pad = -L % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    N = (L + pad) // chunk
    split = lambda a: a.reshape(B, H, N, chunk, *a.shape[3:])  # noqa: E731
    q, k, v, beta = (split(x.astype(jnp.float32)) for x in (q, k, v, beta))
    G = jnp.cumsum(split(g.astype(jnp.float32)), axis=3)  # inclusive, from the chunk's start
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
    W, U0 = sol[..., :dk], sol[..., dk:]
    G_end = G[..., -1:, :]
    xs = (
        W.astype(dtype), U0, (q * eG).astype(dtype), Bqk.astype(dtype),
        (k * jnp.exp(G_end - G)).astype(dtype), jnp.exp(G_end[..., 0, :]),
    )
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in xs)

    @jax.checkpoint
    def step(S, x):
        W_c, U0_c, Q_c, B_c, K_c, decay = x
        S_in = S.astype(dtype)
        U = U0_c - jnp.einsum("bhtk,bhkv->bhtv", W_c, S_in, preferred_element_type=jnp.float32)
        U_in = U.astype(dtype)
        O = jnp.einsum("bhtk,bhkv->bhtv", Q_c, S_in, preferred_element_type=jnp.float32)
        O = O + jnp.einsum("bhts,bhsv->bhtv", B_c, U_in, preferred_element_type=jnp.float32)
        S = decay[..., None] * S + jnp.einsum(
            "bhtk,bhtv->bhkv", K_c, U_in, preferred_element_type=jnp.float32
        )
        return S, O

    _, O = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), jnp.float32), xs)
    O = jnp.moveaxis(O, 0, 2).reshape(B, H, L + pad, dv)
    return O[:, :, :L]
