"""Causal softmax attention in query blocks, for heads whose query/key width
differs from their value width (latent attention: 192 against 128).

``ops/flash_attention.py`` takes a key-position bias only and one head width,
and the dot path would hold ``[B, H, L, L]`` scores (4.3 GB in bf16 at
``[4, 32, 4096, 4096]``). Here the queries go in blocks of :data:`BLOCK` rows,
so the largest array is one block's ``[B, H, BLOCK, keys]`` scores; each block
is checkpointed, so the backward pass recomputes a block's scores instead of
keeping every block's. :data:`GROUP` consecutive blocks run as one
``lax.map`` and meet the keys up to their group's end: a row of 4,096 tokens
is four loops over four key lengths (1.18 times the scores a block-exact
causal cut would compute, against 2 times for one loop over all keys), where
one Python iteration a block made sixteen copies of the code (16.1 MB of the
step's executable against 5.4 MB, compiles for the v5e, PR 28). Plain XLA:
scores and softmax in float32, products in the inputs' type.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import NEG_INF

#: Query rows a block: an implementation size (the scores of a block of a
#: 4,096-token row at batch 4 and 32 heads are 0.5 GB in float32).
BLOCK = 256
#: Blocks a ``lax.map``: they share one key length, their group's end.
GROUP = 4


def causal_attention(q, k, v, key_mask):
    """``softmax(q k^T / sqrt(dqk)) v`` over the real keys at or before each
    query. ``q``, ``k``: ``[B, H, L, dqk]``; ``v``: ``[B, H, L, dv]``;
    ``key_mask``: ``[B, L]``, 1 on real tokens. Returns ``[B, H, L, dv]`` in
    ``q``'s type. Any ``L``: the last group is the shorter one, and its last
    block is padded with query rows that are cut off again."""
    B, H, L, _ = q.shape
    scale = q.shape[-1] ** -0.5
    pad_bias = (1.0 - key_mask.astype(jnp.float32)) * NEG_INF  # [B, L]

    @jax.checkpoint
    def one_block(q_blk, start, k_seen, v_seen, bias):
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q_blk, k_seen, preferred_element_type=jnp.float32
        ) * scale
        q_pos = start + jnp.arange(q_blk.shape[2])[:, None]
        later = jnp.arange(k_seen.shape[2])[None, :] > q_pos
        scores = scores + bias[:, None, None, :] + jnp.where(later, NEG_INF, 0.0)
        weights = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", weights, v_seen)

    out = []
    for first in range(0, L, BLOCK * GROUP):
        end = min(first + BLOCK * GROUP, L)
        block = min(BLOCK, end - first)
        n = -(-(end - first) // block)
        q_grp = jnp.pad(q[:, :, first:end], ((0, 0), (0, 0), (0, n * block - (end - first)), (0, 0)))
        q_grp = jnp.moveaxis(q_grp.reshape(B, H, n, block, -1), 2, 0)
        k_seen, v_seen, bias = k[:, :, :end], v[:, :, :end], pad_bias[:, :end]
        o = jax.lax.map(
            lambda x: one_block(x[0], x[1], k_seen, v_seen, bias),  # noqa: B023 (used in this iteration)
            (q_grp, first + block * jnp.arange(n)),
        )  # [n, B, H, block, dv]
        out.append(jnp.moveaxis(o, 0, 2).reshape(B, H, n * block, -1)[:, :, : end - first])
    return jnp.concatenate(out, axis=2)
