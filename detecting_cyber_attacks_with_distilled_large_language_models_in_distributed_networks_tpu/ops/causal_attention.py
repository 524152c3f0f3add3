"""Causal softmax attention: for heads whose query/key width differs from
their value width (latent attention: 192 against 128), for query heads that
share a key/value head in groups, and for a sliding window.

Which path runs is decided by the shapes, by nothing a caller sets
(``ops/flash_attention.py::causal_path``):

* **no window, a length that tiles, a head that fits** (a multiple of 128
  whose whole head fits the resident kernels' VMEM: 128-wide heads at 8,192
  tokens and at 4,096, 192 / 128-wide ones at 4,096, the Laguna, Olmo-Hybrid
  and Kimi cells' shapes): the Pallas kernels ``flash_fwd`` / ``flash_bwd``
  (``ops/flash_attention.py``), under the named scope :data:`KERNEL_SCOPE`. A
  query tile's scores, softmax and product with the values stay in VMEM, key
  tile by key tile with the running maximum and sum; key tiles after a query
  tile are not visited; the backward pass recomputes score tiles from ``q``,
  ``k``, the key mask and the rows' log-sum-exp. The result and the
  log-sum-exp carry :data:`ATTENTION_RESULT` as their ``checkpoint_name``.
* **no window, a length that tiles, a head too wide or a row too long for
  that VMEM** (a 256-wide head from 8,192 tokens on: the Qwen3-Next cell's
  gated attention at 16,384): the same algorithm in the streamed kernels
  ``flash_fwd_streamed``, ``flash_dkv_streamed`` and ``flash_dq_streamed``,
  which copy a head's tiles in from HBM one at a time, under the same scope
  and with the same residuals and their names.
* **a window, or any other length** (the tiny presets of the CPU tests): the
  XLA query blocks below. (A window of 512 in the kernels measured nothing of
  the Laguna cell's step for 0.8 GB more of temporaries: PERF.md, PR 33.)

The XLA blocks: the dot path would hold ``[B, H, L, L]`` scores (4.3 GB in
bf16 at ``[4, 32, 4096, 4096]``). Here the queries go in blocks of
:data:`BLOCK` rows, so the largest array is one block's ``[B, H, BLOCK,
keys]`` scores; each block is checkpointed, so the backward pass recomputes a
block's scores instead of keeping every block's. Plain XLA: scores and softmax
in float32, products in the inputs' type.

Which keys a block meets is cut in the program, not only masked:

* without a window, :data:`GROUP` consecutive blocks run as one ``lax.map``
  and meet the keys up to their group's end: a row of 4,096 tokens is four
  loops over four key lengths (1.18 times the scores a block-exact causal cut
  would compute, 1.125 at 8,192 tokens, against 2 times for one loop over all
  keys), where one Python iteration a block made sixteen copies of the code
  (16.1 MB of the step's executable against 5.4 MB, compiles for the v5e,
  PR 28);
* with ``window`` w, a block meets the ``BLOCK`` keys of its own rows and the
  ``w - 1`` before them, rounded up to whole blocks (768 keys for a window of
  512), whatever the row's length: every block has the same shapes, so all of
  them run as ONE ``lax.map``.

Grouped heads: ``k`` and ``v`` may have fewer heads than ``q``; query head
``h`` reads key head ``h // (H / Hkv)``. The XLA blocks fold the group's query
heads into the block's rows (``[B, Hkv, G * BLOCK, d]`` against ``[B, Hkv,
keys, d]``), the kernels divide the head index in the key block's index map;
neither repeats a key in memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import NEG_INF
from .flash_attention import causal_flash_attention, causal_path

#: Query rows a block: an implementation size (the scores of a block of a
#: 4,096-token row at batch 4 and 32 heads are 0.5 GB in float32).
BLOCK = 256
#: Blocks a ``lax.map``: they share one key length, their group's end.
GROUP = 4
#: The name of an attention's result (``checkpoint_name``): the kernel path
#: gives it to its result and its rows' log-sum-exp itself, a caller may give
#: it to the blocks' result. A stack that recomputes each layer in the backward
#: pass and keeps the values under this name (``models/blocks.py::decoder``)
#: does not run a layer's attention a second time only to have them again: the
#: blocks' own checkpoints need the layer's q, k and v, not their result, and
#: the backward kernel needs the result and the log-sum-exp.
ATTENTION_RESULT = "attention_result"
#: The ``jax.named_scope`` the Pallas kernels run under, whoever calls: the
#: benchmark's ``attn_kernel_share`` reads the device time under it.
KERNEL_SCOPE = "causal_flash"


def causal_attention(q, k, v, key_mask, window: int | None = None):
    """``softmax(q k^T / sqrt(dqk)) v`` over the real keys at or before each
    query and, with ``window``, fewer than ``window`` positions before it.
    ``q``: ``[B, H, L, dqk]``; ``k``: ``[B, Hkv, L, dqk]``; ``v``: ``[B, Hkv,
    L, dv]`` (``Hkv`` divides ``H``); ``key_mask``: ``[B, L]``, 1 on real
    tokens. Returns ``[B, H, L, dv]`` in ``q``'s type. Any ``L``: one that
    tiles takes the kernels (no window); of the blocks the last group is the
    shorter one, and its last block is padded with query rows that are cut off
    again."""
    B, H, L, _ = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    pad_bias = (1.0 - key_mask.astype(jnp.float32)) * NEG_INF  # [B, L]
    path, tile = causal_path(L, q.shape[3], v.shape[3], q.dtype.itemsize, window)
    if path != "blocks":
        with jax.named_scope(KERNEL_SCOPE):
            return causal_flash_attention(q, k, v, pad_bias, tile, ATTENTION_RESULT, path == "streamed")
    scale = q.shape[-1] ** -0.5
    # A windowed row's block, and the keys it meets before its own rows', in whole blocks.
    block_w = min(BLOCK, L)
    before = None if window is None else -(-(window - 1) // block_w) * block_w

    @jax.checkpoint
    def one_block(q_blk, start, k_seen, v_seen, bias):
        if before is not None:
            # The block's keys lie at [start, start + before + rows) of arrays
            # padded by ``before`` in front. Cut here, inside the checkpoint, so
            # that the backward pass keeps a block's start and not its keys.
            keys = before + q_blk.shape[2] // G
            k_seen, v_seen = (jax.lax.dynamic_slice_in_dim(a, start, keys, 2) for a in (k_seen, v_seen))
            bias = jax.lax.dynamic_slice_in_dim(bias, start, keys, 1)
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q_blk, k_seen, preferred_element_type=jnp.float32
        ) * scale
        rows = jnp.arange(q_blk.shape[2])
        if G > 1:  # a group's heads one after the other, each over the block's rows
            rows = rows % (q_blk.shape[2] // G)
        q_pos = start + rows[:, None]
        k_pos = jnp.arange(k_seen.shape[2])[None, :]
        if before is None:
            later = k_pos > q_pos
        else:
            k_pos = start - before + k_pos
            later = (k_pos > q_pos) | (q_pos - k_pos >= window)
        scores = scores + bias[:, None, None, :] + jnp.where(later, NEG_INF, 0.0)
        if G == 1 and window is None:  # the latent attention's call, as it was measured
            weights = jax.nn.softmax(scores, axis=-1).astype(q_blk.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", weights, v_seen)
        # The same softmax, kept from a rewrite that costs keys^2: where a row's
        # maximum is broadcast straight back over its keys, the TPU compiler
        # fuses the two into a ``reduce-window`` as wide as the row (2 * keys -
        # 1 comparisons a score: 24.5 ms for a block of 8,192 keys that 1 ms
        # reads; my chip runs, PR 32). The barrier makes the maximum an array of
        # its own. The weights are normalised after their product with the
        # values (one pass over a block's scores fewer; the sum is of the
        # float32 exponentials).
        top = jax.lax.optimization_barrier(jax.lax.stop_gradient(scores.max(-1, keepdims=True)))
        weights = jnp.exp(scores - top)
        total = weights.sum(-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(q_blk.dtype), v_seen, preferred_element_type=jnp.float32)
        return (o / total).astype(q_blk.dtype)

    def blocks(q_part, block):
        """``[B, H, n * block, d]`` -> ``[n, B, Hkv, G * block, d]``."""
        n = q_part.shape[2] // block
        if G == 1:
            return jnp.moveaxis(q_part.reshape(B, H, n, block, -1), 2, 0)
        return jnp.moveaxis(q_part.reshape(B, Hkv, G, n, block, -1), 3, 0).reshape(n, B, Hkv, G * block, -1)

    def rows_of(o, block):
        """``blocks``'s inverse on a result ``[n, B, Hkv, G * block, dv]``."""
        n = o.shape[0]
        if G == 1:
            return jnp.moveaxis(o, 0, 2).reshape(B, H, n * block, -1)
        return jnp.moveaxis(o.reshape(n, B, Hkv, G, block, -1), 0, 3).reshape(B, H, n * block, -1)

    if window is not None:
        n = -(-L // block_w)
        tail = n * block_w - L
        span = ((0, 0), (0, 0), (before, tail), (0, 0))
        k_pad, v_pad = jnp.pad(k, span), jnp.pad(v, span)
        bias_pad = jnp.pad(pad_bias, (span[0], span[2]), constant_values=NEG_INF)
        q_blk = blocks(jnp.pad(q, ((0, 0), (0, 0), (0, tail), (0, 0))), block_w)
        o = jax.lax.map(
            lambda x: one_block(x[0], x[1], k_pad, v_pad, bias_pad), (q_blk, block_w * jnp.arange(n))
        )
        return rows_of(o, block_w)[:, :, :L]

    out = []
    for first in range(0, L, BLOCK * GROUP):
        end = min(first + BLOCK * GROUP, L)
        block = min(BLOCK, end - first)
        n = -(-(end - first) // block)
        q_grp = jnp.pad(q[:, :, first:end], ((0, 0), (0, 0), (0, n * block - (end - first)), (0, 0)))
        q_grp = blocks(q_grp, block)
        k_seen, v_seen, bias = k[:, :, :end], v[:, :, :end], pad_bias[:, :end]
        o = jax.lax.map(
            lambda x: one_block(x[0], x[1], k_seen, v_seen, bias),  # noqa: B023 (used in this iteration)
            (q_grp, first + block * jnp.arange(n)),
        )  # [n, B, Hkv, G * block, dv]
        out.append(rows_of(o, block)[:, :, : end - first])
    return jnp.concatenate(out, axis=2)
