"""Pallas flash attention for the TPU: kernels that hold a whole head in VMEM,
and causal ones that stream a head tile by tile from HBM.

No ``[Lq, Lk]`` score ever reaches HBM. Every kernel walks score tiles, bf16
(the inputs' type) into the MXU with float32 accumulation, the softmax
statistics in float32. The resident pair holds a head's keys and values in
VMEM:

* ``flash_fwd`` - grid over (row, query head, query tile): a query tile
  meets its key tiles one after the other with the running maximum, sum and
  result as the loop's carry (the online softmax), and writes the result and
  the rows' log-sum-exp, the residual of the backward pass.
* ``flash_bwd`` - grid over (row, key head, query head of the group): ONE
  kernel for ``dq``, ``dk`` and ``dv``. A key tile meets its query tiles;
  a score tile is recomputed once from ``(q, k, bias, lse)``, TRANSPOSED
  (``[keys, queries]``: the rows' statistics are then lane-dense ``[1,
  queries]`` vectors and ``dv += p do`` and ``dk += ds q`` are plain products),
  and feeds all three gradients; ``dq``, ``dk`` and ``dv`` of a head
  accumulate in float32 in VMEM. ``delta = rowsum(dO * O)`` comes from XLA.

The streamed kernels (causal only) hold one tile of each operand, and the
float32 statistics and accumulators of one output tile as VMEM scratch; the
grid's last axes walk the tiles and the pipeline copies them in from HBM, so
what a launch asks of VMEM does not grow with the length:

* ``flash_fwd_streamed`` - grid over (row, query head, query tile, key
  tile): the same online softmax, result and log-sum-exp as ``flash_fwd``.
* ``flash_dkv_streamed`` - grid over (row, key head, key tile, query head of
  the group, query tile): ``dk`` and ``dv`` of a key tile summed in VMEM over
  the group's query heads and the query tiles, score tiles transposed as in
  ``flash_bwd``.
* ``flash_dq_streamed`` - grid over (row, query head, query tile, key tile):
  ``dq`` of a query tile summed over its key tiles. A second walk, because a
  ``dq`` streamed out tile by tile cannot be summed in place by the key-tile
  walk of ``dk`` and ``dv``.

Key tiles wholly after a query tile are not computed: their grid steps repeat
the diagonal tile's block index, so no copy is issued, and do nothing. Query
head ``h`` reads key head ``h // group`` through the key block's index map.

Who calls it, and which path runs when (:func:`causal_path`, from the shapes
alone):

* ``ops/causal_attention.py::causal_attention`` without a window, when a tile
  of :data:`CAUSAL_TILES` divides the length (``causal=True``: the diagonal
  tile is masked in registers; ``dk`` / ``dv`` sum over the group in VMEM;
  ``dqk`` may differ from ``dv``): the resident pair where a whole head fits
  :data:`VMEM_BUDGET` (``laguna-window-fit-l8k``'s and
  ``olmohybrid-window-fit-l4k``'s full layers, the Kimi cell's latent
  attention), the streamed kernels where it does not
  (``qwen3next-window-fit-l16k``'s 256-wide gated attention at 16,384
  tokens: 186 MiB for a head). A sliding window stays on that module's XLA
  blocks: in these kernels it measured 16.3 against 26.4 ms a layer alone and
  nothing of a step in the cell, for 0.8 GB more of temporaries (PERF.md
  §6).
* :func:`flash_attention`, the encoder's ``ModelConfig.attention_impl="flash"``
  (no cell runs it): the resident pair over every key tile, attention
  dropout, a gradient for the key bias. Lengths that do not tile take the XLA
  dot path.

Attention dropout: a counter-based hash (murmur-style finalizer) over the
GLOBAL (batch, head, q, k) position and a per-call seed - forward and
backward regenerate identical keep masks from the same coordinates, so
nothing L^2 is ever stored. The hash is plain integer jnp arithmetic, so it
runs identically under the CPU interpreter and the TPU lowering. (The dot
path draws its mask from ``jax.random.bernoulli`` instead, so
flash-with-dropout matches the dot path in distribution, not bitwise.)

Bias: only key-position masks are supported (``[B, 1, 1, Lk]`` additive from
the encoder, as ``ops.attention.make_attention_bias`` produces it).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, dot_product_attention

# The encoder's block sizes, chosen before PR 21 on an installation that no
# longer exists; no cell runs the encoder's path. Shorter sequences clamp to L.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512

# Smallest blocks worth running as a Pallas grid. A whole-length single
# block is always fine (block == array dim); otherwise blocks below the TPU
# sublane/lane tile (8 query rows, 128 key columns) would lower poorly and
# a gcd-degenerate fit (e.g. prime L -> block 1) would build a pathological
# grid — those lengths take the XLA dot path instead (see flash_attention).
MIN_BLOCK_Q = 8
MIN_BLOCK_K = 128

#: Score tiles of the causal path, largest first (query and key tiles are the
#: same size there, so that exactly one tile a query tile meets is the masked
#: diagonal): implementation sizes, measured on the v5e at the window cells'
#: shapes (PERF.md, PR 33).
CAUSAL_TILES = (512, 256, 128)
#: What one launch may ask of the v5e's 128 MiB of VMEM (the backward kernel
#: holds a head's q, k, v, dO and three float32 accumulators there).
VMEM_BUDGET = 96 * 2**20
#: Score tiles of the streamed kernels, largest first (query and key tiles the
#: same size, as in :data:`CAUSAL_TILES`): implementation sizes, measured on
#: the v5e at the Qwen3-Next cell's shape, where a head's forward and gradient
#: took 61.3 ms at 1,024 and 68.0 at 512 (PERF.md §6).
STREAM_TILES = (1024, 512, 256, 128)
#: Buffers the pipeline keeps of each block a streamed kernel walks (the key
#: tiles of the forward and of ``dq``, the query tiles of ``dk`` / ``dv``);
#: Mosaic lowers one or two, not three.
STREAM_BUFFERS = 2
#: What one launch of the streamed kernels may ask of VMEM: those tiles, the
#: float32 accumulators of one tile and the score tile's temporaries, whatever
#: the length.
STREAM_VMEM = 64 * 2**20


def _fit(block: int, length: int) -> int:
    """Largest block <= the requested size that tiles ``length``: short
    sequences clamp to L, and lengths that aren't multiples of the
    default (e.g. 384 vs 512) snap to gcd."""
    if length <= block:
        return length
    return math.gcd(length, block)


def fits_blocks(lq: int, lk: int, block_q: int, block_k: int) -> bool:
    """Whether (lq, lk) tile into viable Pallas blocks for these requests.

    A block exactly as requested, or covering the whole length, is always
    viable (explicit small blocks are the caller's choice — tests use them
    under interpret mode); only a gcd fit that SHRANK the request below the
    TPU tile minimum is degenerate."""

    def ok(length: int, block: int, min_block: int) -> bool:
        fit = _fit(block, length)
        return fit == block or fit == length or fit >= min_block

    return ok(lq, block_q, MIN_BLOCK_Q) and ok(lk, block_k, MIN_BLOCK_K)


def _vmem_bytes(lq: int, lk: int, dqk: int, dv: int, itemsize: int) -> int:
    """What ``flash_bwd`` keeps in VMEM for one head: q, dO, dq and k, v, dk,
    dv in the inputs' type, twice (the pipeline's two buffers), the float32
    accumulators, the key bias as a column (a lane tile wide) and the rows'
    statistics; and as much again as a few score tiles take."""
    width = lambda d: -(-d // 128) * 128  # noqa: E731 - a row of d numbers fills whole lane tiles
    blocks = itemsize * (lq * (2 * width(dqk) + width(dv)) + 2 * lk * (width(dqk) + width(dv)))
    scratch = 4 * (lq * width(dqk) + lk * (width(dqk) + width(dv)))
    return 2 * blocks + scratch + 2 * 4 * 128 * lk + 4 * 4 * 8 * lq + 8 * 4 * 512 * 512


def causal_path(length: int, dqk: int, dv: int, itemsize: int, window: int | None = None) -> tuple[str, int | None]:
    """Which path a causal row of ``length`` tokens takes, read from its shapes
    alone, and its score tile: ``("resident", tile)`` where a tile of
    :data:`CAUSAL_TILES` divides the length and a whole head fits
    :data:`VMEM_BUDGET` (``flash_fwd`` / ``flash_bwd``); ``("streamed",
    tile)`` where a tile divides it and the head does not fit
    (``flash_fwd_streamed``, ``flash_dkv_streamed``, ``flash_dq_streamed``,
    the largest of :data:`STREAM_TILES` that divides the length);
    ``("blocks", None)`` with a window or where no tile divides it (the XLA
    query blocks of ``ops/causal_attention.py``)."""
    tile = next((t for t in CAUSAL_TILES if length % t == 0), None)
    if window is not None or tile is None:
        return "blocks", None
    if _vmem_bytes(length, length, dqk, dv, itemsize) <= VMEM_BUDGET:
        return "resident", tile
    return "streamed", next(t for t in STREAM_TILES if length % t == 0)


def _keep_mask(seed, b, h, q0, k0, shape: tuple[int, int], q_axis: int, rate: float):
    """Deterministic fp32 keep mask for dropout over a score tile of
    ``shape`` whose queries run along ``q_axis``, from a hash of the GLOBAL
    (seed, batch, head, q index, k index) coordinate — the forward and the
    backward kernel regenerate the identical mask from the same coordinates,
    whatever their tile order and orientation.

    ``seed`` is a pair of uint32 words (64 bits total): a single 32-bit
    seed would birthday-collide to an identical whole-call mask after
    ~2^16 distinct dropout_rng draws (steps x layers)."""
    # Everything MUST be uint32 before the mixing ops: a traced int32
    # (program_id, block offsets) would silently promote the whole chain
    # to a signed dtype, turning the >> shifts arithmetic and changing the
    # bits between call sites.
    q0 = jnp.asarray(q0).astype(jnp.uint32)
    k0 = jnp.asarray(k0).astype(jnp.uint32)
    s0 = jnp.asarray(seed[0]).astype(jnp.uint32)
    s1 = jnp.asarray(seed[1]).astype(jnp.uint32)
    qi = q0 + jax.lax.broadcasted_iota(jnp.uint32, shape, q_axis)
    ki = k0 + jax.lax.broadcasted_iota(jnp.uint32, shape, 1 - q_axis)
    x = (qi * jnp.uint32(0x9E3779B1)) ^ (ki * jnp.uint32(0x85EBCA77))
    x = x ^ (
        s0
        + jnp.asarray(b).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
        + jnp.asarray(h).astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)
    )
    # Fold the second seed word in with its own odd multiplier so the two
    # words act as one 64-bit seed rather than xor-cancelling.
    x = x + s1 * jnp.uint32(0x632BE59B)
    # murmur3 finalizer: avalanche the combined coordinate.
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # Threshold in integer space (x uniform over [0, 2^32)): Mosaic has no
    # uint32 -> float cast, and none is needed — keep iff x >= rate * 2^32.
    thresh = jnp.uint32(min(2**32 - 1, int(round(rate * 4294967296.0))))
    return (x >= thresh).astype(jnp.float32)


def _later(shape: tuple[int, int], q_axis: int):
    """The diagonal tile's addend: ``NEG_INF`` where the key lies after the
    query (the tile's queries and keys start at the same position)."""
    q_pos = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return jnp.where(k_pos > q_pos, NEG_INF, 0.0)


def _dot(a, b, contract: tuple[int, int]):
    """``a`` and ``b`` contracted over one dimension each, float32 out."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())), preferred_element_type=jnp.float32
    )


def _tile_rows(i, size: int):
    return pl.ds(pl.multiple_of(i * size, size), size)


def _fwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
    *, scale: float, rate: float, causal: bool,
):
    """One query tile ``[bq, dqk]`` against its key tiles of the head's ``[Lk,
    dqk | dv]``, online softmax (+ dropout). ``bias_ref``: ``[1, key tiles, 1,
    bk]``; ``lse_ref``: ``[1, 1, 1, 1, bq]``.

    Matmul inputs stay in the activation dtype (bf16 on TPU) with fp32 MXU
    accumulation — full MXU rate, and the same numerics as the dot path
    (ops/attention.py feeds bf16 into its einsums the same way). Softmax
    statistics and the accumulator are fp32.
    """
    q = q_ref[...]  # [bq, dqk], activation dtype
    bq, dv = q.shape[0], v_ref.shape[-1]
    nk, bk = bias_ref.shape[1], bias_ref.shape[3]
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seed = (seed_ref[0, 0], seed_ref[0, 1])

    def tile(j, carry, diagonal=False):
        acc, m, l = carry
        k_t, v_t = k_ref[_tile_rows(j, bk), :], v_ref[_tile_rows(j, bk), :]
        s = _dot(q, k_t, (1, 1)) * scale + bias_ref[0, j]  # [bq, bk] fp32
        if diagonal:
            s = s + _later((bq, bk), 0)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # The denominator accumulates the UNdropped p (softmax semantics);
        # dropout applies to the normalized weights, i.e. to p here since
        # the normalization divides at the end.
        l = l * alpha + p.sum(axis=1, keepdims=True)
        if rate:
            p = p * (_keep_mask(seed, b, h, qi * bq, j * bk, (bq, bk), 0, rate) / (1.0 - rate))
        return acc * alpha + _dot(p.astype(v_t.dtype), v_t, (1, 0)), m_new, l

    carry = (
        jnp.zeros((bq, dv), jnp.float32), jnp.full((bq, 1), -jnp.inf, jnp.float32), jnp.zeros((bq, 1), jnp.float32)
    )
    if causal:  # the key tiles before the query tile's own, then the diagonal one
        acc, m, l = tile(qi, jax.lax.fori_loop(0, qi, tile, carry), diagonal=True)
    else:
        acc, m, l = jax.lax.fori_loop(0, nk, tile, carry)
    # -1e9 mask addends keep l > 0 even for fully masked rows (matches the
    # dot-attention path, which softmaxes the same finite scores).
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = jnp.transpose(jnp.broadcast_to(m + jnp.log(l), (bq, 128)))[:1]


def _bwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, lse_ref, delta_ref, do_ref, seed_ref,
    dq_ref, dk_ref, dv_ref, *rest,
    scale: float, rate: float, causal: bool, block_k: int, group: int,
):
    """One query head (``q``, ``dO``, ``dq``: ``[Lq, d]``; ``k``, ``v``, ``dk``,
    ``dv``: ``[Lk, d]``): every key tile against its query tiles, score tiles
    recomputed transposed (``[bk, bq]``) from ``(q, k, bias, lse)``.
    ``bias_ref``: ``[1, Lk, 1]``; ``lse_ref`` / ``delta_ref``: ``[1, 1, query
    tiles, 1, bq]``. ``dk`` / ``dv`` accumulate over the group's query heads
    (the grid's last axis) and are written with the group's last head.
    ``rest``: the head's key-bias gradient rows ``[1, 1, Lk, 1]`` where the
    caller wants them, then the float32 accumulators of ``dq``, ``dk``, ``dv``."""
    *db_ref, dq_acc, dk_acc, dv_acc = rest
    nq, bq = lse_ref.shape[2], lse_ref.shape[4]
    bk, nk = block_k, k_ref.shape[0] // block_k
    b, g = pl.program_id(0), pl.program_id(2)
    h = pl.program_id(1) * group + g
    seed = (seed_ref[0, 0], seed_ref[0, 1])

    @pl.when(g == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    dq_acc[...] = jnp.zeros_like(dq_acc)

    def key_tile(j, _):
        keys = _tile_rows(j, bk)
        k_t, v_t, bias = k_ref[keys, :], v_ref[keys, :], bias_ref[0, keys, :]

        def tile(i, carry, diagonal=False):
            dk_t, dv_t, db_t = carry
            rows = _tile_rows(i, bq)
            q_t, do_t = q_ref[rows, :], do_ref[rows, :]
            s = _dot(k_t, q_t, (1, 1)) * scale + bias  # [bk, bq]
            if diagonal:
                s = s + _later((bk, bq), 1)
            p = jnp.exp(s - lse_ref[0, 0, i])  # normalized weights (softmax columns)
            dp = _dot(v_t, do_t, (1, 1))  # [bk, bq] = v @ dO^T
            y = p  # what multiplied v
            if rate:
                keep = _keep_mask(seed, b, h, i * bq, j * bk, (bk, bq), 1, rate) / (1.0 - rate)
                y, dp = p * keep, dp * keep
            ds = p * (dp - delta_ref[0, 0, i])  # softmax jacobian
            ds_t = (ds * scale).astype(q_t.dtype)
            dq_acc[rows, :] += _dot(ds_t, k_t, (0, 0))  # ds^T @ k -> [bq, dqk]
            if db_ref:
                db_t = db_t + ds.sum(axis=1, keepdims=True)
            return dk_t + _dot(ds_t, q_t, (1, 0)), dv_t + _dot(y.astype(do_t.dtype), do_t, (1, 0)), db_t

        carry = tuple(jnp.zeros((bk, d), jnp.float32) for d in (k_t.shape[1], v_t.shape[1], 1))
        if causal:  # the diagonal tile, then the query tiles after the key tile
            dk_t, dv_t, db_t = jax.lax.fori_loop(j + 1, nq, tile, tile(j, carry, diagonal=True))
        else:
            dk_t, dv_t, db_t = jax.lax.fori_loop(0, nq, tile, carry)
        dk_acc[keys, :] += dk_t
        dv_acc[keys, :] += dv_t
        if db_ref:
            db_ref[0][0, 0, keys, :] = db_t

    jax.lax.fori_loop(0, nk, key_tile, None)
    dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(g == group - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _column(row):
    """A lane-dense ``[1, n]`` row of statistics as a ``[n, 128]`` column block
    (every lane the same number)."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))


def _stream_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, m_acc, l_acc, o_acc, *, scale: float):
    """Query tile ``i`` (``q``, ``o``: ``[t, d]``) meets key tile ``j`` (``k``,
    ``v``: ``[t, d]``; ``bias``: ``[1, t]``), the grid's last axis; the online
    softmax's maximum, sum and result live in VMEM scratch from ``j == 0`` to
    the diagonal tile ``j == i``, which writes the result and the rows'
    log-sum-exp (``lse``: ``[1, t]``). Steps ``j > i`` do nothing."""
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, -jnp.inf)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    def tile(diagonal: bool):
        v = v_ref[...]
        s = _dot(q_ref[...], k_ref[...], (1, 1)) * scale + bias_ref[...]  # [t, t] fp32
        if diagonal:
            s = s + _later(s.shape, 0)
        m = m_acc[...]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_acc[...] = l_acc[...] * alpha + p.sum(axis=1, keepdims=True)
        o_acc[...] = o_acc[...] * alpha + _dot(p.astype(v.dtype), v, (1, 0))
        m_acc[...] = m_new

    pl.when(j < i)(lambda: tile(False))

    @pl.when(j == i)
    def _():
        tile(True)
        l = l_acc[...]
        o_ref[...] = (o_acc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.transpose(jnp.broadcast_to(m_acc[...] + jnp.log(l), (l.shape[0], 128)))[:1]


def _stream_dkv_kernel(
    q_ref, k_ref, v_ref, bias_ref, lse_ref, delta_ref, do_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float
):
    """Key tile ``j`` of a key head (``k``, ``v``, ``dk``, ``dv``: ``[t, d]``;
    ``bias``: ``[t, 1]``) meets query tile ``i`` (``q``, ``dO``: ``[t, d]``;
    ``lse``, ``delta``: ``[1, t]``) of the group's query head ``g``, the
    grid's last two axes; score tiles recomputed transposed (``[keys,
    queries]``), ``dk`` / ``dv`` summed in VMEM scratch over every ``g`` and
    ``i >= j`` and written at the last step. Steps ``i < j`` do nothing."""
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(diagonal: bool):
        q, do = q_ref[...], do_ref[...]
        s = _dot(k_ref[...], q, (1, 1)) * scale + bias_ref[...]  # [t, t] fp32
        if diagonal:
            s = s + _later(s.shape, 1)
        p = jnp.exp(s - lse_ref[...])  # normalized weights (softmax columns)
        dp = _dot(v_ref[...], do, (1, 1))  # v @ dO^T
        ds = p * (dp - delta_ref[...])  # softmax jacobian
        dv_acc[...] += _dot(p.astype(do.dtype), do, (1, 0))
        dk_acc[...] += _dot((ds * scale).astype(q.dtype), q, (1, 0))

    pl.when(i > j)(lambda: tile(False))
    pl.when(i == j)(lambda: tile(True))

    @pl.when((g == pl.num_programs(3) - 1) & (i == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _stream_dq_kernel(
    q_ref, k_ref, v_ref, bias_ref, lse_ref, delta_ref, do_ref, dq_ref, dq_acc, lse_col, delta_col, *, scale: float
):
    """Query tile ``i`` (``q``, ``dO``, ``dq``: ``[t, d]``; ``lse``,
    ``delta``: ``[1, t]``, turned into columns in VMEM at ``j == 0``) meets
    key tile ``j`` (``k``, ``v``: ``[t, d]``; ``bias``: ``[1, t]``), the
    grid's last axis; ``dq`` sums in VMEM scratch up to the diagonal tile,
    which writes it. Steps ``j > i`` do nothing."""
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        lse_col[...] = _column(lse_ref[...])
        delta_col[...] = _column(delta_ref[...])

    def tile(diagonal: bool):
        k = k_ref[...]
        s = _dot(q_ref[...], k, (1, 1)) * scale + bias_ref[...]  # [t, t] fp32
        if diagonal:
            s = s + _later(s.shape, 0)
        p = jnp.exp(s - lse_col[:, :1])
        dp = _dot(do_ref[...], v_ref[...], (1, 1))  # dO @ v^T
        ds = p * (dp - delta_col[:, :1])
        dq_acc[...] += _dot((ds * scale).astype(k.dtype), k, (1, 0))

    pl.when(j < i)(lambda: tile(False))

    @pl.when(j == i)
    def _():
        tile(True)
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


class _How(NamedTuple):
    """What a call of the kernels is, beside its arrays (static). ``keep_name``:
    the ``checkpoint_name`` the result and the rows' log-sum-exp carry as
    residuals, so that a caller's recomputation that keeps that name does not
    launch the forward kernel again to have them. ``streamed``: the causal
    kernels that stream a head tile by tile (no dropout, no key-bias
    gradient)."""

    rate: float
    causal: bool
    block_q: int
    block_k: int
    bias_grad: bool
    keep_name: str | None
    interpret: bool
    streamed: bool = False


def _head_block(rows: int, d: int, where):
    """The block of ``rows`` tokens of one head of ``[B, H, L, d]``, ``[rows,
    d]`` in the kernel; ``where(*grid)`` gives its (row of the batch, head,
    block of tokens)."""
    return pl.BlockSpec((None, None, rows, d), lambda *grid: (*where(*grid), 0))


def _compiler_params(q, k, v):
    need = _vmem_bytes(q.shape[2], k.shape[2], q.shape[3], v.shape[3], q.dtype.itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(max(need, 32 * 2**20), 120 * 2**20),
    )


def _flash_forward(q, k, v, bias, seed, how: _How):
    """``q``: ``[B, H, Lq, dqk]``; ``k``, ``v``: ``[B, Hkv, Lk, dqk | dv]``;
    ``bias``: ``[B, Lk]`` float32. Returns the result ``[B, H, Lq, dv]`` and
    the rows' log-sum-exp ``[B, H, Lq / bq, 1, bq]``."""
    b, h, lq, dqk = q.shape
    hkv, lk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv
    bq, bk = _fit(how.block_q, lq), _fit(how.block_k, lk)
    tile = lambda d: _head_block(bq, d, lambda bi, hi, qi: (bi, hi, qi))  # noqa: E731
    head = lambda d: _head_block(lk, d, lambda bi, hi, qi: (bi, hi // group, 0))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=dqk**-0.5, rate=how.rate, causal=how.causal),
        grid=(b, h, lq // bq),
        in_specs=[
            tile(dqk),
            head(dqk),
            head(dv),
            pl.BlockSpec((1, lk // bk, 1, bk), lambda bi, hi, qi: (bi, 0, 0, 0)),
            pl.BlockSpec((1, 2), lambda bi, hi, qi: (0, 0)),
        ],
        out_specs=[tile(dv), pl.BlockSpec((1, 1, 1, 1, bq), lambda bi, hi, qi: (bi, hi, qi, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq // bq, 1, bq), jnp.float32),
        ],
        compiler_params=_compiler_params(q, k, v),
        name="flash_fwd",
        interpret=how.interpret,
    )(q, k, v, bias.reshape(b, lk // bk, 1, bk), seed)


def _flash_backward(q, k, v, bias, seed, out, lse, do, how: _How):
    b, h, lq, dqk = q.shape
    hkv, lk, dv = k.shape[1], k.shape[2], v.shape[3]
    group = h // hkv
    bk = _fit(how.block_k, lk)
    # delta = rowsum(dO ⊙ O): O(L·D) in XLA; exact with or without dropout.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(lse.shape)

    q_head = lambda d: _head_block(lq, d, lambda bi, ki, gi: (bi, ki * group + gi, 0))  # noqa: E731
    k_head = lambda d: _head_block(lk, d, lambda bi, ki, gi: (bi, ki, 0))  # noqa: E731
    rows = pl.BlockSpec((1, 1, *lse.shape[2:]), lambda bi, ki, gi: (bi, ki * group + gi, 0, 0, 0))
    out_specs = [q_head(dqk), k_head(dqk), k_head(dv)]
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)]
    if how.bias_grad:
        out_specs.append(pl.BlockSpec((1, 1, lk, 1), lambda bi, ki, gi: (bi, ki * group + gi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, lk, 1), jnp.float32))
    dq, dk, dv_, *db = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=dqk**-0.5, rate=how.rate, causal=how.causal, block_k=bk, group=group),
        grid=(b, hkv, group),
        in_specs=[
            q_head(dqk), k_head(dqk), k_head(dv),
            pl.BlockSpec((1, lk, 1), lambda bi, ki, gi: (bi, 0, 0)),
            rows, rows, q_head(dv),
            pl.BlockSpec((1, 2), lambda bi, ki, gi: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in ((lq, dqk), (lk, dqk), (lk, dv))],
        compiler_params=_compiler_params(q, k, v),
        name="flash_bwd",
        interpret=how.interpret,
    )(q, k, v, bias[:, :, None], lse, delta, do, seed)
    # [B, H, Lk, 1] per-head rows -> the key bias's own layout.
    dbias = db[0][..., 0].sum(axis=1) if how.bias_grad else jnp.zeros_like(bias)
    return dq, dk, dv_, dbias


def _walked_block(rows: int, d: int, where):
    """:func:`_head_block` for the block a streamed kernel's last grid axis
    walks, in :data:`STREAM_BUFFERS` buffers."""
    return pl.BlockSpec(
        (None, None, rows, d), lambda *grid: (*where(*grid), 0), pipeline_mode=pl.Buffered(STREAM_BUFFERS)
    )


def _stream_params(semantics: tuple[str, ...]):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=STREAM_VMEM)


def _query_walk(t: int, group: int):
    """The blocks of a grid (row, query head, query tile, key tile) with the
    key tile last: a query tile's rows ``(d)``, the key tiles it walks ``(d)``
    (the diagonal tile's again after it), their key bias ``[1, t]`` and the
    query tile's lane-dense statistics ``[1, t]``."""
    rows = lambda d: _head_block(t, d, lambda bi, hi, qi, kj: (bi, hi, qi))  # noqa: E731
    keys = lambda d: _walked_block(t, d, lambda bi, hi, qi, kj: (bi, hi // group, jnp.minimum(qi, kj)))  # noqa: E731
    bias = pl.BlockSpec((None, 1, t), lambda bi, hi, qi, kj: (bi, 0, jnp.minimum(qi, kj)))
    stats = pl.BlockSpec((None, None, None, 1, t), lambda bi, hi, qi, kj: (bi, hi, qi, 0, 0))
    return rows, keys, bias, stats


def _stream_forward(q, k, v, bias, seed, how: _How):
    """:func:`_flash_forward`'s result and log-sum-exp, causal, a head's keys
    and values streamed a tile at a time: grid (row, query head, query tile,
    key tile); a key tile after the query tile repeats the diagonal tile's
    block index, so no copy is issued for it."""
    del seed
    b, h, lq, dqk = q.shape
    group, dv, t = h // k.shape[1], v.shape[3], how.block_q
    rows, keys, key_bias, stats = _query_walk(t, group)
    return pl.pallas_call(
        functools.partial(_stream_fwd_kernel, scale=dqk**-0.5),
        grid=(b, h, lq // t, lq // t),
        in_specs=[rows(dqk), keys(dqk), keys(dv), key_bias],
        out_specs=[rows(dv), stats],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq // t, 1, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, 1), jnp.float32), pltpu.VMEM((t, dv), jnp.float32)],
        compiler_params=_stream_params(("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_fwd_streamed",
        interpret=how.interpret,
    )(q, k, v, bias[:, None, :])


def _stream_backward(q, k, v, bias, seed, out, lse, do, how: _How):
    """:func:`_flash_backward` for the streamed path, in two kernels: ``dk`` and
    ``dv`` over (row, key head, key tile, the group's query head, query tile),
    ``dq`` over (row, query head, query tile, key tile); tiles before the
    diagonal (after it, for ``dq``) repeat its block index and do nothing."""
    del seed
    b, h, lq, dqk = q.shape
    hkv, dv = k.shape[1], v.shape[3]
    group, t, n = h // hkv, how.block_q, lq // how.block_q
    scale = dqk**-0.5
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1).reshape(lse.shape)

    # dk, dv: key tile kj of key head ki against query tile qi of query head ki * group + gi
    q_tile = lambda d: _walked_block(t, d, lambda bi, ki, kj, gi, qi: (bi, ki * group + gi, jnp.maximum(qi, kj)))  # noqa: E731
    k_tile = lambda d: _head_block(t, d, lambda bi, ki, kj, gi, qi: (bi, ki, kj))  # noqa: E731
    q_rows = pl.BlockSpec(
        (None, None, None, 1, t), lambda bi, ki, kj, gi, qi: (bi, ki * group + gi, jnp.maximum(qi, kj), 0, 0)
    )
    dk, dv_ = pl.pallas_call(
        functools.partial(_stream_dkv_kernel, scale=scale),
        grid=(b, hkv, n, group, n),
        in_specs=[
            q_tile(dqk), k_tile(dqk), k_tile(dv),
            pl.BlockSpec((None, t, 1), lambda bi, ki, kj, gi, qi: (bi, kj, 0)),
            q_rows, q_rows, q_tile(dv),
        ],
        out_specs=[k_tile(dqk), k_tile(dv)],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (k, v)],
        scratch_shapes=[pltpu.VMEM((t, dqk), jnp.float32), pltpu.VMEM((t, dv), jnp.float32)],
        compiler_params=_stream_params(("parallel", "parallel", "parallel", "arbitrary", "arbitrary")),
        name="flash_dkv_streamed",
        interpret=how.interpret,
    )(q, k, v, bias[:, :, None], lse, delta, do)

    # dq: query tile qi of query head hi against key tile kj of key head hi // group
    rows, keys, key_bias, stats = _query_walk(t, group)
    dq = pl.pallas_call(
        functools.partial(_stream_dq_kernel, scale=scale),
        grid=(b, h, n, n),
        in_specs=[rows(dqk), keys(dqk), keys(dv), key_bias, stats, stats, rows(dv)],
        out_specs=rows(dqk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((t, dqk), jnp.float32), pltpu.VMEM((t, 128), jnp.float32), pltpu.VMEM((t, 128), jnp.float32)],
        compiler_params=_stream_params(("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_dq_streamed",
        interpret=how.interpret,
    )(q, k, v, bias[:, None, :], lse, delta, do)
    return dq, dk, dv_, jnp.zeros_like(bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash(q, k, v, bias, seed, how: _How):
    return _flash_fwd(q, k, v, bias, seed, how._replace(keep_name=None))[0]


def _flash_fwd(q, k, v, bias, seed, how):
    out, lse = (_stream_forward if how.streamed else _flash_forward)(q, k, v, bias, seed, how)
    if how.keep_name is not None:
        out, lse = checkpoint_name(out, how.keep_name), checkpoint_name(lse, how.keep_name)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd(how, res, do):
    backward = _stream_backward if how.streamed else _flash_backward
    return *backward(*res, do, how), np.zeros(res[4].shape, dtype=jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


def causal_flash_attention(q, k, v, bias, tile: int, keep_name: str | None = None, streamed: bool = False):
    """``ops/causal_attention.py::causal_attention``'s no-window path for a
    length that :func:`causal_path` gave ``tile`` for (``streamed`` where it
    said so); its shapes and result, the key mask as its additive ``bias``
    ``[B, L]``. Interpreted off the TPU, so the CPU tests run the kernels."""
    how = _How(0.0, True, tile, tile, False, keep_name, jax.default_backend() != "tpu", streamed)
    return _flash(q, k, v, bias, jnp.zeros((1, 2), jnp.uint32), how)


def flash_attention(
    q: jnp.ndarray,  # [B, H, Lq, D]
    k: jnp.ndarray,  # [B, H, Lk, D]
    v: jnp.ndarray,  # [B, H, Lk, D]
    bias: jnp.ndarray | None = None,  # [B, 1, 1, Lk] additive key mask
    *,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    deterministic: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blocked flash attention; drop-in for ``dot_product_attention``,
    including attention dropout (hash-based masks — same distribution as
    the dot path, different bits). ``interpret=None`` auto-selects
    interpreter mode off TPU so the same tests run on the CPU mesh.

    Lengths whose gcd with the requested blocks is degenerate (prime or odd
    L — block 1 would mean an Lq-step grid) fall back to the XLA dot path,
    which is faster than a shredded Pallas grid at any such length; so do
    heads too long for :data:`VMEM_BUDGET`."""
    rate = 0.0
    if dropout_rate > 0.0 and not deterministic:
        if dropout_rng is None:
            raise ValueError("flash attention dropout needs dropout_rng")
        rate = float(dropout_rate)
    if bias is not None and (bias.ndim != 4 or bias.shape[1] != 1 or bias.shape[2] != 1):
        raise ValueError(f"flash_attention supports key-position bias [B,1,1,Lk] only, got {bias.shape}")
    lq, lk = q.shape[2], k.shape[2]
    fits_vmem = _vmem_bytes(lq, lk, q.shape[3], v.shape[3], q.dtype.itemsize) <= VMEM_BUDGET
    if not (fits_blocks(lq, lk, block_q, block_k) and fits_vmem):
        return dot_product_attention(
            q, k, v, bias,
            dropout_rate=dropout_rate,
            dropout_rng=dropout_rng,
            deterministic=deterministic,
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if rate:
        seed = jax.random.bits(dropout_rng, (1, 2), jnp.uint32)
    else:
        seed = jnp.zeros((1, 2), jnp.uint32)
    key_bias = jnp.zeros((q.shape[0], lk), jnp.float32) if bias is None else bias[:, 0, 0, :].astype(jnp.float32)
    return _flash(q, k, v, key_bias, seed, _How(rate, False, block_q, block_k, bias is not None, None, interpret))
