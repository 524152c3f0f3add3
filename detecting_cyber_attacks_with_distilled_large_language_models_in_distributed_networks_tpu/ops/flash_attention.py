"""Pallas blocked flash attention for TPU — forward AND backward kernels.

Forward: the [Lq, Lk] score matrix is never materialized in HBM — each grid
step streams one query block against key/value blocks held in VMEM,
maintaining the online-softmax running max/denominator (the standard flash
recurrence), with fp32 accumulation feeding the MXU. Memory is O(L·D) per
(batch, head) instead of O(L²). The kernel also emits the per-row
logsumexp, the residual the backward needs.

Backward: two Pallas kernels (the Dao et al. split) recompute score tiles
on the fly from (q, k, bias, lse) — O(L²) values exist only transiently in
VMEM tiles, never in HBM:

* dK/dV kernel — grid over key blocks; each instance streams query blocks,
  accumulating ``dv += pᵀ·dO`` and ``dk += dsᵀ·q`` (plus the key-bias
  gradient rows);
* dQ kernel — grid over query blocks; each instance streams key blocks,
  accumulating ``dq += ds·k``.

The softmax-jacobian correction uses ``delta = rowsum(dO ⊙ O)`` (computed
in XLA — O(L·D)), which is exact with or without dropout since the output
is always ``weights @ v``.

Attention dropout: supported in both directions via a counter-based hash
(murmur-style finalizer) over the GLOBAL (batch, head, q, k) position and
a per-call seed — forward and backward regenerate identical keep masks
from the same coordinates, so nothing L² is ever stored. The hash is plain
integer jnp arithmetic, so it runs identically under the CPU interpreter
and the TPU lowering. (The dot path draws its mask from
``jax.random.bernoulli`` instead, so flash-with-dropout matches the dot
path in distribution, not bitwise.)

The reference has no analogue — its attention is whatever torch runs inside
HF ``DistilBertModel`` (reference client1.py:61). At the reference's L=128
XLA's fused dot attention is already fine; this kernel is the long-context
headroom path (``ModelConfig.attention_impl="flash"``) and the building
block the ring-attention sequence-parallel path composes with.

Bias: only key-position masks — shape ``[B, 1, 1, Lk]`` additive, as produced
by ``ops.attention.make_attention_bias`` — are supported.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Block sizes chosen before PR 21 on an installation that no longer exists
# (B=8, H=12, D=64, L=2048): bigger K blocks amortize the per-matmul MXU
# ramp. No cell runs this kernel, so the ledger has no figure for it. Shorter
# sequences clamp to L automatically.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512

# Smallest blocks worth running as a Pallas grid. A whole-length single
# block is always fine (block == array dim); otherwise blocks below the TPU
# sublane/lane tile (8 query rows, 128 key columns) would lower poorly and
# a gcd-degenerate fit (e.g. prime L -> block 1) would build a pathological
# grid — those lengths take the XLA dot path instead (see flash_attention).
MIN_BLOCK_Q = 8
MIN_BLOCK_K = 128


def _fit(block: int, length: int) -> int:
    """Largest block <= the requested size that tiles ``length``: short
    sequences clamp to L, and lengths that aren't multiples of the
    default (e.g. 384 vs 512) snap to gcd."""
    if length <= block:
        return length
    import math

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


def _keep_mask(seed, b, h, q0, k0, bq: int, bk: int, rate: float):
    """Deterministic [bq, bk] fp32 keep mask for dropout, from a hash of
    the GLOBAL (seed, batch, head, q index, k index) coordinate — the
    forward and both backward kernels regenerate the identical mask from
    the same coordinates, whatever their block iteration order.

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
    qi = q0 + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
    ki = k0 + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
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


def _fwd_kernel(
    q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref,
    *, scale: float, block_k: int, rate: float,
):
    """One query block vs. all key blocks, online softmax (+ dropout).

    Matmul inputs stay in the activation dtype (bf16 on TPU) with fp32 MXU
    accumulation — full MXU rate, and the same numerics as the dot path
    (ops/attention.py feeds bf16 into its einsums the same way). Softmax
    statistics and the accumulator are fp32.
    """
    q = q_ref[0, 0]  # [bq, D], activation dtype
    bq = q.shape[0]
    d = v_ref.shape[-1]
    lk = k_ref.shape[2]
    num_kb = lk // block_k
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seed = (seed_ref[0, 0], seed_ref[0, 1])
    inv = 1.0 / (1.0 - rate) if rate else 1.0

    def body(i, carry):
        acc, m, l = carry
        k_blk = k_ref[0, 0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(i * block_k, block_k), :]
        b_blk = bias_ref[0, 0, pl.ds(i * block_k, block_k)].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
            + b_blk[None, :]
        )  # [bq, bk] fp32
        m_new = jnp.maximum(m, s.max(axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        # The denominator accumulates the UNdropped p (softmax semantics);
        # dropout applies to the normalized weights, i.e. to p here since
        # the normalization divides at the end.
        l_new = l * alpha + p.sum(axis=1)
        if rate:
            keep = _keep_mask(
                seed, b, h, qi * bq, i * block_k, bq, block_k, rate
            )
            p = p * keep * inv
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_kb, body, (acc0, m0, l0))
    # -1e9 mask addends keep l > 0 even for fully masked rows (matches the
    # dot-attention path, which softmaxes the same finite scores).
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :, 0] = m + jnp.log(l)


def _dkdv_kernel(
    q_ref, k_ref, v_ref, bias_ref, lse_ref, delta_ref, do_ref, seed_ref,
    dk_ref, dv_ref, db_ref,
    *, scale: float, block_q: int, rate: float,
):
    """One key block vs. all query blocks: accumulate dk, dv, and this
    head's key-bias gradient rows. Score tiles are recomputed from
    (q, k, bias, lse) — fp32 throughout (the XLA recompute backward this
    replaces also ran fp32; grads match the dot path's numerics)."""
    k_blk = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    bias_blk = bias_ref[0, 0].astype(jnp.float32)  # [bk]
    bk, d = k_blk.shape
    lq = q_ref.shape[2]
    num_qb = lq // block_q
    b, h, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seed = (seed_ref[0, 0], seed_ref[0, 1])
    inv = 1.0 / (1.0 - rate) if rate else 1.0

    def body(i, carry):
        dk_acc, dv_acc, db_acc = carry
        qb = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), 0]  # [bq]
        dlt = delta_ref[0, 0, pl.ds(i * block_q, block_q), 0]
        s = (
            jax.lax.dot_general(
                qb, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
            + bias_blk[None, :]
        )  # [bq, bk]
        p = jnp.exp(s - lse[:, None])  # normalized weights (softmax rows)
        dpn = jax.lax.dot_general(
            dob, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] = dO @ vᵀ
        if rate:
            keep = _keep_mask(
                seed, b, h, i * block_q, ki * bk, block_q, bk, rate
            )
            y = p * keep * inv  # dropped weights (what multiplied v)
            dpn = dpn * keep * inv
        else:
            y = p
        ds = p * (dpn - dlt[:, None])  # softmax jacobian
        dv_acc = dv_acc + jax.lax.dot_general(
            y, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # yᵀ @ dO -> [bk, D]
        dk_acc = dk_acc + scale * jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ @ q -> [bk, D]
        db_acc = db_acc + ds.sum(axis=0)  # [bk]
        return dk_acc, dv_acc, db_acc

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv, db = jax.lax.fori_loop(0, num_qb, body, (z, z, jnp.zeros((bk,), jnp.float32)))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    db_ref[0, 0, :, 0] = db


def _dq_kernel(
    q_ref, k_ref, v_ref, bias_ref, lse_ref, delta_ref, do_ref, seed_ref,
    dq_ref,
    *, scale: float, block_k: int, rate: float,
):
    """One query block vs. all key blocks: accumulate dq."""
    qb = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
    dob = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]  # [bq]
    dlt = delta_ref[0, 0, :, 0]
    bq, d = qb.shape
    lk = k_ref.shape[2]
    num_kb = lk // block_k
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    seed = (seed_ref[0, 0], seed_ref[0, 1])
    inv = 1.0 / (1.0 - rate) if rate else 1.0

    def body(i, dq_acc):
        k_blk = k_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        bias_blk = bias_ref[0, 0, pl.ds(i * block_k, block_k)].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                qb, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
            + bias_blk[None, :]
        )
        p = jnp.exp(s - lse[:, None])
        dpn = jax.lax.dot_general(
            dob, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if rate:
            keep = _keep_mask(
                seed, b, h, qi * bq, i * block_k, bq, block_k, rate
            )
            dpn = dpn * keep * inv
        ds = p * (dpn - dlt[:, None])
        return dq_acc + scale * jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq = jax.lax.fori_loop(0, num_kb, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _key_bias(bias: jnp.ndarray | None, batch: int, lk: int) -> jnp.ndarray:
    """Returns [B, 1, Lk]: the middle singleton keeps the Pallas block's
    second-to-last dim equal to the array dim (the TPU lowering requires
    last-two block dims divisible by (8, 128) or equal to the array's)."""
    if bias is None:
        return jnp.zeros((batch, 1, lk), jnp.float32)
    if bias.ndim != 4 or bias.shape[1] != 1 or bias.shape[2] != 1:
        raise ValueError(
            f"flash_attention supports key-position bias [B,1,1,Lk] only, got {bias.shape}"
        )
    return bias[:, 0, :, :].astype(jnp.float32)


def _flash_forward(
    q, k, v, bias, seed, *, rate: float, block_q: int, block_k: int, interpret: bool
):
    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_q = _fit(block_q, lq)
    block_k = _fit(block_k, lk)
    key_bias = _key_bias(bias, b, lk)
    scale = 1.0 / (d**0.5)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_k=block_k, rate=rate
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, lq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, lk, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, lk), lambda bi, hi, qi: (bi, 0, 0)),
            pl.BlockSpec((1, 2), lambda bi, hi, qi: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, key_bias, seed)


def _flash_backward(
    q, k, v, bias, seed, out, lse, do,
    *, rate: float, block_q: int, block_k: int, interpret: bool,
):
    b, h, lq, d = q.shape
    lk = k.shape[2]
    block_q = _fit(block_q, lq)
    block_k = _fit(block_k, lk)
    key_bias = _key_bias(bias, b, lk)
    scale = 1.0 / (d**0.5)
    # delta = rowsum(dO ⊙ O): O(L·D) in XLA; exact with or without dropout.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[..., None]  # [B, H, Lq, 1]

    full_q = pl.BlockSpec((1, 1, lq, d), lambda bi, hi, i: (bi, hi, 0, 0))
    full_k = pl.BlockSpec((1, 1, lk, d), lambda bi, hi, i: (bi, hi, 0, 0))
    blk_q = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i: (bi, hi, i, 0))
    blk_k = pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, i: (bi, hi, i, 0))
    full_rows = pl.BlockSpec((1, 1, lq, 1), lambda bi, hi, i: (bi, hi, 0, 0))
    blk_rows = pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, i: (bi, hi, i, 0))
    full_bias = pl.BlockSpec((1, 1, lk), lambda bi, hi, i: (bi, 0, 0))
    blk_bias = pl.BlockSpec((1, 1, block_k), lambda bi, hi, i: (bi, 0, i))
    seed_spec = pl.BlockSpec((1, 2), lambda bi, hi, i: (0, 0))

    dk, dv, db_h = pl.pallas_call(
        functools.partial(
            _dkdv_kernel, scale=scale, block_q=block_q, rate=rate
        ),
        grid=(b, h, lk // block_k),
        in_specs=[full_q, blk_k, blk_k, blk_bias, full_rows, full_rows, full_q, seed_spec],
        out_specs=[
            blk_k,
            blk_k,
            pl.BlockSpec((1, 1, block_k, 1), lambda bi, hi, i: (bi, hi, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, h, lk, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, key_bias, lse, delta, do, seed)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_k=block_k, rate=rate),
        grid=(b, h, lq // block_q),
        in_specs=[blk_q, full_k, full_k, full_bias, blk_rows, blk_rows, blk_q, seed_spec],
        out_specs=blk_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, key_bias, lse, delta, do, seed)

    dbias = None
    if bias is not None:
        # [B, H, Lk, 1] per-head rows -> the key-position bias layout.
        dbias = db_h[..., 0].sum(axis=1)[:, None, None, :].astype(bias.dtype)
    return dq, dk, dv, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, bias, seed, rate, block_q, block_k, interpret):
    out, _ = _flash_forward(
        q, k, v, bias, seed,
        rate=rate, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, bias, seed, rate, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, bias, seed,
        rate=rate, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd(rate, block_q, block_k, interpret, res, do):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv, dbias = _flash_backward(
        q, k, v, bias, seed, out, lse, do,
        rate=rate, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


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
    which is faster than a shredded Pallas grid at any such length."""
    rate = 0.0
    if dropout_rate > 0.0 and not deterministic:
        if dropout_rng is None:
            raise ValueError("flash attention dropout needs dropout_rng")
        rate = float(dropout_rate)
    if not fits_blocks(q.shape[2], k.shape[2], block_q, block_k):
        from .attention import dot_product_attention

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
    return _flash(q, k, v, bias, seed, rate, block_q, block_k, interpret)
