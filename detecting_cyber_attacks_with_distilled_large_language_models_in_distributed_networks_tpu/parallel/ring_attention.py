"""Ring attention: sequence-parallel blockwise attention over a mesh axis.

Long-context path: the sequence dimension is sharded over a ``seq`` mesh
axis, each device holding [B, H, L/n, D] query/key/value shards. Attention
runs in n ring steps — every device computes blockwise attention of its
local queries against the key/value chunk it currently holds, then passes
that chunk to its ring neighbor with ``jax.lax.ppermute`` (one ICI hop),
accumulating results with the online-softmax (flash) recurrence. No device
ever materializes the full [L, L] score matrix or the full K/V — memory is
O(L/n · D) per device and communication rides the ICI ring.

The reference has nothing like this (sequences are fixed 128 tokens,
reference client1.py:27); this is the framework's long-context scaling
story, composing the flash recurrence (ops/flash_attention.py) with the
mesh machinery (parallel/mesh.py).

``ring_attention`` must be called inside ``shard_map`` with ``axis_name``
bound (the model's ``attention_impl="ring"`` path assumes the whole forward
runs under one); ``ring_attention_sharded`` wraps full arrays for
standalone/tests. Everything is differentiable — ``ppermute`` and the
recurrence are standard JAX ops, so autodiff composes (gradients take the
reverse ring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _partial_attention(q, k, v, bias, scale, drop=None):
    """Unnormalized flash statistics of local queries vs one K/V chunk.

    Returns ``(pv, m, l)``: exp-weighted values, row max, row denominator —
    enough to merge chunks with the online-softmax recurrence.

    Numerics contract: matmul INPUTS stay in the activation dtype (bf16 on
    TPU — both einsums feed the MXU half-width operands) with fp32
    accumulation via ``preferred_element_type``; scaling, softmax
    statistics and the merge recurrence run fp32. Same contract as the dot
    path (ops/attention.py) and the flash kernels
    (ops/flash_attention.py) — under fp32 activations (CPU tests) it
    degenerates to full fp32, so dot-path parity stays exact.

    ``drop = (seed, rate, b_off, q_off, k_off)`` applies attention dropout
    with a GLOBAL-coordinate hash mask (ops/hash_dropout.py) — batch rows,
    query and key positions all offset to their global indices: the pv
    numerator is masked and inverse-scaled, the denominator ``l``
    accumulates undropped weights — exactly the dot path's
    drop-after-softmax semantics (ops/attention.py:56-61) expressed in the
    online recurrence."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    m = s.max(axis=-1)  # [B,H,Lq]
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    if drop is not None:
        from ..ops.hash_dropout import hash_keep_mask

        seed, rate, b_off, q_off, k_off = drop
        keep = hash_keep_mask(
            seed, p.shape, rate, offsets={0: b_off, 2: q_off, 3: k_off}
        )
        p = p * keep * (1.0 / (1.0 - rate))
    pv = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(q.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return pv, m, l


def _merge_partial(acc, m, l, pv_i, m_i, l_i):
    """Online-softmax merge of one chunk's partial statistics into the
    running ``(acc, m, l)`` — shared by the sharded ring and the
    single-device blockwise variant so their numerics stay structurally
    identical."""
    m_new = jnp.maximum(m, m_i)
    alpha = jnp.exp(m - m_new)
    alpha_i = jnp.exp(m_i - m_new)
    acc = acc * alpha[..., None] + pv_i * alpha_i[..., None]
    l = l * alpha + l_i * alpha_i
    return acc, m_new, l


def ring_attention(
    q: jnp.ndarray,  # [B, H, Lq_local, D] — local query shard
    k: jnp.ndarray,  # [B, H, Lk_local, D] — local key shard
    v: jnp.ndarray,  # [B, H, Lk_local, D]
    bias: jnp.ndarray | None = None,  # [B, 1, 1, Lk_local] — mask for LOCAL keys
    *,
    axis_name: str = "seq",
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    deterministic: bool = True,
    batch_offset: jax.Array | int = 0,
) -> jnp.ndarray:
    """Sequence-parallel attention inside ``shard_map``; the key-position
    bias (when given) rotates around the ring together with its K/V chunk.

    Only key-position biases are accepted: a bias with a real query dimension
    would be applied to *other devices'* queries after the first rotation.

    Attention dropout (``dropout_rate``/``dropout_rng``): masks come from a
    hash of the GLOBAL (query, key) coordinates — each K/V chunk's global
    offset rotates around the ring alongside it — so the sampled mask is
    invariant to the seq-axis shard count (the same property the flash
    kernels' forward/backward mask regeneration relies on). The rng must be
    shard-invariant (flax ``make_rng`` keys are).
    """
    if bias is not None and (
        bias.ndim != 4 or bias.shape[1] != 1 or bias.shape[2] != 1
    ):
        raise ValueError(
            f"ring_attention supports key-position bias [B,1,1,Lk] only, "
            f"got {bias.shape}"
        )
    n = jax.lax.psum(1, axis_name)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_bias = bias is not None
    rate = float(dropout_rate) if not deterministic else 0.0
    if rate > 0.0 and dropout_rng is None:
        raise ValueError("ring attention dropout needs dropout_rng")
    lk = k.shape[2]
    if rate > 0.0:
        seed = jax.random.bits(dropout_rng, (2,), jnp.uint32)
        q_off = jax.lax.axis_index(axis_name) * q.shape[2]
    else:
        seed = q_off = None

    def merge(acc, m, l, k_c, v_c, b_c, k_off):
        drop = (
            None
            if rate == 0.0
            else (seed, rate, batch_offset, q_off, k_off)
        )
        pv_i, m_i, l_i = _partial_attention(
            q, k_c, v_c, b_c if has_bias else None, scale, drop
        )
        return _merge_partial(acc, m, l, pv_i, m_i, l_i)

    def rotate(x):
        return jax.tree.map(lambda t: jax.lax.ppermute(t, axis_name, perm), x)

    b_sz, h, lq, d = q.shape
    acc0 = jnp.zeros((b_sz, h, lq, d), jnp.float32)
    m0 = jnp.full((b_sz, h, lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b_sz, h, lq), jnp.float32)

    # Constants enter the scan carry device-invariant but come out varying
    # over every mesh axis q varies over (the ring axis alone inside a pure
    # seq shard_map; clients/data too inside the 3-axis fedseq composition);
    # mark them varying up front so the scan carry types match.
    want_vma = tuple(jax.typeof(q).vma)

    def _vary(x):
        have = jax.typeof(x).vma
        missing = tuple(a for a in want_vma if a not in have)
        if not missing:
            return x
        return jax.lax.pcast(x, missing, to="varying")

    acc0, m0, l0 = jax.tree.map(_vary, (acc0, m0, l0))
    b0 = bias if has_bias else ()  # empty pytree: nothing rotates when no mask
    # Each chunk's global key offset rides the ring with its K/V (axis_index
    # itself must be marked varying to enter the rotating carry).
    k_off0 = _vary(jax.lax.axis_index(axis_name).astype(jnp.int32) * lk)

    def step(carry, _):
        k_c, v_c, b_c, k_off, acc, m, l = carry
        acc, m, l = merge(acc, m, l, k_c, v_c, b_c, k_off)
        return (
            rotate(k_c), rotate(v_c), rotate(b_c), rotate(k_off), acc, m, l
        ), None

    # n-1 compute+rotate steps; the final chunk is merged without the last
    # rotation (its rotated carry would be discarded — one wasted ICI hop
    # of full K/V per layer otherwise).
    (k_f, v_f, b_f, k_off_f, acc, m, l), _ = jax.lax.scan(
        step, (k, v, b0, k_off0, acc0, m0, l0), None, length=n - 1
    )
    acc, m, l = merge(acc, m, l, k_f, v_f, b_f, k_off_f)
    # -1e9 mask addends keep l > 0 even for fully masked rows (parity with
    # the dot/flash paths).
    return (acc / l[..., None]).astype(q.dtype)


def blockwise_attention_local(
    q: jnp.ndarray,  # [B, H, L, D] — full arrays, ONE device
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,  # [B, 1, 1, L] key-position mask
    *,
    n_chunks: int = 8,
) -> jnp.ndarray:
    """The ring schedule's compute on one device: K/V split into
    ``n_chunks`` chunks merged with the same ``_partial_attention`` +
    online-softmax recurrence, ppermute hops removed. Numerically it is
    ``ring_attention`` on an ``n_chunks``-device mesh (the recurrence and
    chunk order are identical; only the transport differs), so it serves
    as (a) a single-chip stand-in for the ring path's per-chunk math and
    (b) a parity anchor against the dot path (tests/test_attention.py).
    Deterministic only — the dropout story lives in the sharded path."""
    b_sz, h, lq, d = q.shape
    lk = k.shape[2]
    if lk % n_chunks:
        raise ValueError(f"L={lk} must divide into n_chunks={n_chunks}")
    ck = lk // n_chunks
    scale = 1.0 / (d**0.5)
    # [n, B, H, ck, D] chunk-major stacks feed the scan.
    kc = jnp.moveaxis(k.reshape(b_sz, h, n_chunks, ck, d), 2, 0)
    vc = jnp.moveaxis(v.reshape(b_sz, h, n_chunks, ck, d), 2, 0)
    if bias is not None:
        bc = jnp.moveaxis(bias.reshape(b_sz, 1, 1, n_chunks, ck), 3, 0)
        xs = (kc, vc, bc)
    else:
        xs = (kc, vc)

    acc0 = jnp.zeros((b_sz, h, lq, d), jnp.float32)
    m0 = jnp.full((b_sz, h, lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b_sz, h, lq), jnp.float32)

    def step(carry, chunk):
        acc, m, l = carry
        k_c, v_c = chunk[0], chunk[1]
        b_c = chunk[2] if bias is not None else None
        pv_i, m_i, l_i = _partial_attention(q, k_c, v_c, b_c, scale)
        return _merge_partial(acc, m, l, pv_i, m_i, l_i), None

    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), xs)
    return (acc / l[..., None]).astype(q.dtype)


@functools.lru_cache(maxsize=64)
def _sharded_ring_fn(
    mesh: Mesh,
    axis_name: str,
    dropout_rate: float,
    deterministic: bool,
    has_bias: bool,
    has_rng: bool,
):
    """Build + jit the sharded ring program once per static configuration.

    The eager call path matters: an unjitted ``shard_map`` dispatches
    op-by-op across the virtual devices (measured ~10x slower than the
    compile itself on an 8-device CPU mesh), so the wrapper jits and the
    cache keys on everything static. The dropout key is a traced argument
    (replicated spec), so re-keying dropout reuses the same executable."""
    seq_spec = P(None, None, axis_name, None)
    bias_spec = P(None, None, None, axis_name)

    def call(q, k, v, *rest):
        bias = rest[0] if has_bias else None
        rng = rest[-1] if has_rng else None
        args = (q, k, v) if bias is None else (q, k, v, bias)
        return ring_attention(
            *args,
            axis_name=axis_name,
            dropout_rate=dropout_rate,
            dropout_rng=rng,
            deterministic=deterministic,
        )

    in_specs = (
        (seq_spec,) * 3
        + ((bias_spec,) if has_bias else ())
        + ((P(),) if has_rng else ())
    )

    return jax.jit(
        jax.shard_map(
            call, mesh=mesh, in_specs=in_specs, out_specs=seq_spec
        )
    )


def ring_attention_sharded(
    q: jnp.ndarray,  # [B, H, L, D] — full arrays
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: jnp.ndarray | None = None,
    *,
    mesh: Mesh,
    axis_name: str = "seq",
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    deterministic: bool = True,
) -> jnp.ndarray:
    """Standalone wrapper: shards the sequence axis of full [B, H, L, D]
    arrays over ``axis_name`` and runs the ring. The model-integrated path
    instead runs the whole encoder under one ``shard_map``."""
    fn = _sharded_ring_fn(
        mesh,
        axis_name,
        float(dropout_rate),
        bool(deterministic),
        bias is not None,
        dropout_rng is not None,
    )
    args = (q, k, v) + ((bias,) if bias is not None else ())
    if dropout_rng is not None:
        args += (dropout_rng,)
    return fn(*args)
