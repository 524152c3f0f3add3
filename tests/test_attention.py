"""Attention impl parity: flash (Pallas, interpret on CPU) and ring
(shard_map sequence parallelism) must match the XLA dot-attention path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    ModelConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.distilbert import (
    DDoSClassifier,
    init_params,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.attention import (
    dot_product_attention,
    make_attention_bias,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
    flash_attention,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.ring_attention import (
    ring_attention_sharded,
)


def _qkv(rng, b=2, h=2, l=64, d=16, dtype=jnp.float32):
    shape = (b, h, l, d)
    q = jnp.asarray(rng.normal(size=shape), dtype)
    k = jnp.asarray(rng.normal(size=shape), dtype)
    v = jnp.asarray(rng.normal(size=shape), dtype)
    return q, k, v


def _mask_bias(rng, b=2, l=64):
    mask = (rng.random((b, l)) > 0.2).astype(np.int32)
    mask[:, 0] = 1  # CLS always visible
    return make_attention_bias(jnp.asarray(mask))


def test_flash_matches_dot_forward(rng):
    q, k, v = _qkv(rng)
    bias = _mask_bias(rng)
    ref = dot_product_attention(q, k, v, bias)
    out = flash_attention(q, k, v, bias, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_matches_dot_no_bias(rng):
    q, k, v = _qkv(rng, l=32)
    ref = dot_product_attention(q, k, v, None)
    out = flash_attention(q, k, v, None, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gradients_match_dot(rng):
    q, k, v = _qkv(rng, b=1, h=2, l=32, d=8)
    bias = _mask_bias(rng, b=1, l=32)

    def loss_dot(q, k, v):
        return (dot_product_attention(q, k, v, bias) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, bias, block_q=8, block_k=8) ** 2).sum()

    g_ref = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_rejects_full_bias(rng):
    q, k, v = _qkv(rng, l=16)
    full_bias = jnp.zeros((2, 2, 16, 16))
    with pytest.raises(ValueError, match="key-position bias"):
        flash_attention(q, k, v, full_bias, block_q=8, block_k=8)


def test_flash_in_model_forward(rng):
    """attention_impl='flash' through the full classifier equals 'dot'."""
    base = ModelConfig.tiny(attention_dropout=0.0)
    flash_cfg = base.replace(attention_impl="flash")
    model_dot = DDoSClassifier(base)
    model_flash = DDoSClassifier(flash_cfg)
    params = init_params(model_dot, base, jax.random.key(0))
    ids = jnp.asarray(rng.integers(0, base.vocab_size, (2, base.max_len)), jnp.int32)
    mask = jnp.ones((2, base.max_len), jnp.int32)
    out_dot = model_dot.apply({"params": params}, ids, mask, True)
    out_flash = model_flash.apply({"params": params}, ids, mask, True)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dot), atol=2e-4
    )


def test_ring_matches_dot(rng, eight_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(eight_devices[:2]), ("seq",))
    q, k, v = _qkv(rng, b=1, h=2, l=32, d=8)
    bias = _mask_bias(rng, b=1, l=32)
    ref = dot_product_attention(q, k, v, bias)
    out = ring_attention_sharded(q, k, v, bias, mesh=mesh, axis_name="seq")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blockwise_local_matches_dot_and_ring(rng, eight_devices):
    """blockwise_attention_local (the single-chip ring stand-in: ring
    schedule minus transport) matches the dot path and the real sharded
    ring bit-for-bit-close on the same inputs."""
    from jax.sharding import Mesh

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.ring_attention import (
        blockwise_attention_local,
    )

    q, k, v = _qkv(rng, b=1, h=2, l=32, d=8)
    bias = _mask_bias(rng, b=1, l=32)
    ref = dot_product_attention(q, k, v, bias)
    out = blockwise_attention_local(q, k, v, bias, n_chunks=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    mesh = Mesh(np.array(eight_devices[:4]), ("seq",))
    ring = ring_attention_sharded(q, k, v, bias, mesh=mesh, axis_name="seq")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ring), atol=2e-6)
    # No-bias path too.
    out_nb = blockwise_attention_local(q, k, v, n_chunks=8)
    np.testing.assert_allclose(
        np.asarray(out_nb),
        np.asarray(dot_product_attention(q, k, v, None)),
        atol=2e-5,
    )


def test_ring_no_bias_matches_dot(rng, eight_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(eight_devices[:2]), ("seq",))
    q, k, v = _qkv(rng, b=1, h=1, l=16, d=8)
    ref = dot_product_attention(q, k, v, None)
    out = ring_attention_sharded(q, k, v, mesh=mesh, axis_name="seq")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_gradients_match_dot(rng, eight_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(eight_devices[:2]), ("seq",))
    q, k, v = _qkv(rng, b=1, h=1, l=16, d=8)
    bias = _mask_bias(rng, b=1, l=16)

    def loss_dot(q, k, v):
        return (dot_product_attention(q, k, v, bias) ** 2).sum()

    def loss_ring(q, k, v):
        return (
            ring_attention_sharded(q, k, v, bias, mesh=mesh, axis_name="seq") ** 2
        ).sum()

    g_ref = jax.grad(loss_dot, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.slow
def test_ring_model_forward_matches_dot(rng, eight_devices):
    """Full classifier under a sequence-sharded shard_map (ring attention,
    shard-offset positions, global CLS pooling) equals the unsharded dot
    path."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(eight_devices[:2]), ("seq",))
    base = ModelConfig.tiny(
        attention_dropout=0.0, max_len=64, max_position_embeddings=64
    )
    ring_cfg = base.replace(attention_impl="ring", ring_axis="seq")
    model_dot = DDoSClassifier(base)
    model_ring = DDoSClassifier(ring_cfg)
    params = init_params(model_dot, base, jax.random.key(0))
    ids = jnp.asarray(rng.integers(0, base.vocab_size, (2, 64)), jnp.int32)
    mask_np = (rng.random((2, 64)) > 0.3).astype(np.int32)
    mask_np[:, 0] = 1
    mask = jnp.asarray(mask_np)

    ref = model_dot.apply({"params": params}, ids, mask, True)
    out = jax.shard_map(
        lambda p, i, m: model_ring.apply({"params": p}, i, m, True),
        mesh=mesh,
        in_specs=(P(), P(None, "seq"), P(None, "seq")),
        out_specs=P(),
    )(params, ids, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@pytest.mark.slow
def test_ring_sequence_parallel_training_matches_dot(rng, eight_devices):
    """Long-context TRAINING parity: gradients of the full classifier under
    sequence-sharded ring attention (shard_map, K/V ppermute ring) equal the
    unsharded dot path, and a short Adam loop actually learns through it."""
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(eight_devices[:2]), ("seq",))
    # Only attention_dropout=0.0 is required (ring impl validation); the
    # other dropouts are inert under deterministic=True.
    base = ModelConfig.tiny(
        attention_dropout=0.0, max_len=64, max_position_embeddings=64
    )
    ring_cfg = base.replace(attention_impl="ring", ring_axis="seq")
    model_dot = DDoSClassifier(base)
    model_ring = DDoSClassifier(ring_cfg)
    params = init_params(model_dot, base, jax.random.key(0))
    B = 4
    ids = jnp.asarray(rng.integers(0, base.vocab_size, (B, 64)), jnp.int32)
    # Random padding mask: the grad path through make_attention_bias and
    # the shard-offset handling must be part of the parity check.
    mask_np = (rng.random((B, 64)) > 0.3).astype(np.int32)
    mask_np[:, 0] = 1
    mask = jnp.asarray(mask_np)
    labels = jnp.asarray(rng.integers(0, 2, B), jnp.int32)

    fwd_ring = jax.shard_map(
        lambda p, i, m: model_ring.apply({"params": p}, i, m, True),
        mesh=mesh,
        in_specs=(P(), P(None, "seq"), P(None, "seq")),
        out_specs=P(),
    )

    def loss_dot(p):
        lg = model_dot.apply({"params": p}, ids, mask, True)
        return optax.softmax_cross_entropy_with_integer_labels(lg, labels).mean()

    def loss_ring(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            fwd_ring(p, ids, mask), labels
        ).mean()

    g_dot = jax.grad(loss_dot)(params)
    g_ring = jax.grad(loss_ring)(params)
    for a, b in zip(jax.tree.leaves(g_dot), jax.tree.leaves(g_ring)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    # A few Adam steps through the sequence-parallel path must reduce loss.
    opt = optax.adam(1e-3)
    ost = opt.init(params)

    @jax.jit
    def step(p, o):
        l, g = jax.value_and_grad(loss_ring)(p)
        u, o = opt.update(g, o, p)
        return optax.apply_updates(p, u), o, l

    losses = []
    p = params
    for _ in range(5):
        p, ost, l = step(p, ost)
        losses.append(float(l))
    assert losses[-1] < losses[0], losses


def test_ring_rejects_query_bias(rng, eight_devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(eight_devices[:2]), ("seq",))
    q, k, v = _qkv(rng, b=1, h=1, l=16, d=8)
    causal = jnp.zeros((1, 1, 16, 16))
    with pytest.raises(ValueError, match="key-position bias"):
        ring_attention_sharded(q, k, v, causal, mesh=mesh, axis_name="seq")


def test_ring_config_initializes_and_runs_outside_shard_map(rng):
    """attention_impl='ring' must work through the normal Trainer path:
    init_params and unsharded eval trace outside shard_map and fall back to
    the identical unsharded math."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        TrainConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        Trainer,
    )

    cfg = ModelConfig.tiny(attention_impl="ring", attention_dropout=0.0)
    trainer = Trainer(cfg, TrainConfig())
    state = trainer.init_state(seed=0)  # would raise NameError before the fix
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, cfg.max_len)), jnp.int32)
    mask = jnp.ones((2, cfg.max_len), jnp.int32)
    ref = DDoSClassifier(cfg.replace(attention_impl="dot", attention_dropout=0.0)).apply(
        {"params": state.params}, ids, mask, True
    )
    out = trainer.model.apply({"params": state.params}, ids, mask, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_dropout_matches_unsharded_and_is_invariant(eight_devices):
    """Ring attention dropout (global-coordinate hash masks): the sampled
    output is identical at any seq shard count, deterministic per key, and
    different keys give different masks. (The former ring+dropout config
    rejection is obsolete — every impl supports attention dropout now.)"""
    import numpy as np
    from jax.sharding import Mesh

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.ring_attention import (
        ring_attention_sharded,
    )

    r = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(r.normal(size=(1, 2, 16, 8)).astype(np.float32))
        for _ in range(3)
    )
    key = jax.random.key(2)

    def run(n, key=key):
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(n), ("seq",))
        return np.asarray(
            ring_attention_sharded(
                q, k, v, mesh=mesh,
                dropout_rate=0.3, dropout_rng=key, deterministic=False,
            )
        )

    o1, o2, o4 = run(1), run(2), run(4)
    np.testing.assert_allclose(o2, o1, atol=1e-5)
    np.testing.assert_allclose(o4, o1, atol=1e-5)
    np.testing.assert_array_equal(run(2), run(2))  # deterministic per key
    assert not np.allclose(o1, run(2, jax.random.key(3)))  # key matters
    # Clean (no-dropout) output differs from the dropped one.
    clean = np.asarray(
        ring_attention_sharded(
            q, k, v,
            mesh=Mesh(np.array(jax.devices()[:2]).reshape(2), ("seq",)),
        )
    )
    assert not np.allclose(clean, o1, atol=1e-5)


def test_flash_handles_non_multiple_block_lengths():
    """L=384 doesn't tile into the default 256/512 blocks — the kernel must
    snap to a divisor (gcd -> 128) instead of erroring, and still match the
    dot path."""
    import numpy as np

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.attention import (
        dot_product_attention,
        make_attention_bias,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
        flash_attention,
    )

    rng = np.random.default_rng(0)
    B, H, L, D = 2, 2, 384, 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, L, D)).astype(np.float32))
        for _ in range(3)
    )
    mask = np.ones((B, L), np.int32)
    mask[1, 300:] = 0
    bias = make_attention_bias(jnp.asarray(mask))
    out = flash_attention(q, k, v, bias)
    ref = dot_product_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_degenerate_length_falls_back_to_dot(rng):
    """Prime / odd lengths whose gcd with the default blocks is degenerate
    must take the XLA dot path (block-1 Pallas grids are pathological),
    still matching dot numerics exactly."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_K,
        DEFAULT_BLOCK_Q,
        fits_blocks,
    )

    assert fits_blocks(64, 64, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)  # <= block
    assert fits_blocks(2048, 2048, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    assert not fits_blocks(1031, 1031, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)  # prime
    assert not fits_blocks(768, 1031, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    # 768 = 256*3: q fits; k gcd(768, 512)=256 >= 128: fits.
    assert fits_blocks(768, 768, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)

    q, k, v = _qkv(rng, l=521)  # prime length > default blocks
    bias = _mask_bias(rng, l=521)
    ref = dot_product_attention(q, k, v, bias)
    out = flash_attention(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    # And gradients flow through the fallback.
    g = jax.grad(lambda q: flash_attention(q, k, v, bias).sum())(q)
    gref = jax.grad(lambda q: dot_product_attention(q, k, v, bias).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=1e-5)


@pytest.mark.slow
def test_flash_dropout_deterministic_and_unbiased(rng):
    """Flash attention dropout: same rng -> same output; different rng ->
    different mask; averaging over many seeds recovers the no-dropout
    output (inverted-dropout unbiasedness) and the keep rate matches."""
    q, k, v = _qkv(rng, b=1, h=2, l=32, d=8)
    bias = _mask_bias(rng, b=1, l=32)
    base = flash_attention(q, k, v, bias, block_q=16, block_k=16)
    key = jax.random.key(0)

    def drop(key):
        return flash_attention(
            q, k, v, bias, dropout_rate=0.4, dropout_rng=key,
            deterministic=False, block_q=16, block_k=16,
        )

    out1, out2 = drop(key), drop(key)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert not np.allclose(np.asarray(out1), np.asarray(drop(jax.random.key(1))))
    assert not np.allclose(np.asarray(out1), np.asarray(base))
    # E[dropout(w)] = w: the seed-average converges to the clean output.
    # 64 seeds put ~sqrt(p/(1-p))/8 ~ 0.1 of per-element noise on the mean,
    # so bound the max loosely and the average error tightly.
    outs = np.stack(
        [np.asarray(drop(jax.random.key(s))) for s in range(64)]
    )
    err = np.abs(outs.mean(0) - np.asarray(base))
    # Rows whose softmax concentrates on one key carry per-seed noise of
    # the full |v| scale, so bound the bulk, not the max.
    assert err.mean() < 0.05, err.mean()
    assert np.quantile(err, 0.9) < 0.2, np.quantile(err, 0.9)
    # And the mask itself keeps at the configured rate.
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.flash_attention import (
        _keep_mask,
    )

    keeps = np.mean(
        [
            np.asarray(
                _keep_mask(
                    jax.random.bits(jax.random.key(s), (2,), jnp.uint32),
                    jnp.int32(0), jnp.int32(1), 0, 0, 32, 32, 0.4,
                )
            ).mean()
            for s in range(16)
        ]
    )
    np.testing.assert_allclose(keeps, 0.6, atol=0.03)
    # deterministic=True ignores the rate entirely.
    out_det = flash_attention(
        q, k, v, bias, dropout_rate=0.4, dropout_rng=key,
        deterministic=True, block_q=16, block_k=16,
    )
    np.testing.assert_allclose(np.asarray(out_det), np.asarray(base), atol=1e-6)


@pytest.mark.slow
def test_flash_dropout_gradients_check(rng):
    """The Pallas backward regenerates the identical dropout mask from the
    (seed, position) hash: reverse-mode grads must match finite differences
    (the mask is locally constant, so f is differentiable at the check
    point)."""
    from jax.test_util import check_grads

    q, k, v = _qkv(rng, b=1, h=1, l=16, d=8)
    bias = _mask_bias(rng, b=1, l=16)
    key = jax.random.key(3)

    def f(q, k, v, bias):
        return flash_attention(
            q, k, v, bias, dropout_rate=0.3, dropout_rng=key,
            deterministic=False, block_q=8, block_k=8,
        ).sum()

    # Fast-lane determinism: same key -> identical value; different key ->
    # different mask (the 64-seed unbiasedness statistics run in the slow
    # lane).
    assert float(f(q, k, v, bias)) == float(f(q, k, v, bias))
    alt = flash_attention(
        q, k, v, bias, dropout_rate=0.3, dropout_rng=jax.random.key(4),
        deterministic=False, block_q=8, block_k=8,
    ).sum()
    assert float(f(q, k, v, bias)) != float(alt)
    check_grads(f, (q, k, v, bias), order=1, modes=["rev"], atol=2e-2, rtol=2e-2)


def test_flash_pallas_backward_matches_dot_large_blocks(rng):
    """Grad parity on a multi-block case (several q and k blocks per head),
    including the key-bias gradient."""
    q, k, v = _qkv(rng, b=2, h=2, l=64, d=16)
    bias = _mask_bias(rng, b=2, l=64)

    def loss(fn):
        def inner(q, k, v, bias):
            return (fn(q, k, v, bias) * 0.37).sum()
        return inner

    flash_fn = loss(lambda *a: flash_attention(*a, block_q=16, block_k=16))
    dot_fn = loss(dot_product_attention)
    g_flash = jax.grad(flash_fn, argnums=(0, 1, 2, 3))(q, k, v, bias)
    g_dot = jax.grad(dot_fn, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(g_flash, g_dot):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
