"""Position-keyed hash dropout (ops/hash_dropout.py): the mask primitive
behind seq-shard-invariant dropout (models/distilbert.py _seq_dropout,
parallel/ring_attention.py attention dropout)."""

import numpy as np

import jax
import jax.numpy as jnp

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.hash_dropout import (
    hash_dropout,
    hash_keep_mask,
)


def _seed(i=0):
    return jax.random.bits(jax.random.key(i), (2,), jnp.uint32)


def test_keep_rate_and_determinism():
    m = hash_keep_mask(_seed(), (64, 64), 0.3)
    m2 = hash_keep_mask(_seed(), (64, 64), 0.3)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
    np.testing.assert_allclose(np.asarray(m).mean(), 0.7, atol=0.03)
    # Different seeds -> different masks; rate 0 keeps everything.
    assert not np.array_equal(np.asarray(m), np.asarray(hash_keep_mask(_seed(1), (64, 64), 0.3)))
    assert np.asarray(hash_keep_mask(_seed(), (8, 8), 0.0)).all()


def test_offset_slices_reproduce_global_mask():
    """THE invariance property: a shard hashing positions [k, k+Ls) along
    the offset axis reproduces exactly the global mask's slice — so any
    seq shard count samples the same mask."""
    full = np.asarray(hash_keep_mask(_seed(), (4, 32, 8), 0.4, offsets={}))
    for n_shards in (2, 4):
        ls = 32 // n_shards
        parts = [
            np.asarray(
                hash_keep_mask(
                    _seed(), (4, ls, 8), 0.4, offsets={1: i * ls}
                )
            )
            for i in range(n_shards)
        ]
        np.testing.assert_array_equal(np.concatenate(parts, axis=1), full)


def test_batch_axis_offsets_give_data_shards_independent_masks():
    """Rows on different data shards must not reuse one mask: the axis-0
    (batch) offset reproduces the global mask's row slices, which are
    mutually distinct — the models' _drop_offsets wiring depends on it."""
    full = np.asarray(hash_keep_mask(_seed(), (8, 16, 4), 0.4, offsets={}))
    top = np.asarray(hash_keep_mask(_seed(), (4, 16, 4), 0.4, offsets={0: 0}))
    bot = np.asarray(hash_keep_mask(_seed(), (4, 16, 4), 0.4, offsets={0: 4}))
    np.testing.assert_array_equal(np.concatenate([top, bot], axis=0), full)
    assert not np.array_equal(top, bot)


def test_hash_dropout_scales_and_zeroes():
    x = jnp.ones((16, 16), jnp.float32)
    key = jax.random.key(5)
    y = np.asarray(hash_dropout(x, 0.25, key))
    kept = y > 0
    np.testing.assert_allclose(y[kept], 1.0 / 0.75, rtol=1e-6)
    np.testing.assert_allclose(kept.mean(), 0.75, atol=0.08)
    # deterministic=True and rate 0 are identity.
    np.testing.assert_array_equal(
        np.asarray(hash_dropout(x, 0.25, key, deterministic=True)), np.asarray(x)
    )
    np.testing.assert_array_equal(
        np.asarray(hash_dropout(x, 0.0, key)), np.asarray(x)
    )


import pytest


@pytest.mark.slow
def test_model_seq_dropout_invariance_via_ring(eight_devices):
    """End-to-end through the model: the same forward (dropout ON) under
    shard_map at seq=1 vs seq=4 produces identical logits. (Slow: three
    full-model shard_map compiles; the mask-level invariance runs in the
    fast lane, test_offset_slices_reproduce_global_mask.)"""
    from jax.sharding import Mesh, PartitionSpec as P

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        ModelConfig,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.distilbert import (
        DDoSClassifier,
        init_params,
    )

    L = 16
    cfg = ModelConfig.tiny(
        max_len=L,
        max_position_embeddings=L,
        dropout=0.2,
        attention_dropout=0.2,
        head_dropout=0.3,
        attention_impl="ring",
        ring_axis="seq",
    )
    model = DDoSClassifier(cfg)
    params = init_params(model, cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 200, (4, L)).astype(np.int32))
    mask = jnp.ones((4, L), jnp.int32)
    key = jax.random.key(9)

    def logits_at(n_seq):
        mesh = Mesh(
            np.array(jax.devices()[:n_seq]).reshape(n_seq), ("seq",)
        )
        fn = jax.shard_map(
            lambda i, m: model.apply(
                {"params": params}, i, m, False, rngs={"dropout": key}
            ),
            mesh=mesh,
            in_specs=(P(None, "seq"), P(None, "seq")),
            out_specs=P(),
        )
        return np.asarray(fn(ids, mask))

    l1, l2, l4 = logits_at(1), logits_at(2), logits_at(4)
    np.testing.assert_allclose(l2, l1, atol=1e-5)
    np.testing.assert_allclose(l4, l1, atol=1e-5)
    # And dropout is active: deterministic forward differs.
    det = model.apply({"params": params}, ids, mask, True)
    assert not np.allclose(l1, np.asarray(det), atol=1e-5)
