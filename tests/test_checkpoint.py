"""Checkpoint/resume: full-state round trip, sharded restore, warm start."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.checkpoint import (
    Checkpointer,
    maybe_warm_start,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
)


def _tiny_trainer():
    return Trainer(ModelConfig.tiny(), TrainConfig(seed=3))


def _tiny_batch(cfg, rng, bs=8):
    return {
        "input_ids": rng.integers(0, cfg.vocab_size, (bs, cfg.max_len)).astype(np.int32),
        "attention_mask": np.ones((bs, cfg.max_len), np.int32),
        "labels": rng.integers(0, 2, bs).astype(np.int32),
    }


def _assert_tree_equal(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a,
        b,
    )


def test_single_client_roundtrip(tmp_path, rng):
    trainer = _tiny_trainer()
    state = trainer.init_state(seed=0)
    batch = _tiny_batch(trainer.model_cfg, rng)
    for _ in range(3):
        state, _ = trainer.train_step(state, batch)

    with Checkpointer(str(tmp_path / "ckpt")) as ckpt:
        ckpt.save(int(state.step), state, meta={"round": 1})
        ckpt.wait()
        template = trainer.init_state(seed=0)
        restored = ckpt.restore(template)
        assert ckpt.restore_meta() == {"round": 1}

    # Full fidelity: params, opt_state (Adam moments), step, and the PRNG key.
    _assert_tree_equal(restored.params, state.params)
    _assert_tree_equal(restored.opt_state, state.opt_state)
    assert int(restored.step) == int(state.step) == 3
    np.testing.assert_array_equal(
        jax.random.key_data(restored.rng), jax.random.key_data(state.rng)
    )

    # Resumed training continues identically to uninterrupted training.
    cont_a, loss_a = trainer.train_step(state, batch)
    cont_b, loss_b = trainer.train_step(restored, batch)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)


def test_federated_sharded_roundtrip(tmp_path, eight_devices):
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.federated import (
        FederatedTrainer,
    )

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
        DataConfig,
    )

    cfg = ExperimentConfig.for_clients(
        2,
        model=ModelConfig.tiny(),
        data=DataConfig(max_len=ModelConfig.tiny().max_len),
        mesh=MeshConfig(clients=2, data=1),
    )
    trainer = FederatedTrainer(cfg)
    state = trainer.init_state(seed=1)

    with Checkpointer(str(tmp_path / "fed")) as ckpt:
        ckpt.save(0, state, meta={"round": 0, "config": cfg.to_dict()})
        ckpt.wait()
        template = trainer.init_state(seed=1)
        restored = ckpt.restore(template)
        meta = ckpt.restore_meta()

    _assert_tree_equal(restored.params, state.params)
    _assert_tree_equal(restored.opt_state, state.opt_state)
    np.testing.assert_array_equal(
        jax.random.key_data(restored.rngs), jax.random.key_data(state.rngs)
    )
    # Restore lands on the template's sharding (clients axis), not host-replicated.
    leaf = jax.tree.leaves(restored.params)[0]
    assert leaf.sharding == jax.tree.leaves(template.params)[0].sharding
    assert meta["round"] == 0
    assert meta["config"]["fed"]["num_clients"] == 2


def test_max_to_keep_garbage_collects(tmp_path, rng):
    trainer = _tiny_trainer()
    state = trainer.init_state(seed=0)
    with Checkpointer(str(tmp_path / "gc"), max_to_keep=2) as ckpt:
        for step in range(4):
            ckpt.save(step, state)
        ckpt.wait()
        assert ckpt.latest_step() == 3
        restored = ckpt.restore(trainer.init_state(seed=0), step=3)
        with pytest.raises(Exception):
            ckpt.restore(trainer.init_state(seed=0), step=0)  # GC'd
    _assert_tree_equal(restored.params, state.params)


def test_warm_start_absent_and_present(tmp_path, rng):
    trainer = _tiny_trainer()
    template = trainer.init_state(seed=0)

    # Reference behavior when no .pth exists (client1.py:375-377): fresh start.
    state, step = maybe_warm_start(str(tmp_path / "nope"), template)
    assert state is None and step is None

    trained = trainer.init_state(seed=0)
    batch = _tiny_batch(trainer.model_cfg, rng)
    trained, _ = trainer.train_step(trained, batch)
    with Checkpointer(str(tmp_path / "warm")) as ckpt:
        ckpt.save(7, trained)
        ckpt.wait()

    state, step = maybe_warm_start(str(tmp_path / "warm"), template)
    assert step == 7
    _assert_tree_equal(state.params, trained.params)


def test_warm_start_incompatible_checkpoint_degrades_to_fresh(tmp_path, rng):
    """A checkpoint saved under a different model shape (e.g. the default
    vocab grew between runs) must warm-start as None, not abort — warm start
    is an optimization (reference client1.py:375-377 proceeds from scratch
    when no compatible .pth exists)."""
    old = Trainer(ModelConfig.tiny(vocab_size=100), TrainConfig(seed=3))
    state = old.init_state(seed=0)
    with Checkpointer(str(tmp_path / "old")) as ckpt:
        ckpt.save(4, state)
        ckpt.wait()

    new = Trainer(ModelConfig.tiny(vocab_size=140), TrainConfig(seed=3))
    template = new.init_state(seed=0)
    restored, step = maybe_warm_start(str(tmp_path / "old"), template)
    assert restored is None and step is None


def test_prng_impl_is_plumbed():
    """TrainConfig.prng_impl selects the dropout-key generator (rbg default
    — the cheap TPU impl every cell runs — threefry on request)."""
    for impl in ("rbg", "threefry2x32"):
        tr = Trainer(ModelConfig.tiny(), TrainConfig(seed=0, prng_impl=impl))
        st = tr.init_state(seed=0)
        assert str(jax.random.key_impl(st.rng)) == impl
    with pytest.raises(ValueError, match="unknown prng_impl"):
        TrainConfig(prng_impl="bogus")


def test_restore_empty_dir_raises(tmp_path):
    trainer = _tiny_trainer()
    with Checkpointer(str(tmp_path / "empty")) as ckpt:
        with pytest.raises(FileNotFoundError):
            ckpt.restore(trainer.init_state(seed=0))
