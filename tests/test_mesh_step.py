"""The mesh tier's lockstep step, per shard (train/fedsteps.py
``_step_body``): inside one ``shard_map`` over ``clients x data`` each
device steps its own clients on its own rows and draws dropout bits for
those rows only. Pinned here: it is the packed step's mathematics, the
gradients summed over ``data`` are the batch mean's, data shards draw independent
masks, replicas stay bit-equal, and the state ``init_state`` hands the step
is placed as the step returns it, so the step is traced once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.pipeline import (
    TokenizedSplit,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.profile import (
    default_ledger,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
    make_mesh,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train import (
    FederatedTrainer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.fedsteps import (
    shard_step_keys,
)

L, VOCAB, BATCH = 16, 64, 8


def _cfg(clients, *, dropout=True, prng="threefry2x32", **fed_kw):
    off = {} if dropout else dict(dropout=0.0, attention_dropout=0.0, head_dropout=0.0)
    return ExperimentConfig(
        model=ModelConfig.tiny(
            vocab_size=VOCAB, max_len=L, max_position_embeddings=L, **off
        ),
        data=DataConfig(max_len=L, batch_size=BATCH, eval_batch_size=BATCH),
        train=TrainConfig(learning_rate=1e-3, seed=0, prng_impl=prng),
        fed=FedConfig(num_clients=clients, **fed_kw),
        mesh=MeshConfig(clients=clients, data=1),
    )


def _trainer(cfg, devices, clients, data):
    return FederatedTrainer(
        cfg, mesh=make_mesh(clients, data, devices=devices[: clients * data])
    )


def _rows(C, n, seed=0):
    rng = np.random.default_rng(seed)
    return TokenizedSplit(
        rng.integers(1, VOCAB, (C, n, L)).astype(np.int32),
        np.ones((C, n, L), np.int32),
        rng.integers(0, 2, (C, n)).astype(np.int32),
    )


def _batches(split, steps):
    return [
        {
            "input_ids": split.input_ids[:, i * BATCH : (i + 1) * BATCH],
            "attention_mask": split.attention_mask[:, i * BATCH : (i + 1) * BATCH],
            "labels": split.labels[:, i * BATCH : (i + 1) * BATCH],
        }
        for i in range(steps)
    ]


def _mesh_steps(trainer, batches):
    """``len(batches)`` lockstep launches of ``train_step``: the losses
    ``[steps, C]`` and the final state."""
    state = trainer.init_state()
    anchor = (
        (jax.tree.map(jnp.copy, state.params),)
        if trainer.cfg.fed.prox_mu > 0.0
        else ()
    )
    losses = []
    for b in batches:
        state, loss = trainer.train_step(state, trainer._feed(b), *anchor)
        losses.append(np.asarray(loss))
    return np.stack(losses), state


def _assert_params_close(a, b, atol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


@pytest.mark.parametrize("mu", [0.0, 0.1])
def test_per_shard_step_matches_packed_step(eight_devices, mu):
    """Threefry, mesh 2x1 (one data shard: nothing but the counter is
    folded into the keys): two lockstep steps give each client the losses
    and parameters of the packed per-client step, FedProx included."""
    C = 2
    cfg = _cfg(C, prox_mu=mu)
    batches = _batches(_rows(C, 2 * BATCH), 2)
    losses, state = _mesh_steps(_trainer(cfg, eight_devices, 2, 1), batches)

    packed = _trainer(cfg, eight_devices, 1, 1)
    assert packed._packed_eligible()
    start = packed.init_state()
    anchors = [packed._slice_client(start.params, c) for c in range(C)]
    cstates = packed._unstack_cstates(start)
    step_fn = packed._build_packed_step()
    want = np.zeros_like(losses)
    for i, b in enumerate(batches):
        for c in range(C):
            cb = {k: v[c] for k, v in b.items()}
            args = (cstates[c], cb) + ((anchors[c],) if mu > 0.0 else ())
            cstates[c], task = step_fn(*args)
            want[i, c] = float(task)
    np.testing.assert_allclose(losses, want, atol=1e-4)
    for c in range(C):
        _assert_params_close(
            packed._slice_client(state.params, c), cstates[c][0], atol=1.5e-3
        )
    assert int(state.step) == int(cstates[0][2]) == 2


@pytest.mark.parametrize("mu", [0.0, 0.1])
def test_data_shards_mean_is_the_batch_mean(eight_devices, mu):
    """Dropout off, mesh 2x2 against 2x1: the shards' gradients, summed
    over ``data`` by autodiff under the 1/shards of the objective, and the
    ``pmean`` of their losses are the whole batch's; the FedProx term,
    which every shard holds whole, counts once."""
    C = 2
    cfg = _cfg(C, dropout=False, prox_mu=mu)
    batches = _batches(_rows(C, 2 * BATCH), 2)
    l1, s1 = _mesh_steps(_trainer(cfg, eight_devices, 2, 1), batches)
    l2, s2 = _mesh_steps(_trainer(cfg, eight_devices, 2, 2), batches)
    np.testing.assert_allclose(l2, l1, atol=1e-5)
    # Adam's normalisation turns a reduction-order ulp in a near-zero
    # gradient into a fraction of one step (lr 1e-3).
    _assert_params_close(s2.params, s1.params, atol=2e-4)


@pytest.mark.parametrize("prng", ["rbg", "threefry2x32"])
def test_data_shards_draw_independent_masks(eight_devices, prng):
    """The keys the body derives, probed without a model on mesh 2x2: the
    two data shards of a client draw different masks for their rows, two
    clients differ, the kept share is the rate, and with one data shard
    the key is the packed step's (the counter folded in, nothing else)."""
    mesh = make_mesh(2, 2, devices=eight_devices[:4])
    C, rows, width, rate = 4, 8, 512, 0.1
    rngs = jax.random.split(jax.random.key(3, impl=prng), C)
    step = jnp.int32(5)

    def probe(rngs, step):
        keys = shard_step_keys(rngs, step, True)
        return jax.vmap(
            lambda k: jax.random.bernoulli(k, 1.0 - rate, (rows // 2, width))
        )(keys)

    keep = np.asarray(
        jax.jit(
            jax.shard_map(
                probe,
                mesh=mesh,
                in_specs=(P("clients"), P()),
                out_specs=P("clients", "data"),
            )
        )(rngs, step)
    )
    assert keep.shape == (C, rows, width)
    halves = keep.reshape(C, 2, rows // 2, width)
    for c in range(C):
        assert not np.array_equal(halves[c, 0], halves[c, 1])
    assert not np.array_equal(keep[0], keep[1])
    n = keep.size
    sigma = np.sqrt(rate * (1 - rate) / n)
    assert abs(keep.mean() - (1 - rate)) < 5 * sigma
    one_shard = shard_step_keys(rngs, step, False)
    want = jax.vmap(jax.random.fold_in, in_axes=(0, None))(rngs, step)
    np.testing.assert_array_equal(
        jax.random.key_data(one_shard), jax.random.key_data(want)
    )


@pytest.fixture(scope="module")
def two_rounds(eight_devices, tmp_path_factory):
    """``init_state`` -> two whole rounds (fit, aggregate, reset) on mesh
    2x2 under the production generator, 2 clients a mesh row, then a save,
    a restore and one more fit. The ledger is the process's, so the traces
    are counted as differences."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.checkpoint import (
        Checkpointer,
    )

    C = 4
    cfg = _cfg(C, prng="rbg")
    trainer = _trainer(cfg, eight_devices, 2, 2)
    train = _rows(C, 2 * BATCH, seed=1)
    weights = np.array([1.0, 2.0, 3.0, 4.0])
    ledger = default_ledger()
    before = ledger.compile_counts("fed.train_step")
    state = first = trainer.init_state()
    placements = {
        "step": (first.step.sharding, first.step.committed),
        "rngs": (first.rngs.sharding, first.rngs.committed),
    }
    for r in range(2):
        state, losses = trainer.fit_local(state, train, epoch_offset=r)
        assert np.isfinite(losses).all()
        state = trainer.round_aggregate(state, round_index=r, weights=weights)
        aggregated = jax.tree.map(np.asarray, state.params)
        state = trainer.reset_optimizer(state)
    after_rounds = ledger.compile_counts("fed.train_step")
    directory = str(tmp_path_factory.mktemp("mesh_step") / "ckpt")
    with Checkpointer(directory) as ckpt:
        ckpt.save(2, state)
        ckpt.wait()
        restored = ckpt.restore(trainer.init_state())
    restored, losses = trainer.fit_local(restored, train, epoch_offset=2)
    assert np.isfinite(losses).all()
    after_restore = ledger.compile_counts("fed.train_step")
    return dict(
        trainer=trainer, placements=placements, aggregated=aggregated,
        before=before, after_rounds=after_rounds, after_restore=after_restore,
        sig=(C, BATCH, L),
    )


def test_init_state_is_placed_as_the_step_returns_it(two_rounds):
    sh = two_rounds["trainer"].sh
    assert two_rounds["placements"]["step"] == (sh.replicated, True)
    assert two_rounds["placements"]["rngs"] == (sh.client, True)


def test_step_is_traced_once_over_two_rounds(two_rounds):
    """FedAvg and the reset hand back the placements ``init_state`` gave,
    so the second call of ``train_step`` and every later one hit the first
    call's trace."""
    before = two_rounds["before"]
    new = {
        sig: n - before.get(sig, 0)
        for sig, n in two_rounds["after_rounds"].items()
        if n != before.get(sig, 0)
    }
    assert new == {two_rounds["sig"]: 1}


def test_step_is_not_traced_again_after_a_restore(two_rounds):
    assert two_rounds["after_restore"] == two_rounds["after_rounds"]


def test_replicas_bit_equal_after_per_shard_fit_and_fedavg(two_rounds):
    """What the 2x2 cell's ``correct`` asserts on the chip: after a fit on
    the per-shard step and ``round_aggregate`` every client's row of every
    leaf is the same to the bit, and finite."""
    for leaf in jax.tree.leaves(two_rounds["aggregated"]):
        assert np.isfinite(leaf).all()
        for c in range(1, leaf.shape[0]):
            np.testing.assert_array_equal(leaf[c], leaf[0])
