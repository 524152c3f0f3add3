"""strategies/: pluggable server aggregation (ISSUE 16).

The contracts pinned here:

* registry — spec strings parse/build/reject exactly as `--strategy`
  documents them;
* math — FedAvg/FedProx are identities on the folded mean, Momentum and
  FedOpt match a hand-rolled optax reference bit-for-bit (same
  make_server_optimizer transform, same fp32 casts, same key order),
  HeadBoost boosts exactly the matching leaves;
* state — server-opt strategies reset on first round / shape change;
  StreamAgg's per-client strategy stats die with a dropped client;
* replay — a live loopback round per strategy stays crc-pinned
  bit-exact against the strategy replay over the clean survivor mean
  (the pure-transform contract that extends the crc gates);
* composition — the FedProx client step threads through the FSDP mesh
  trainer with the replicated engine's trajectory.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
    AggregationServer,
    wire,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm.stream_agg import (
    StreamAgg,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    FedConfig,
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.strategies import (
    STRATEGIES,
    FedAvg,
    FedOpt,
    FedProx,
    HeadBoost,
    Momentum,
    make_strategy,
    parse_strategy,
)


def _flat(rng, scale=1.0):
    return {
        "encoder/w": (scale * rng.normal(size=(4, 3))).astype(np.float32),
        "classifier/w": (scale * rng.normal(size=(3, 2))).astype(np.float32),
        "classifier/b": (scale * rng.normal(size=(2,))).astype(np.float32),
    }


# ------------------------------------------------------------------ registry
def test_parse_strategy_specs():
    assert parse_strategy("fedavg") == ("fedavg", {})
    assert parse_strategy("fedprox:mu=1.0") == ("fedprox", {"mu": 1.0})
    name, kw = parse_strategy("fedopt:opt=yogi,lr=0.05")
    assert name == "fedopt"
    assert kw == {"opt": "yogi", "lr": 0.05}  # strings stay, floats parse
    with pytest.raises(ValueError, match="unknown strategy"):
        parse_strategy("sgd")
    with pytest.raises(ValueError, match="bad strategy param"):
        parse_strategy("fedprox:mu")
    with pytest.raises(ValueError, match="bad strategy param"):
        parse_strategy("fedprox:=1.0")


def test_make_strategy_defaults_and_rejections():
    assert make_strategy(None).name == "fedavg"
    s = make_strategy("momentum:lr=0.5,momentum=0.8")
    assert (s.name, s.lr, s.momentum) == ("momentum", 0.5, 0.8)
    assert make_strategy(s) is s  # passthrough
    with pytest.raises(ValueError, match="rejected params"):
        make_strategy("fedprox:nu=1.0")  # unknown kwarg -> operator error
    assert sorted(STRATEGIES) == [
        "fedavg", "fedopt", "fedprox", "headboost", "momentum",
    ]


def test_param_validation():
    with pytest.raises(ValueError, match="mu"):
        FedProx(mu=0.0)
    with pytest.raises(ValueError, match="gamma"):
        HeadBoost(gamma=-1.0)
    with pytest.raises(ValueError, match="match"):
        HeadBoost(match="")
    with pytest.raises(ValueError, match="adam|yogi"):
        FedOpt(opt="sgd")
    with pytest.raises(ValueError, match="lr"):
        FedOpt(lr=0.0)
    with pytest.raises(ValueError, match="momentum"):
        Momentum(momentum=1.0)


# ---------------------------------------------------------------- identities
def test_fedavg_and_fedprox_are_identity_on_the_mean():
    rng = np.random.default_rng(0)
    prev, mean = _flat(rng), _flat(rng, 2.0)
    assert FedAvg().apply(prev, mean) is mean  # the historical fold
    prox = FedProx(mu=0.3)
    assert prox.apply(prev, mean) is mean  # server side untouched
    assert prox.client_mu() == pytest.approx(0.3)  # the client half
    assert FedAvg().client_mu() == 0.0
    assert prox.describe() == {"name": "fedprox", "params": {"mu": 0.3}}


def test_momentum_lr1_m0_reduces_to_the_mean():
    rng = np.random.default_rng(1)
    prev, mean = _flat(rng), _flat(rng, 2.0)
    out = Momentum(lr=1.0, momentum=0.0).apply(prev, mean)
    for k in mean:
        np.testing.assert_allclose(out[k], mean[k], rtol=1e-6)


def test_momentum_compounds_identical_round_deltas():
    """Heavy-ball memory: the same mean-vs-prev delta pushed twice must
    move the global further the second round."""
    strat = Momentum(lr=1.0, momentum=0.9)
    prev = {"w": np.zeros(4, np.float32)}
    delta = np.full(4, 0.01, np.float32)
    g1 = strat.apply(prev, {"w": prev["w"] + delta}, round_no=1)
    step1 = np.abs(g1["w"] - prev["w"]).mean()
    g2 = strat.apply(g1, {"w": g1["w"] + delta}, round_no=2)
    step2 = np.abs(g2["w"] - g1["w"]).mean()
    assert step2 > step1 * 1.5


@pytest.mark.parametrize(
    "strat, fed_kw",
    [
        (Momentum(lr=0.7, momentum=0.9),
         dict(server_opt="momentum", server_lr=0.7, server_momentum=0.9)),
        (FedOpt(opt="adam", lr=0.1),
         dict(server_opt="adam", server_lr=0.1)),
        (FedOpt(opt="yogi", lr=0.1),
         dict(server_opt="yogi", server_lr=0.1)),
    ],
)
def test_server_opt_matches_optax_reference_bitexact(strat, fed_kw):
    """Two rounds vs a hand-rolled loop over the SAME
    make_server_optimizer transform: pseudo-gradient prev - mean,
    persistent state, fp32 casts in sorted-key order — bit-for-bit."""
    import optax

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.fedavg import (
        make_server_optimizer,
    )

    rng = np.random.default_rng(2)
    tx = make_server_optimizer(FedConfig(**fed_kw))
    prev = strat.apply(None, _flat(rng))  # round 1: mean adopted as-is
    ref_prev, opt_state = dict(prev), None
    for rnd in (2, 3):
        mean = _flat(rng, 1.0 + 0.1 * rnd)
        live = strat.apply(prev, mean, round_no=rnd)
        p32 = {k: np.asarray(ref_prev[k], np.float32) for k in sorted(mean)}
        g = {k: p32[k] - np.asarray(mean[k], np.float32) for k in sorted(mean)}
        if opt_state is None:
            opt_state = tx.init(p32)
        updates, opt_state = tx.update(g, opt_state, p32)
        ref = optax.apply_updates(p32, updates)
        ref_prev = {k: np.asarray(ref[k], np.float32) for k in sorted(ref)}
        for k in mean:
            np.testing.assert_array_equal(live[k], ref_prev[k])
        prev = live


def test_server_opt_resets_on_first_round_and_shape_change():
    rng = np.random.default_rng(3)
    strat = FedOpt(opt="adam", lr=0.1)
    mean = _flat(rng)
    out = strat.apply(None, mean)  # no global yet: the mean IS the global
    assert out is mean and strat._opt_state is None
    strat.apply(out, _flat(rng, 2.0), round_no=2)
    assert strat._opt_state is not None
    # Shape change (model swap): adopt the new mean, restart the state.
    grown = {"w": np.ones((8, 8), np.float32)}
    out = strat.apply(mean, grown, round_no=3)
    assert out is grown and strat._opt_state is None


def test_headboost_boosts_exactly_the_matching_leaves():
    prev = {
        "classifier/w": np.zeros(3, np.float32),
        "encoder/w": np.zeros(3, np.float32),
    }
    mean = {
        "classifier/w": np.ones(3, np.float32),
        "encoder/w": np.ones(3, np.float32),
    }
    out = HeadBoost(gamma=2.0).apply(prev, mean)
    np.testing.assert_array_equal(out["classifier/w"], np.full(3, 2.0))
    np.testing.assert_array_equal(out["encoder/w"], np.ones(3))
    # No previous global to measure an update against: exact FedAvg.
    assert HeadBoost(gamma=2.0).apply(None, mean) is mean
    # No leaf matches: exact FedAvg values.
    out = HeadBoost(gamma=2.0, match="does-not-exist").apply(prev, mean)
    for k in mean:
        np.testing.assert_array_equal(out[k], mean[k])


# ------------------------------------------------- StreamAgg strategy stats
def _register_dense(agg, cid, flat, n_samples):
    agg.register(
        cid, keys=tuple(sorted(flat)), n_samples=n_samples
    )
    agg.add_dense(cid, flat)


def test_stream_agg_client_stats_snapshot_and_weights():
    rng = np.random.default_rng(4)
    agg = StreamAgg()
    _register_dense(agg, 0, _flat(rng), 40)  # honest
    _register_dense(agg, 1, _flat(rng), 10)  # lazy: 0.25x the rows
    stats = agg.client_stats()
    assert sorted(stats) == [0, 1]
    assert stats[0]["weight"] == 40.0 and stats[1]["weight"] == 10.0
    assert stats[0]["bytes"] > 0 and stats[0]["scale"] == 1.0
    stats[0]["weight"] = -1  # snapshot copy: the round's view is frozen
    assert agg.client_stats()[0]["weight"] == 40.0


def test_stream_agg_drop_before_fold_purges_strategy_stats():
    rng = np.random.default_rng(5)
    agg = StreamAgg()
    _register_dense(agg, 0, _flat(rng), 10)
    _register_dense(agg, 1, _flat(rng), 10)
    assert agg.drop_client(1) is True  # nothing folded: clean removal
    assert sorted(agg.client_stats()) == [0]
    agg.stats()  # invariant: strategy stats ⊆ intents (would assert)
    mean = agg.finalize([0], [10.0])  # single survivor round
    strat = Momentum(lr=1.0, momentum=0.9)
    out = strat.apply(None, mean)
    assert out is mean  # first-global adoption, crc-preserving


def test_stream_agg_poisoned_drop_still_purges_strategy_stats():
    """A folded contributor dying poisons the round — but the strategy
    view must not keep the ghost: stats die with the intent even on the
    failure path (the stats() invariant)."""
    rng = np.random.default_rng(6)
    agg = StreamAgg()
    _register_dense(agg, 0, _flat(rng), 10)
    _register_dense(agg, 1, _flat(rng), 10)
    agg.freeze([0, 1], [10.0, 10.0])  # both complete: every leaf folds
    assert agg.drop_client(0) is False
    assert agg.poisoned and "leaf folds already consumed" in agg.poisoned
    assert sorted(agg.client_stats()) == [1]
    agg.stats()  # invariant holds on the poisoned path too


def test_all_lazy_fleet_weights_still_normalize():
    """Every client lazy (tiny but nonzero sample counts): the fold
    normalizes over the small weights and the strategies see the round
    through client_stats unchanged."""
    rng = np.random.default_rng(7)
    agg = StreamAgg()
    flats = [_flat(rng), _flat(rng), _flat(rng)]
    for cid, f in enumerate(flats):
        _register_dense(agg, cid, f, 2)  # all-lazy: equal tiny shards
    mean = agg.finalize([0, 1, 2], [2.0, 2.0, 2.0])
    expected = {
        k: (flats[0][k] / 3 + flats[1][k] / 3 + flats[2][k] / 3)
        for k in flats[0]
    }
    for k in expected:
        np.testing.assert_allclose(mean[k], expected[k], rtol=1e-5)
    stats = agg.client_stats()
    assert [stats[c]["weight"] for c in (0, 1, 2)] == [2.0, 2.0, 2.0]


# ----------------------------------------------------- server wiring guards
def test_server_refuses_strategy_with_secure_agg_and_dp():
    with pytest.raises(ValueError, match="secure aggregation"):
        AggregationServer(
            num_clients=2, secure_agg=True, strategy="momentum"
        )
    with pytest.raises(ValueError, match="central DP"):
        AggregationServer(num_clients=2, dp_clip=1.0, strategy="fedopt")
    with pytest.raises(ValueError, match="unknown strategy"):
        AggregationServer(num_clients=2, strategy="sgd")


def test_server_set_strategy_swaps_between_rounds():
    with AggregationServer(port=0, num_clients=1) as server:
        assert server.strategy.name == "fedavg"
        server.set_strategy("headboost:gamma=1.5")
        assert server.strategy.name == "headboost"
        assert server.strategy.gamma == pytest.approx(1.5)
    with AggregationServer(port=0, num_clients=2, dp_clip=1.0) as server:
        with pytest.raises(ValueError, match="secure-agg/DP"):
            server.set_strategy("momentum")


def test_root_refuses_relay_with_mismatched_strategy():
    """Split-brain guard: a relay stamping a different strategy id on
    its upward upload is refused loudly (the meta check fires before
    any round state is touched)."""
    with AggregationServer(port=0, num_clients=2) as server:
        with pytest.raises(wire.WireError, match="split-brain"):
            server._register_tree_meta(
                None, None, 7, {wire.STRATEGY_META_KEY: "momentum"}
            )
        # Matching stamp (dict form, as the relay sends it) passes.
        assert server._register_tree_meta(
            None, None, 7, {wire.STRATEGY_META_KEY: {"name": "fedavg"}}
        )
        # Absent stamp = old peer, accepted as-is.
        assert server._register_tree_meta(None, None, 7, {})


# ------------------------------------------------ live rounds, crc-pinned
def _live_round_bitexact(tmp_path, spec):
    """Two live loopback rounds: the transformed aggregate must be
    crc-pinned bit-exact against the strategy replay over the clean
    survivor mean — round 2 exercises the stateful prev-global path
    (momentum memory, adam moments, head deltas)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.faults.scenario import (
        CellSpec,
        ScenarioConfig,
        run_cell,
    )

    cfg = ScenarioConfig(
        num_clients=3, rounds=2, payload_kb=24, deadline_s=6.0,
        personas=("lazy",), partitions=("iid",),
    )
    res = run_cell(
        CellSpec(
            name=f"lazy|iid|{spec}",
            personas=("lazy", "honest", "honest"),
            partition="iid",
            strategy=spec,
        ),
        cfg,
        str(tmp_path),
    )
    assert [r.ok for r in res.rounds] == [True, True], res.notes
    for r in res.rounds:
        assert r.bitexact is True, (spec, r, res.notes)
    assert res.rounds[-1].contributors == [0, 1, 2]


def test_live_round_bitexact_momentum(tmp_path):
    """The fast lane's one live strategy cell: momentum is the fully
    stateful representative (server optimizer memory across rounds)."""
    _live_round_bitexact(tmp_path, "momentum:lr=1.0,momentum=0.6")


@pytest.mark.slow
@pytest.mark.parametrize(
    "spec",
    ["fedprox:mu=0.5", "fedopt:opt=yogi,lr=0.1", "headboost:gamma=2.0"],
)
def test_live_round_bitexact_per_strategy(tmp_path, spec):
    _live_round_bitexact(tmp_path, spec)


def test_fedprox_lifts_noniid_accuracy_over_fedavg(tmp_path):
    """`fedtpu scenario --train` on its hardest cell: Dirichlet alpha=0.1
    with the lazy persona on client 0, where the big mixed-label shard
    sits on the lazy client and plain averaging stalls near chance
    (48.44% at this seed). FedProx's anchor has to keep lifting the
    held-out accuracy of the final aggregate (67.19%, +18.75 points; the
    floor of 5 trips only when the strategy stops helping at all), every
    trained round crc-exact against the strategy replay."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.faults.scenario import (
        ScenarioConfig,
        contract_violations,
        run_matrix,
    )

    cfg = ScenarioConfig(
        num_clients=3, rounds=5, personas=("lazy",),
        partitions=("dirichlet",), dirichlet_alpha=0.1, seed=5,
        deadline_s=20.0, auth_cell=False, train=True,
        strategies=("fedprox:mu=1.0",),
    )
    results, _grid = run_matrix(cfg, str(tmp_path))
    accuracy = {r.spec.strategy: r.accuracy for r in results}
    assert set(accuracy) == {"fedavg", "fedprox:mu=1.0"}
    assert accuracy["fedprox:mu=1.0"] - accuracy["fedavg"] >= 5.0, accuracy
    assert contract_violations(results) == []
    for r in results:
        assert r.ok_rounds == r.exact_rounds == cfg.rounds, r.notes


# --------------------------------------------------- FedProx client engine
def _batch(mcfg, rng, B=8):
    L = mcfg.max_len
    return {
        "input_ids": rng.integers(
            0, mcfg.vocab_size, (B, L)
        ).astype(np.int32),
        "attention_mask": np.ones((B, L), np.int32),
        "labels": rng.integers(0, 2, B).astype(np.int32),
    }


@pytest.mark.slow
def test_prox_step_vanishes_at_anchor_and_pulls_at_large_mu():
    """At params == anchor the proximal gradient mu*(p - anchor) is
    exactly zero, so the first prox step matches the plain step; a large
    mu then keeps the trajectory measurably closer to the anchor."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train import (
        Trainer,
    )

    mcfg = ModelConfig.tiny()
    rng = np.random.default_rng(8)
    batch = _batch(mcfg, rng)

    def run(mu, steps):
        tr = Trainer(
            mcfg, TrainConfig(learning_rate=1e-3, seed=0, prox_mu=mu)
        )
        state = tr.init_state(seed=0)
        anchor = jax.tree.map(jnp.copy, state.params)
        for _ in range(steps):
            if mu > 0.0:
                state, _ = tr.train_step(state, batch, anchor)
            else:
                state, _ = tr.train_step(state, batch)
        dist = sum(
            float(np.abs(np.asarray(p) - np.asarray(a)).sum())
            for p, a in zip(
                jax.tree.leaves(state.params), jax.tree.leaves(anchor)
            )
        )
        return tr.host_params(state), dist

    # One mu for both halves keeps this at two compiled programs: the
    # prox gradient mu*(p - anchor) is exactly zero at p == anchor no
    # matter how large mu is.
    plain, d_plain = run(0.0, 1)
    prox, _ = run(50.0, 1)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(prox)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)
    _, d_free = run(0.0, 5)
    _, d_anchored = run(50.0, 5)
    assert d_anchored < d_free * 0.9, (d_anchored, d_free)


def test_adopted_aggregate_becomes_the_next_prox_anchor():
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train import (
        Trainer,
    )

    mcfg = ModelConfig.tiny()
    tr = Trainer(mcfg, TrainConfig(learning_rate=1e-3, seed=0, prox_mu=0.1))
    state = tr.init_state(seed=0)
    assert tr._prox_anchor is None
    agg = jax.tree.map(
        lambda p: np.asarray(p) + 0.5, tr.host_params(state)
    )
    state = tr.adopt_aggregate(state, agg)
    anchor = tr._round_anchor(state)
    for a, p in zip(jax.tree.leaves(anchor), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))


@pytest.mark.slow
def test_fsdp_prox_trajectory_matches_replicated(eight_devices):
    """`--fsdp --strategy fedprox` composition: the prox term rides the
    RAW (shard-at-rest) params outside the remat region, so the FSDP
    trajectory must track the replicated engine's within reduction-order
    ulps — and the prox pull must actually be active (differ from the
    mu=0 trajectory)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        default_tokenizer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.pipeline import (
        TokenizedSplit,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
        make_host_mesh,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train import (
        Trainer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.client_mesh import (
        FsdpMeshTrainer,
    )

    tok = default_tokenizer()
    L = 32
    mcfg = ModelConfig.tiny(
        vocab_size=len(tok.vocab), max_len=L, max_position_embeddings=2 * L
    )
    tcfg = TrainConfig(
        prng_impl="threefry2x32", learning_rate=1e-3, epochs_per_round=1,
        log_every=0, seed=0, prox_mu=0.05,
    )
    rng = np.random.default_rng(9)
    split = TokenizedSplit(
        rng.integers(0, mcfg.vocab_size, (48, L)).astype(np.int32),
        np.ones((48, L), np.int32),
        rng.integers(0, 2, 48).astype(np.int32),
    )

    def run(trainer):
        state, losses = trainer.fit(
            trainer.init_state(), split, batch_size=8
        )
        return trainer.host_params(state), losses

    h_plain, l_plain = run(Trainer(mcfg, tcfg, pad_id=tok.pad_id))
    h_fsdp, l_fsdp = run(
        FsdpMeshTrainer(
            mcfg, tcfg, mesh=make_host_mesh(2), pad_id=tok.pad_id
        )
    )
    np.testing.assert_allclose(l_plain, l_fsdp, rtol=1e-5)
    # Wider than the mu=0 pin (2e-6, test_mesh_fsdp): the prox-grad
    # term's reduce-scatter rounding feeds Adam's rsqrt every step, so
    # the reduction-order ulps compound over the epoch. Still fp32
    # noise, not divergence — the per-epoch loss above is equal.
    for a, b in zip(jax.tree.leaves(h_plain), jax.tree.leaves(h_fsdp)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)
    import dataclasses

    h_free = run(
        Trainer(
            mcfg, dataclasses.replace(tcfg, prox_mu=0.0), pad_id=tok.pad_id
        )
    )
    deltas = [
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(jax.tree.leaves(h_plain), jax.tree.leaves(h_free))
    ]
    assert max(deltas) > 0.0  # mu=0.05 measurably bends the trajectory
