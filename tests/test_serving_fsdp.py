"""Sharded scorer (serving/engine.py ``mesh=``): params split per-leaf
at rest, gathered at use by a separate jitted program — probs must be
BIT-identical to the replicated engine's, pad rows must not perturb
sibling rows, the bucket ladder must hold its edges (n == largest
bucket, n == 1, n > largest), and a hot swap / rolling reload must
reuse every warm program (0 recompiles, gather program included)."""

import numpy as np
import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
    default_tokenizer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
    device_tree_bytes,
    make_host_mesh,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.serving import (
    ScoreEngine,
    ScoringClient,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
)

BUCKETS = (1, 4, 8)

TEXTS = [
    f"Destination port is {p}. Flow duration is {d} microseconds. "
    f"Total forward packets are {n}."
    for p, d, n in [(80, 100, 3), (443, 2500, 9), (8080, 7, 1)]
]


@pytest.fixture(scope="module")
def setup(eight_devices):
    tok = default_tokenizer()
    model_cfg = ModelConfig.tiny(vocab_size=len(tok.vocab))
    trainer = Trainer(model_cfg, TrainConfig(), pad_id=tok.pad_id)
    # Host-side master copy: both engines place from the same numpy
    # bytes, so any probs difference is the engines', not placement's.
    import jax

    params = jax.tree.map(
        np.asarray, trainer.init_state(seed=0).params
    )
    mesh = make_host_mesh(2, devices=eight_devices[:2])
    return tok, model_cfg, trainer, params, mesh


@pytest.fixture(scope="module")
def engines(setup):
    tok, model_cfg, _trainer, params, mesh = setup
    rep = ScoreEngine(
        model_cfg, params, pad_id=tok.pad_id, buckets=BUCKETS, round_id=1
    )
    shard = ScoreEngine(
        model_cfg,
        params,
        pad_id=tok.pad_id,
        buckets=BUCKETS,
        round_id=1,
        mesh=mesh,
    )
    return rep, shard


def _ragged_batch(model_cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    L = model_cfg.max_len
    ids = rng.integers(1, model_cfg.vocab_size, size=(n, L), dtype=np.int32)
    mask = np.ones_like(ids)
    mask[:, L // 2:] = 0  # ragged lengths: real pad territory per row
    return ids, mask


def test_sharded_probs_bit_identical_to_replicated(engines, setup):
    """The serving crc contract at the bucket edges: a lone probe
    (n == 1), an exactly-full largest bucket (n == 8, zero pad rows),
    and a padded mid-size (n == 5) all return the replicated engine's
    exact bits — scalar score AND per-class softmax."""
    _tok, model_cfg, _trainer, _params, _mesh = setup
    rep, shard = engines
    for n in (1, BUCKETS[-1], 5):
        ids, mask = _ragged_batch(model_cfg, n, seed=n)
        p0, cp0, b0, _ = rep.score(ids, mask)
        p1, cp1, b1, _ = shard.score(ids, mask)
        assert b0 == b1
        np.testing.assert_array_equal(p0, p1)
        np.testing.assert_array_equal(cp0, cp1)


def test_sharded_static_bytes_are_split_per_chip(engines):
    """Shard-at-rest accounting: the sharded engine's params occupy
    ~1/N of the replicated engine's bytes on any one chip (<= 0.6 at
    N=2; replicated leaves keep full size)."""
    rep, shard = engines
    rep_bytes = device_tree_bytes(rep.snapshot()[0])
    shard_bytes = device_tree_bytes(shard.snapshot()[0])
    assert rep_bytes > 0
    assert shard_bytes / rep_bytes <= 0.6


def test_sharded_pad_rows_do_not_perturb_probs(engines, setup):
    """Per-row independence under sharding: the same 3 rows score the
    same bits whether padded up with PAD rows (n=3 -> bucket 4) or
    riding in a full batch of 8 real rows (bucket 8, no pads)."""
    _tok, model_cfg, _trainer, _params, _mesh = setup
    _rep, shard = engines
    ids, mask = _ragged_batch(model_cfg, 8, seed=3)
    alone, cp_alone, _, _ = shard.score(ids[:3], mask[:3])
    full, cp_full, _, _ = shard.score(ids, mask)
    np.testing.assert_array_equal(alone, full[:3])
    np.testing.assert_array_equal(cp_alone, cp_full[:3])


def test_sharded_bucket_overflow_raises(engines, setup):
    _tok, model_cfg, _trainer, _params, _mesh = setup
    _rep, shard = engines
    ids, mask = _ragged_batch(model_cfg, BUCKETS[-1] + 1)
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        shard.score(ids, mask)


def test_sharded_swap_reuses_warm_programs(setup):
    """A hot swap re-places onto the same shape-deterministic layout:
    after warmup, swapping new params and re-scoring every bucket must
    trace NOTHING — bucket programs and the gather program alike."""
    tok, model_cfg, trainer, params, mesh = setup
    eng = ScoreEngine(
        model_cfg, params, pad_id=tok.pad_id, buckets=BUCKETS, mesh=mesh
    )
    eng.warmup()
    import jax

    new_params = jax.tree.map(
        lambda a: np.asarray(a) + np.float32(1e-3), params
    )
    eng.swap(new_params, round_id=2)
    for n in (1, 3, 8):
        ids, mask = _ragged_batch(model_cfg, n, seed=n)
        _, _, _, rid = eng.score(ids, mask)
        assert rid == 2
    assert eng.ledger.recompiles() == []
    assert all(v == 1 for v in eng.compile_counts.values())
    # The gather program compiled exactly once too (its own ledger site).
    assert eng.ledger.compile_counts("serving.gather") == {("gather",): 1}


def test_sharded_swaps_under_load_keep_warm(setup):
    """The same guarantee with traffic on the engine: a scorer thread
    hammers two warm buckets while three swaps land. No score fails,
    every reply names a round that was served, and nothing retraces."""
    import threading
    import time

    import jax

    tok, model_cfg, trainer, params, mesh = setup
    eng = ScoreEngine(
        model_cfg, params, pad_id=tok.pad_id, buckets=BUCKETS, mesh=mesh
    )
    eng.warmup()
    ids, mask = _ragged_batch(model_cfg, 8)
    stop, rounds, errors = threading.Event(), set(), []

    def hammer():
        try:
            while not stop.is_set():
                rounds.add(eng.score(ids, mask)[3])
                rounds.add(eng.score(ids[:1], mask[:1])[3])
        except Exception as e:  # the finding, not a crash of the thread
            errors.append(e)

    scorer = threading.Thread(target=hammer, daemon=True)
    scorer.start()
    bumped = jax.tree.map(lambda a: np.asarray(a) + np.float32(1e-3), params)
    try:
        for rid in (1, 2, 3):
            eng.swap(bumped if rid % 2 else params, round_id=rid)
            deadline = time.monotonic() + 60.0
            while rid not in rounds and scorer.is_alive():
                assert time.monotonic() < deadline, "no score landed on the swap"
                time.sleep(0.005)
    finally:
        stop.set()
        scorer.join(timeout=60.0)
    assert not scorer.is_alive() and errors == []
    assert rounds <= {0, 1, 2, 3} and {1, 2, 3} <= rounds
    assert eng.ledger.recompiles() == []
    assert eng.ledger.compile_counts("serving.gather") == {("gather",): 1}


def test_sharded_replica_rolling_reload_keeps_warm(setup):
    """Fleet composition: a SHARDED FleetReplica behind ServingFleet
    survives a rolling reload — drain→swap lands the new params on the
    same shard layout, the round advances on the wire, and no warm
    bucket retraces."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.router.fleet import (
        FleetReplica,
        ServingFleet,
    )

    tok, model_cfg, trainer, params, mesh = setup
    rep = FleetReplica(
        0,
        model_cfg,
        params,
        tok,
        round_id=1,
        buckets=(1, 4),
        gather_window_s=0.002,
        mesh=mesh,
    ).start()
    fleet = ServingFleet([rep], probe_interval_s=0.2).start()
    try:
        rep.engine.warmup()
        with ScoringClient("127.0.0.1", fleet.port) as cli:
            assert cli.score(text=TEXTS[0])["round"] == 1
            import jax

            new_params = jax.tree.map(
                lambda a: np.asarray(a) + np.float32(1e-3), params
            )
            sweep = fleet.rolling_reload(new_params, round_id=2)
            assert [s["replica"] for s in sweep["replicas"]] == [0]
            assert cli.score(text=TEXTS[1])["round"] == 2
        assert rep.engine.ledger.recompiles() == []
    finally:
        fleet.close()
