"""FSDP client mesh (ISSUE 15): shard-at-rest params/optimizer over the
local ``data`` axis with gather-at-use (train/client_mesh.FsdpMeshTrainer).

The contracts pinned here:

* trajectory — FSDP vs replicated-mesh vs single-device under threefry:
  metrics EQUAL, params within fp32 reduction-order ulps (the grad
  reduce-scatter may sum partials in a different order than the
  all-reduce — the PR-2 documented class, allclose-pinned);
* memory — per-chip static-state bytes (params + Adam moments) scale
  ~1/N (exact addressable-shard accounting);
* wire — host-gather -> adopt (scatter onto shards) -> host-gather is
  byte/crc-exact, streamed-reply leaves scatter DIRECTLY onto their
  shard specs, and a live `--fsdp` loopback round composes with
  streamed uploads and secure-agg+DP unchanged;
* checkpoint — shard -> save -> restore -> shard is leaf-bit-exact.
"""

import csv
import json
import os
import threading

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli import (
    main,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
    AggregationServer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.comm import (
    wire,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
    default_tokenizer,
    make_synthetic,
    make_all_client_splits,
    tokenize_client,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
    device_tree_bytes,
    fsdp_dim,
    fsdp_spec,
    fsdp_tree_shardings,
    make_host_mesh,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.client_mesh import (
    FsdpMeshTrainer,
    MeshTrainer,
    make_client_trainer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
)

L = 32


@pytest.fixture(scope="module")
def tok():
    return default_tokenizer()


def _cfg(tok, *, data=2, fsdp=True, prng="threefry2x32"):
    model = ModelConfig.tiny(
        vocab_size=len(tok.vocab), max_len=L, max_position_embeddings=2 * L
    )
    return ExperimentConfig(
        model=model,
        data=DataConfig(max_len=L, batch_size=8, data_fraction=0.3),
        train=TrainConfig(
            prng_impl=prng,
            epochs_per_round=1,
            learning_rate=1e-3,
            log_every=0,
        ),
        fed=FedConfig(num_clients=1),
        mesh=MeshConfig(clients=1, data=data, fsdp=fsdp),
    )


@pytest.fixture(scope="module")
def client_data(tok):
    cfg = _cfg(tok)
    df = make_synthetic("cicids2017", 400, seed=42)
    splits = make_all_client_splits(df, 1, cfg.data)
    return tokenize_client(splits[0], tok, max_len=L)


# ----------------------------------------------------------- spec builders
def test_fsdp_spec_picks_largest_divisible_dim():
    assert fsdp_dim((6, 4), 2) == 0  # largest divisible
    assert fsdp_dim((4, 6), 2) == 1
    assert fsdp_dim((3, 5), 2) is None  # nothing divides
    assert fsdp_dim((), 2) is None  # scalar
    assert fsdp_dim((8, 8), 2) == 0  # tie -> lowest index
    assert fsdp_dim((8,), 1) is None  # one shard = replicated
    assert fsdp_spec((6, 4), 2) == P("data", None)
    assert fsdp_spec((4, 6), 2) == P(None, "data")
    assert fsdp_spec((3, 5), 2) == P()
    # Deterministic: the wire tier derives the same layout independently.
    assert fsdp_spec((1024, 768), 4) == fsdp_spec((1024, 768), 4)


def test_fsdp_tree_shardings_replicates_scalars_and_keys(eight_devices):
    mesh = make_host_mesh(2)
    rng = jax.random.key(0, impl="threefry2x32")
    tree = {
        "w": np.zeros((8, 4), np.float32),
        "b": np.zeros((3,), np.float32),  # undividable
        "step": np.zeros((), np.int32),
        "rng": rng,
    }
    sh = fsdp_tree_shardings(tree, mesh)
    assert sh["w"].spec == P("data", None)
    assert sh["b"].spec == P()
    assert sh["step"].spec == P()
    assert sh["rng"].spec == P()


def test_mesh_config_validates_fsdp():
    with pytest.raises(ValueError, match="data >= 2"):
        MeshConfig(clients=1, data=1, fsdp=True)
    with pytest.raises(ValueError, match="seq"):
        MeshConfig(clients=1, data=2, seq=2, fsdp=True)


def test_make_client_trainer_dispatches_fsdp(tok, eight_devices):
    t = make_client_trainer(_cfg(tok))
    assert isinstance(t, FsdpMeshTrainer)
    assert t.n_shards == 2
    # fsdp off keeps the replicated meshed trainer
    t = make_client_trainer(_cfg(tok, fsdp=False))
    assert isinstance(t, MeshTrainer) and not isinstance(t, FsdpMeshTrainer)


# ----------------------------------------------------- trajectory + memory
def test_fsdp_matches_replicated_and_single_device_trajectory(
    tok, client_data, eight_devices
):
    """The headline identity: FSDP over 2 shards vs the plain engine —
    same threefry trajectory, equal final metrics, params within
    reduction-order ulps (the reduce-scatter vs all-reduce class)."""
    cfg = _cfg(tok)
    plain = Trainer(cfg.model, cfg.train, pad_id=tok.pad_id)
    s0, _ = plain.fit(plain.init_state(), client_data.train, batch_size=8)
    m0 = plain.evaluate_state(s0, client_data.test)
    h0 = plain.host_params(s0)
    fsdp = FsdpMeshTrainer(
        cfg.model, cfg.train, mesh=make_host_mesh(2), pad_id=tok.pad_id
    )
    sf, _ = fsdp.fit(fsdp.init_state(), client_data.train, batch_size=8)
    mf = fsdp.evaluate_state(sf, client_data.test)
    for k in ("Accuracy", "Precision", "Recall", "F1-Score"):
        assert m0[k] == mf[k], (k, m0[k], mf[k])
    np.testing.assert_allclose(m0["Loss"], mf["Loss"], rtol=1e-5)
    np.testing.assert_array_equal(
        m0["confusion_matrix"], mf["confusion_matrix"]
    )
    hf = fsdp.host_params(sf)
    for a, b in zip(jax.tree.leaves(h0), jax.tree.leaves(hf)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)


def test_fsdp_static_state_shards_at_rest(tok, eight_devices):
    """The memory contract: per-chip params+opt bytes scale ~1/N, and
    the leaves actually live on their shard specs (not just constrained
    transiently inside the step)."""
    cfg = _cfg(tok)
    rep = MeshTrainer(
        cfg.model, cfg.train, mesh=make_host_mesh(2), pad_id=tok.pad_id
    )
    fsdp = FsdpMeshTrainer(
        cfg.model, cfg.train, mesh=make_host_mesh(2), pad_id=tok.pad_id
    )
    sr = rep.init_state()
    sf = fsdp.init_state()
    rep_bytes = device_tree_bytes((sr.params, sr.opt_state))
    fsdp_bytes = device_tree_bytes((sf.params, sf.opt_state))
    ratio = fsdp_bytes / rep_bytes
    assert ratio <= 0.6, (fsdp_bytes, rep_bytes, ratio)
    sharded = [
        leaf
        for leaf in jax.tree.leaves(sf.params)
        if getattr(leaf.sharding, "spec", P()) != P()
    ]
    assert sharded, "no param leaf is sharded at rest"
    # The step keeps the layout: one train step in, leaves still sharded.
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": rng.integers(
            0, cfg.model.vocab_size, (8, L)
        ).astype(np.int32),
        "attention_mask": np.ones((8, L), np.int32),
        "labels": rng.integers(0, 2, 8).astype(np.int32),
    }
    sf2, _ = fsdp.train_step(sf, batch)
    assert device_tree_bytes((sf2.params, sf2.opt_state)) == fsdp_bytes


def test_fsdp_backward_regathers_instead_of_retaining(tok, eight_devices):
    """The peak-memory MECHANISM (invisible to the at-rest byte count,
    which is taken outside the step): the rematted FSDP loss saves NO
    gathered full-size weight as a residual — every saved value is a
    region argument (the shards at rest) or an activation — so the
    backward RE-GATHERS. Built exactly as make_fsdp_train_step builds
    it. Guards the remat construction: wrapping only the gather (or
    using the stock except-these-names policy without the
    sharding-constraint exclusion) saves the gathered tree and fails
    this test."""
    import contextlib
    import io

    from jax.ad_checkpoint import print_saved_residuals
    from jax.sharding import NamedSharding

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        FSDP_GATHER_NAME,
        _tag_gather,
        fsdp_remat_loss,
        loss_fn,
    )

    cfg = _cfg(tok)
    mesh = make_host_mesh(2)
    fsdp = FsdpMeshTrainer(
        cfg.model, cfg.train, mesh=mesh, pad_id=tok.pad_id
    )
    state = fsdp.init_state()
    replicated = NamedSharding(mesh, P())

    def gather(p):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, replicated), p
        )

    tagged = _tag_gather(gather)
    loss_rm = fsdp_remat_loss(
        lambda p, batch, rng: loss_fn(fsdp.model, tagged(p), batch, rng)
    )
    rng = np.random.default_rng(1)
    batch = {
        "input_ids": jnp_like(
            rng.integers(0, cfg.model.vocab_size, (8, L)).astype(np.int32)
        ),
        "attention_mask": jnp_like(np.ones((8, L), np.int32)),
        "labels": jnp_like(rng.integers(0, 2, 8).astype(np.int32)),
    }
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        print_saved_residuals(
            loss_rm,
            state.params,
            batch,
            jax.random.key(0, impl=cfg.train.prng_impl),
        )
    leaked = [
        line
        for line in buf.getvalue().splitlines()
        if FSDP_GATHER_NAME in line and "argument" not in line
    ]
    assert not leaked, leaked


def jnp_like(arr):
    import jax.numpy as jnp

    return jnp.asarray(arr)


# ----------------------------------------------------------- wire boundary
def test_fsdp_gather_scatter_round_trip_crc_exact(tok, eight_devices):
    """The wire-exchange gather contract:
    host-gather -> adopt (scatter onto shards, fresh sharded Adam) ->
    host-gather is byte- and crc-exact, so secure-agg/DP masking sees
    the identical flat vector a single-device client would produce."""
    cfg = _cfg(tok)
    plain = Trainer(cfg.model, cfg.train, pad_id=tok.pad_id)
    fsdp = FsdpMeshTrainer(
        cfg.model, cfg.train, mesh=make_host_mesh(2), pad_id=tok.pad_id
    )
    p0 = plain.host_params(plain.init_state())
    pf = fsdp.host_params(fsdp.init_state())
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(pf)):
        np.testing.assert_array_equal(a, b)
    rng = np.random.default_rng(7)
    agg = jax.tree.map(
        lambda x: (x + rng.normal(0, 0.01, x.shape)).astype(x.dtype), p0
    )
    state = fsdp.adopt_aggregate(fsdp.init_state(), agg)
    back = fsdp.host_params(state)
    for a, b in zip(jax.tree.leaves(agg), jax.tree.leaves(back)):
        np.testing.assert_array_equal(a, b)
    assert wire.flat_crc32(wire.flatten_params(agg)) == wire.flat_crc32(
        wire.flatten_params(back)
    )
    assert int(state.step) == 0


def test_fsdp_reply_leaf_sink_scatters_onto_shards(tok, eight_devices):
    """Streamed-reply leaves land DIRECTLY on their shard spec (never a
    full replica per chip), bit-identical to the host-tree path."""
    cfg = _cfg(tok)
    fsdp = FsdpMeshTrainer(
        cfg.model, cfg.train, mesh=make_host_mesh(2), pad_id=tok.pad_id
    )
    arr = np.arange(32, dtype=np.float32).reshape(8, 4)
    placed = fsdp.reply_leaf_sink("encoder/x/kernel", arr)
    assert placed.sharding.spec == P("data", None)
    np.testing.assert_array_equal(np.asarray(placed), arr)
    small = np.arange(3, dtype=np.float32)
    placed_small = fsdp.reply_leaf_sink("encoder/x/bias", small)
    assert placed_small.sharding.spec == P()
    np.testing.assert_array_equal(np.asarray(placed_small), small)


def test_fsdp_checkpoint_round_trip_bit_exact(tok, client_data, tmp_path, eight_devices):
    """shard -> save -> restore -> shard: the restore template is the
    FSDP init_state, so leaves land back on their shards (orbax
    sharding-aware restore) and the host view is leaf-bit-exact."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.checkpoint import (
        Checkpointer,
    )

    cfg = _cfg(tok)
    fsdp = FsdpMeshTrainer(
        cfg.model, cfg.train, mesh=make_host_mesh(2), pad_id=tok.pad_id
    )
    state, _ = fsdp.fit(fsdp.init_state(), client_data.train, batch_size=8)
    before = fsdp.host_params(state)
    ckpt_dir = str(tmp_path / "ck")
    with Checkpointer(ckpt_dir) as ckpt:
        ckpt.save(1, state)
        ckpt.wait()
        restored = ckpt.restore(fsdp.init_state())
    for leaf in jax.tree.leaves(restored.params):
        assert hasattr(leaf, "sharding")
    sharded = [
        leaf
        for leaf in jax.tree.leaves(restored.params)
        if getattr(leaf.sharding, "spec", P()) != P()
    ]
    assert sharded, "restore lost the shard-at-rest layout"
    after = jax.tree.map(np.asarray, restored.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    # Opt state (Adam moments) round-trips bit-exactly too.
    for a, b in zip(
        jax.tree.leaves(state.opt_state), jax.tree.leaves(restored.opt_state)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- fedsteps parameterization
@pytest.mark.slow
def test_packed_step_spec_parameterization_matches_plain(tok, eight_devices):
    """make_packed_step(gather=, constrain=) — the FSDP-parameterized
    packed step — advances one client identically (to reduction-order
    ulps) to the plain packed step under threefry keys."""
    import jax.numpy as jnp
    import optax

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.distilbert import (
        DDoSClassifier,
        init_params,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        loss_fn,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.fedsteps import (
        make_packed_step,
    )

    cfg = _cfg(tok)
    mesh = make_host_mesh(2)
    from jax.sharding import NamedSharding

    replicated = NamedSharding(mesh, P())
    model = DDoSClassifier(cfg.model)
    optimizer = optax.adam(1e-3)

    def objective(p, batch, step_rng, anchor):
        task = loss_fn(model, p, batch, step_rng)
        return task, task

    def gather(p):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, replicated), p
        )

    def constrain(tree):
        shardings = fsdp_tree_shardings(tree, mesh)
        return jax.tree.map(
            jax.lax.with_sharding_constraint, tree, shardings
        )

    rng = jax.random.key(0, impl="threefry2x32")
    # Host-side master copy: the packed step DONATES its state buffers,
    # so each run must place fresh device arrays from host numpy.
    params = jax.tree.map(np.asarray, init_params(model, cfg.model, rng))
    nprng = np.random.default_rng(0)
    batch = {
        "input_ids": nprng.integers(
            0, cfg.model.vocab_size, (8, L)
        ).astype(np.int32),
        "attention_mask": np.ones((8, L), np.int32),
        "labels": nprng.integers(0, 2, 8).astype(np.int32),
    }

    def run(step, place):
        drng = jax.random.fold_in(
            jax.random.key(0, impl="threefry2x32"), 1
        )
        cstate = (
            place(params),
            place(jax.tree.map(np.asarray, optimizer.init(params))),
            jnp.zeros((), jnp.int32),
            drng,
        )
        for _ in range(3):
            cstate, task = step(cstate, batch)
        return jax.tree.map(np.asarray, cstate[0]), float(task)

    plain_step = make_packed_step(objective, optimizer, 0, 0.0)
    fsdp_step = make_packed_step(
        objective, optimizer, 0, 0.0, gather=gather, constrain=constrain
    )
    p_plain, l_plain = run(plain_step, lambda t: t)
    p_fsdp, l_fsdp = run(
        fsdp_step, lambda t: jax.device_put(t, fsdp_tree_shardings(t, mesh))
    )
    np.testing.assert_allclose(l_plain, l_fsdp, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_plain), jax.tree.leaves(p_fsdp)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)


@pytest.mark.slow
def test_build_federated_steps_gather_constrain_matches_plain(
    tok, eight_devices
):
    """build_federated_steps(gather=, constrain=) — the stacked FedState
    lifted to shard-at-rest over the data axis — advances every client
    lane identically (to reduction-order ulps) to the plain stacked
    step under threefry keys. The callables see STACKED [C, ...] trees:
    gather replicates over the fsdp axis only (clients stacking stays),
    constrain pins each leaf onto P('clients', *fsdp_spec(dims[1:]))."""
    import jax.numpy as jnp

    from jax.sharding import NamedSharding

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.models.distilbert import (
        DDoSClassifier,
        init_params,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
        FedShardings,
        make_mesh,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
        make_optimizer,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.fedsteps import (
        FedState,
        build_federated_steps,
    )

    C, DATA = 2, 2
    model_cfg = ModelConfig.tiny(
        vocab_size=len(tok.vocab), max_len=L, max_position_embeddings=2 * L
    )
    cfg = ExperimentConfig(
        model=model_cfg,
        data=DataConfig(max_len=L, batch_size=8),
        train=TrainConfig(
            prng_impl="threefry2x32", learning_rate=1e-3, log_every=0
        ),
        fed=FedConfig(num_clients=C),
        mesh=MeshConfig(clients=C, data=DATA, fsdp=True),
    )
    mesh = make_mesh(C, DATA, devices=eight_devices[: C * DATA])
    sh = FedShardings(mesh)

    def stacked_sharding(x):
        dims = tuple(int(d) for d in np.shape(x))
        inner = tuple(fsdp_spec(dims[1:], DATA)) if len(dims) > 1 else ()
        return NamedSharding(mesh, P("clients", *inner))

    def gather(tree):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, sh.client), tree
        )

    def constrain(tree):
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, stacked_sharding(x)
            ),
            tree,
        )

    model = DDoSClassifier(cfg.model)
    optimizer = make_optimizer(cfg.train)
    plain = build_federated_steps(cfg, model, optimizer, sh)
    fsdp = build_federated_steps(
        cfg, model, optimizer, sh, gather=gather, constrain=constrain
    )
    with pytest.raises(ValueError, match="pass both or neither"):
        build_federated_steps(cfg, model, optimizer, sh, gather=gather)

    rng = jax.random.key(0, impl="threefry2x32")
    p1 = jax.tree.map(np.asarray, init_params(model, cfg.model, rng))
    stacked = jax.tree.map(lambda a: np.stack([a] * C), p1)
    opt0 = jax.tree.map(np.asarray, jax.vmap(optimizer.init)(stacked))
    nprng = np.random.default_rng(0)
    batch = {
        "input_ids": nprng.integers(
            0, cfg.model.vocab_size, (C, 8, L)
        ).astype(np.int32),
        "attention_mask": np.ones((C, 8, L), np.int32),
        "labels": nprng.integers(0, 2, (C, 8)).astype(np.int32),
    }
    base_keys = jax.vmap(
        lambda i: jax.random.fold_in(
            jax.random.key(0, impl="threefry2x32"), i
        )
    )(np.arange(C))

    def run(steps, place_params):
        state = FedState(
            params=place_params(stacked),
            opt_state=place_params(opt0),
            step=jnp.zeros((), jnp.int32),
            rngs=jax.device_put(base_keys, sh.client),
        )
        losses = None
        for _ in range(3):
            state, losses = steps.train_step(state, batch)
        return (
            jax.tree.map(np.asarray, state.params),
            np.asarray(losses),
        )

    p_plain, l_plain = run(
        plain, lambda t: jax.device_put(t, sh.client)
    )
    p_fsdp, l_fsdp = run(
        fsdp,
        lambda t: jax.device_put(t, jax.tree.map(stacked_sharding, t)),
    )
    np.testing.assert_allclose(l_plain, l_fsdp, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_plain), jax.tree.leaves(p_fsdp)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)
    # Shard-at-rest actually held: per-chip static bytes ~1/DATA.
    rep_bytes = device_tree_bytes(jax.device_put(stacked, sh.client))
    fsdp_bytes = device_tree_bytes(
        jax.device_put(stacked, jax.tree.map(stacked_sharding, stacked))
    )
    assert fsdp_bytes / rep_bytes <= 0.6


# --------------------------------------------------------------- live wire
def _write_cfg(tmp_path, cfg, name):
    path = str(tmp_path / name)
    with open(path, "w") as f:
        json.dump(cfg.to_dict(), f)
    return path


def _read_metrics_csv(path):
    with open(path) as f:
        return dict(next(iter(csv.DictReader(f))))


def _run_client(argv, results, key):
    try:
        results[key] = main(argv)
    except BaseException as e:
        results[key] = e


def test_fsdp_client_two_round_loopback_matches_single_device(
    tok, tmp_path, eight_devices
):
    """The acceptance run: live server + `client --data-parallel 2
    --fsdp` for TWO rounds (round 2 streams the upload off the server's
    round-1 advert, and streamed replies scatter leaves onto shards) vs
    the single-device client on identical config/data — final local AND
    aggregated metrics threefry-identical. The wire-codec step profiler
    is armed (--profile-stride 1), so the wire-upload/wire-reply spans
    carry step_wire_ms_* attrs and the timeline renders the wire-codec
    row (the PR-12 device-plane residual, proven live)."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.profile import (
        memory_report,
        set_profile_stride,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.timeline import (
        load_spans,
        timeline_table,
    )

    cfg = _cfg(tok)
    cfg_plain = _cfg(tok, data=1, fsdp=False)
    outs = {}
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    try:
        for name, cfgv, extra in (
            ("single", cfg_plain, []),
            (
                "fsdp",
                cfg,
                [
                    "--data-parallel", "2", "--fsdp",
                    "--profile-stride", "1",
                    "--trace-jsonl", str(trace_dir / "client.jsonl"),
                ],
            ),
        ):
            cfg_path = _write_cfg(tmp_path, cfgv, f"cfg_{name}.json")
            out = str(tmp_path / name)
            outs[name] = out
            with AggregationServer(
                port=0, num_clients=1, timeout=60
            ) as server:
                errs: list = []

                def _serve():
                    try:
                        server.serve(rounds=2)
                    except Exception as e:
                        errs.append(e)

                t = threading.Thread(target=_serve, daemon=True)
                t.start()
                rc = main(
                    [
                        "client", "--client-id", "0", "--host", "127.0.0.1",
                        "--port", str(server.port), "--config", cfg_path,
                        "--synthetic", "400", "--output-dir", out,
                        "--timeout", "60", "--rounds", "2", *extra,
                    ]
                )
                t.join(timeout=60)
            assert rc == 0 and not errs, (rc, errs)
    finally:
        set_profile_stride(0)
    for phase in ("local", "aggregated"):
        a = _read_metrics_csv(
            os.path.join(outs["single"], f"client0_{phase}_metrics.csv")
        )
        b = _read_metrics_csv(
            os.path.join(outs["fsdp"], f"client0_{phase}_metrics.csv")
        )
        assert set(a) == set(b)
        for k in a:
            if k == "Loss":
                np.testing.assert_allclose(
                    float(a[k]), float(b[k]), rtol=1e-5, err_msg=(phase, k)
                )
            else:
                assert a[k] == b[k], (phase, k, a[k], b[k])
    # Wire-codec profiler satellite: the streamed round's spans carry
    # the sampled per-leaf pack/unpack attrs and the timeline renders
    # the row.
    spans = load_spans(trace_dir=str(trace_dir))
    wire_spans = [
        s
        for s in spans
        if s.get("span") in ("wire-upload", "wire-reply")
        and s.get("step_wire_ms_p50") is not None
    ]
    assert any(s["span"] == "wire-reply" for s in wire_spans), spans
    assert any(s["span"] == "wire-upload" for s in wire_spans), spans
    assert all(s.get("step_sampled", 0) >= 1 for s in wire_spans)
    table = timeline_table(spans)
    assert "wire-codec" in table
    # Adopt-aggregate boundary watermark (PR-12 residual closed): the
    # meshed client path stamps post-aggregate now; CPU backends record
    # the visit as unavailable rather than skipping it.
    assert "post-aggregate" in memory_report()


def test_fsdp_client_composes_with_secure_agg_and_dp(
    tok, tmp_path, eight_devices, monkeypatch
):
    """--secure-agg + --dp with a MIXED fleet: client 0 single-device,
    client 1 --data-parallel 2 --fsdp, one live secure DP round. The
    server's dp_base_crc equality check REJECTS a round whose clients
    upload different bases, so completion proves the FSDP host gather is
    byte-identical to the single-device client's."""
    monkeypatch.delenv("FEDTPU_SECRET", raising=False)
    monkeypatch.delenv("FEDTPU_CLIENT_SECRET", raising=False)
    base_cfg = _cfg(tok, data=1, fsdp=False)
    cfg = ExperimentConfig(
        model=base_cfg.model,
        data=base_cfg.data,
        train=base_cfg.train,
        fed=FedConfig(num_clients=2),
        mesh=MeshConfig(clients=2, data=1),
    )
    cfg_path = _write_cfg(tmp_path, cfg, "cfg2.json")
    out = str(tmp_path / "compose")
    with AggregationServer(
        port=0,
        num_clients=2,
        timeout=90,
        secure_agg=True,
        dp_clip=1.0,
        dp_noise_multiplier=0.05,
    ) as server:
        errs: list = []

        def _serve():
            try:
                server.serve(rounds=1)
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=_serve, daemon=True)
        t.start()
        results: dict = {}
        base = [
            "--host", "127.0.0.1", "--port", str(server.port),
            "--config", cfg_path, "--synthetic", "400",
            "--output-dir", out, "--timeout", "90",
            "--secure-agg", "--dp",
        ]
        c1 = threading.Thread(
            target=_run_client,
            args=(
                [
                    "client", "--client-id", "1",
                    "--data-parallel", "2", "--fsdp", *base,
                ],
                results,
                "fsdp",
            ),
            daemon=True,
        )
        c1.start()
        results["single"] = main(["client", "--client-id", "0", *base])
        c1.join(timeout=120)
        t.join(timeout=60)
    assert results["single"] == 0 and results["fsdp"] == 0, results
    assert not errs, errs
    for c in (0, 1):
        assert os.path.exists(
            os.path.join(out, f"client{c}_aggregated_metrics.csv")
        )


# ------------------------------------------------------------ wire profiler
def test_wire_step_profiler_site_and_attrs():
    """The 'wire' StepProfiler site: single 'wire' phase, the
    fedtpu_wire_step_seconds family, step_wire_ms_* span attrs."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.metrics import (
        MetricsRegistry,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.profile import (
        StepProfiler,
    )

    reg = MetricsRegistry()
    prof = StepProfiler(2, site="wire", registry=reg)
    assert prof.phases == ("wire",)
    sampled = [prof.tick() for _ in range(4)]
    assert sampled == [True, False, True, False]
    prof.note("wire", 0.002)
    prof.note("wire", 0.004)
    attrs = prof.span_attrs()
    assert attrs["step_wire_ms_p50"] > 0
    assert attrs["step_sampled"] == 2
    assert "fedtpu_wire_step_seconds" in reg.render()
    with pytest.raises(ValueError, match="unknown phase"):
        prof.note("device", 0.1)
    # Window reset clears the samples (long-lived client contract).
    prof.begin_window()
    assert prof.span_attrs() == {}
