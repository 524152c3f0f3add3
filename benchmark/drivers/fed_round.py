"""Traffic kind ``fed_round``: whole federated rounds on the mesh tier.

One round is what ``FederatedTrainer.run`` does, by the same public methods
in the same order: ``fit_local`` (every client's local epochs in lockstep),
``evaluate_clients`` on the local models, ``round_aggregate`` (FedAvg), and
``evaluate_clients`` on the aggregate, then the per-round optimizer reset.
Rounds repeat until the window ends; only whole rounds count.

Parameters of a mix (``benchmark/traffic/<mix>.json``): ``clients``,
``mesh`` {clients, data}, ``train_rows`` and ``eval_rows`` per client,
``batch``, ``eval_batch``, ``epochs``, ``partition`` (``iid`` |
``dirichlet:<alpha>``), ``stack`` (``dense`` | ``ragged``),
``learning_rate``.
"""

from __future__ import annotations

import numpy as np

from .. import harness
from ..harness import Context, pkg

#: Leaves whose aggregate is compared with the numpy weighted mean.
MEAN_CHECK_LEAVES = 4
MEAN_CHECK_MAX = 5_000_000  # elements per client
#: Whole rounds before the window. A shorter warm-up round would not do:
#: the epoch's loss mean is a program of its own per number of batches. The
#: first round compiles every program of a round; the second compiles the
#: step again, because the state that comes back from FedAvg and the
#: optimizer reset is placed otherwise than init_state's (PERF.md section 6).
WARM_ROUNDS = 2


def partition(labels: np.ndarray, clients: int, scheme: str, rng) -> list[np.ndarray]:
    """Row indices per client. ``iid``: a seeded permutation cut into equal
    shards. ``dirichlet:<alpha>``: each class's rows divided among the
    clients in Dirichlet(alpha) proportions (label skew, unequal sizes)."""
    n = len(labels)
    if scheme == "iid":
        return list(np.array_split(rng.permutation(n), clients))
    kind, _, alpha = scheme.partition(":")
    if kind != "dirichlet" or not alpha:
        raise ValueError(f"unknown partition {scheme!r} (iid | dirichlet:<alpha>)")
    parts: list[list[int]] = [[] for _ in range(clients)]
    for cls in np.unique(labels):
        rows = rng.permutation(np.flatnonzero(labels == cls))
        cuts = (np.cumsum(rng.dirichlet([float(alpha)] * clients)) * len(rows)).astype(int)
        for c, chunk in enumerate(np.split(rows, cuts[:-1])):
            parts[c].extend(chunk.tolist())
    return [rng.permutation(np.array(p, np.int64)) for p in parts]


def build(ctx: Context):
    """Config, trainer, seeded data and state: everything before the first
    round."""
    import jax

    config = pkg("config")
    t = ctx.traffic
    C = int(t["clients"])
    if ctx.rehearsal:
        C = min(C, 4)
    train_rows = ctx.scaled("train_rows", 32)
    eval_rows = ctx.scaled("eval_rows", 16)
    bs = ctx.scaled("batch", 8)
    ebs = ctx.scaled("eval_batch", 8)
    mesh = t["mesh"]
    model_cfg = ctx.model_config()
    cfg = config.ExperimentConfig(
        model=model_cfg,
        data=config.DataConfig(max_len=model_cfg.max_len, batch_size=bs, eval_batch_size=ebs),
        train=config.TrainConfig(
            epochs_per_round=int(t["epochs"]), learning_rate=float(t["learning_rate"]),
            seed=ctx.seed, log_every=0,
        ),
        fed=config.FedConfig(num_clients=C, rounds=1, weighted=True),
        mesh=config.MeshConfig(clients=int(mesh["clients"]), data=int(mesh["data"])),
    )
    tok = pkg("data").default_tokenizer()
    with ctx.rec.span("build_trainer"):
        trainer = pkg("train.federated").FederatedTrainer(cfg, pad_id=tok.pad_id)
    want = (int(mesh["clients"]), int(mesh["data"]))
    got = tuple(int(x) for x in trainer.mesh.devices.shape)
    got = got + (1,) * (2 - len(got))
    ctx.say(f"mesh asked {want[0]}x{want[1]}, got {'x'.join(map(str, got))}; {C} clients")
    if got != want:
        ctx.fail(f"the trainer built mesh {got}, the cell asks {want}")

    _, pool = harness.tokenised_flows(ctx, C * (train_rows + eval_rows), ctx.seed, tok)
    rng = np.random.default_rng(ctx.seed + 7)
    pipeline = pkg("data.pipeline")
    train, evals = [], []
    for rows in partition(pool.labels, C, t["partition"], rng):
        cut = int(round(len(rows) * train_rows / (train_rows + eval_rows)))
        train.append(pool.take(rows[:cut]))
        evals.append(pool.take(rows[cut:]))
    if t["stack"] == "ragged":
        stacked = pipeline.stack_clients_ragged(train, pad_id=tok.pad_id)
    else:
        stacked = pipeline.stack_clients(train)
    weights = np.array([len(s) for s in train], np.float64)
    if t["stack"] == "ragged":
        rows_per_fit = int(t["epochs"]) * int(weights.sum())
        steps_per_fit = int(t["epochs"]) * int(sum(-(-len(s) // bs) for s in train))
    else:
        steps_per_fit = int(t["epochs"]) * C * (stacked.labels.shape[1] // bs)
        rows_per_fit = steps_per_fit * bs
    prepared = trainer.prepare_eval(evals)
    with ctx.rec.span("init_state"):
        params = harness.init_params_on_device(
            ctx.family, model_cfg, ctx.seed, cfg.train.prng_impl
        )
        state = trainer.init_state(seed=ctx.seed, params=params)
        del params
        jax.block_until_ready(state.params)
    return {
        "cfg": cfg, "trainer": trainer, "state": state, "stacked": stacked,
        "prepared": prepared, "weights": weights, "rows_per_fit": rows_per_fit, "steps_per_fit": steps_per_fit,
        "eval_rows": int(sum(len(s) for s in evals)), "evals": evals, "C": C,
    }


def one_round(ctx: Context, b: dict, r: int, *, check_mean: bool = False) -> dict:
    """Round ``r``; returns the round's span record. With ``check_mean`` a
    sample of leaves is read before FedAvg and compared after it with the
    numpy weighted mean (outside every timed span but ``round``)."""
    import jax

    trainer, cfg = b["trainer"], b["cfg"]
    E = cfg.train.epochs_per_round
    with ctx.rec.span("round", r=r) as round_rec:
        anchor = trainer.round_anchor(b["state"])
        with ctx.rec.span("fit", rows=b["rows_per_fit"], steps=b["steps_per_fit"]):
            state, losses = trainer.fit_local(b["state"], b["stacked"], epoch_offset=r * E)
            # fit_local ends in a host read of the epoch's losses; the
            # state is fenced too, so the span holds every step's work.
            jax.block_until_ready(state.params)
        b["state"] = state
        with ctx.rec.span("eval", rows=b["eval_rows"]):
            local = trainer.evaluate_clients(state.params, prepared=b["prepared"])
        before = None
        if check_mean:
            leaves = jax.tree.leaves(state.params)
            # Matrices and vectors alike, but not the embedding tables: C
            # float64 copies of those would be gigabytes on the host.
            small = [i for i, x in enumerate(leaves) if x.size // x.shape[0] <= MEAN_CHECK_MAX]
            pick = np.linspace(0, len(small) - 1, MEAN_CHECK_LEAVES).astype(int)
            before = {small[i]: np.asarray(leaves[small[i]], np.float64) for i in pick}
        with ctx.rec.span("agg"):
            state = trainer.round_aggregate(
                state, round_index=r, weights=b["weights"], anchor=anchor
            )
            jax.block_until_ready(state.params)
        b["state"] = state
        with ctx.rec.span("eval", rows=b["eval_rows"]):
            aggregated = trainer.evaluate_clients(state.params, prepared=b["prepared"])
        if cfg.fed.reset_optimizer_each_round:
            with ctx.rec.span("reset"):
                b["state"] = state = trainer.reset_optimizer(state)
                jax.block_until_ready(state.opt_state)
    round_rec["losses_finite"] = bool(np.isfinite(losses).all())
    round_rec["loss_mean"] = float(np.mean(losses))
    round_rec["acc_local"] = float(np.mean([m["Accuracy"] for m in local]))
    round_rec["acc_agg"] = float(np.mean([m["Accuracy"] for m in aggregated]))
    if before is not None:
        check_weighted_mean(ctx, b, before)
    return round_rec


def check_weighted_mean(ctx: Context, b: dict, before: dict) -> None:
    """FedAvg's output on the sampled leaves is the sample-count-weighted
    mean of the clients' leaves, in every replica."""
    import jax

    w = b["weights"] / b["weights"].sum()
    leaves = jax.tree.leaves(b["state"].params)
    # The worst over the sampled leaves and the checked rounds, in limits.
    name = "fedavg.mean_err_over_limit"
    for i, pre in before.items():
        want = np.tensordot(w, pre, axes=(0, 0))
        got = np.asarray(leaves[i], np.float64)
        # fp32 accumulation in another order than numpy's float64: a few
        # ulps of the leaf's own magnitude.
        tol = 1e-5 * max(float(np.abs(want).max()), 1e-6)
        worst = float(np.abs(got - want[None]).max())
        ctx.compare(name, max(ctx.compared.get(name, [0.0])[0], worst / tol), 1.0)
        if not worst <= tol:
            ctx.fail(
                f"FedAvg leaf {i}: differs from the numpy weighted mean by {worst} (limit {tol})"
            )


def replicas_identical(state) -> bool:
    """After FedAvg every client's replica is the same model, to the bit."""
    import jax
    import jax.numpy as jnp

    same = jax.jit(
        lambda p: jnp.stack([jnp.all(x == x[:1]) for x in jax.tree.leaves(p)]).all()
    )(state.params)
    return bool(same)


def replica0_crc(state) -> int:
    """The repo's own checksum (comm/wire.py) of client 0's replica."""
    import jax

    wire = pkg("comm.wire")
    params0 = jax.tree.map(lambda x: np.asarray(x[0]), state.params)
    return int(wire.flat_crc32(wire.flatten_params(params0)))


def run(ctx: Context) -> dict:
    import jax

    b = build(ctx)
    C = b["C"]
    rounds = harness.run_rounds(
        ctx, lambda r, **kw: one_round(ctx, b, r, **kw), warm_rounds=WARM_ROUNDS, check_mean=True
    )
    state = b["state"]
    identical = replicas_identical(state)
    if not ctx.compare("replicas_differ", int(not identical), 0):
        ctx.fail("client replicas differ after FedAvg")
    crc = replica0_crc(state)
    params0 = jax.tree.map(lambda x: x[0], state.params)
    ref = harness.check_trained(ctx, params0, b["evals"][0], what="fed replica 0")
    out = harness.round_results(ctx, rounds, ("fit", "eval", "agg", "reset"))
    ctx.say(
        f"window: {len(rounds)} rounds ({len(ctx.rec.select('round'))} untraced) of {C} clients; "
        f"round_s median {ctx.num(out['end_to_end']['round_s'])}; accuracy local/aggregated "
        f"last round {rounds[-1]['acc_local']:.1f}%/{rounds[-1]['acc_agg']:.1f}%; "
        f"replica 0 crc32 {crc:#010x}; replicas {'identical' if identical else 'DIFFER'}"
    )
    ctx.rec.data.update(
        chips=int(np.prod(b["trainer"].mesh.devices.shape)), reference=ref,
        params_crc32=crc, rounds=len(rounds),
    )
    return out
