"""Traffic kind ``window_fit``: ``client_fit``'s round with detection windows
for rows: one site fine-tunes a long-context backbone on windows of its own
capture (``Trainer.fit`` for the round's local epochs, then
``Trainer.evaluate`` on held-out windows), on its own chip. A window is W
consecutive seeded flows joined into one document (the program's window
renderer) and tokenised once by the program's tokenizer; a row is a window, so
``train_samples_per_s`` is windows per second. Spans and counters are
``client_fit``'s (``round``, ``fit`` with ``rows`` and ``steps``, ``eval`` with
``rows``), and ``fit`` also carries ``tokens``, ``tokens_real`` and
``routed_slots_here`` (the token-slots the program's counters say were routed
to the experts this chip holds), which the family's FLOPs read. A traced run
also leaves the two timed programs' compiled texts for the scope readers.

Besides ``harness.check_trained``, ``correct`` compares the timed step itself:
one launch of the window's compiled ``engine.train_step`` at the cell's batch
from the seed's weights on the first timed batch, against the reference's
loss, its gradient over every window of the batch (the whole tree's relative
L2, and the worst leaf's) and a reference Adam step (the parameters' change),
the reference computing under the program's own choice of experts; the share
of the token-slots on which the two routers chose differently; and the
program's count of slots its expert buffers could not take, in every fit and
every evaluation, with 0.

Parameters of a mix: ``flows_min``, ``flows_max``, ``burst_min``,
``burst_max``, ``train_windows``, ``eval_windows``, ``batch``, ``eval_batch``,
``epochs``, ``learning_rate``.
"""

from __future__ import annotations

import time

import numpy as np

from .. import flows, harness
from ..harness import Context, pkg

#: Whole rounds before the window; one compiles every program of a round.
WARM_ROUNDS = 1

#: What this cell's programs take in the persistent compile cache: the step
#: 145 MB serialised, the evaluation 102 MB, the comparison's programs 15-30
#: MB each (compiles for the v5e and the machine's cache directory, PR 28).
CACHE_BYTES_NEEDED = 512 * 1024 * 1024


def leave_a_small_cache_alone(ctx: Context) -> None:
    """Under a size cap that cannot hold this cell's programs
    (``jax_compilation_cache_max_size``, which the chip machine sets to 192
    MiB, evicting the least recently used), the cache serves the cell
    nothing and the cell empties it for the others: every run read
    "persistent cache 0 hit(s) 43 miss(es)", and a BERT-large run directly
    after one missed 14 of its 24 programs and took 172 s to set up for 35
    (my chip runs, PR 28). Such a run compiles outside the cache. Called
    before the process's first compilation: JAX decides once whether the
    cache is in use."""
    import jax

    cap = int(jax.config.jax_compilation_cache_max_size)
    if 0 <= cap < CACHE_BYTES_NEEDED and not ctx.rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
        ctx.say(
            f"the compile cache is capped at {cap / 2**20:.0f} MiB and this cell's programs need some "
            f"{CACHE_BYTES_NEEDED / 2**20:.0f}: compiling outside it, so that the other cells' entries stay"
        )


def make_windows(t: dict, max_len: int, n: int, seed: int, tok, rec: harness.Recorder):
    """``n`` seeded windows of the mix ``t``, half benign and half holding
    one contiguous DDoS burst, as a TokenizedSplit of rows of ``max_len``
    tokens."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(int(t["flows_min"]), int(t["flows_max"]) + 1, size=n)
    attacked = rng.permutation(n) < n // 2
    with rec.span("make_flows"):
        texts, labels = flows.make_flows(4 * int(sizes.sum()), seed)
    pools = {0: [x for x, y in zip(texts, labels) if y == 0], 1: [x for x, y in zip(texts, labels) if y == 1]}
    doc_flows, doc_labels, bounds = [], [], []
    for W, hit in zip(sizes.tolist(), attacked.tolist()):
        kinds = np.zeros(W, np.int32)
        if hit:
            burst = max(1, int(np.ceil(rng.uniform(float(t["burst_min"]), float(t["burst_max"])) * W)))
            at = int(rng.integers(0, W - burst + 1))
            kinds[at : at + burst] = 1
        bounds.append((len(doc_flows), len(doc_flows) + W))
        doc_flows += [pools[int(k)].pop() for k in kinds]
        doc_labels += kinds.tolist()
    docs, held = pkg("data").render_windows(doc_flows, np.asarray(doc_labels), bounds)
    with rec.span("tokenise", flows=len(doc_flows)):
        enc = tok.batch_encode(docs, max_len=max_len)
    return pkg("data.pipeline").TokenizedSplit(enc["input_ids"], enc["attention_mask"], held)


def run(ctx: Context) -> dict:
    import jax

    leave_a_small_cache_alone(ctx)
    config = pkg("config")
    t = ctx.traffic
    train_windows = ctx.scaled("train_windows", 8)
    eval_windows = ctx.scaled("eval_windows", 4)
    bs = ctx.scaled("batch", 2)
    ebs = ctx.scaled("eval_batch", 2)
    E = int(t["epochs"])
    model_cfg = ctx.model_config()
    train_cfg = config.TrainConfig(
        epochs_per_round=E, learning_rate=float(t["learning_rate"]), seed=ctx.seed, log_every=0
    )
    tok = pkg("data").default_tokenizer()
    trainer = pkg("train.engine").Trainer(model_cfg, train_cfg, pad_id=tok.pad_id)
    pool = make_windows(t, ctx.model["max_len"], train_windows + eval_windows, ctx.seed, tok, ctx.rec)
    order = np.random.default_rng(ctx.seed + 7).permutation(len(pool))
    train, held = pool.take(order[:train_windows]), pool.take(order[train_windows:])
    L = int(train.input_ids.shape[1])
    rows_per_fit = E * (train_windows // bs) * bs
    real = pool.attention_mask.sum(-1)
    ctx.say(
        f"windows: {len(pool)} of {L} tokens, {int(real.min())}-{int(real.max())} real "
        f"(mean {real.mean():.0f}); {int(pool.labels.sum())} hold a burst; {bs * L} tokens a step"
    )
    with ctx.rec.span("init_state"):
        params = harness.init_params_on_device(ctx.family, model_cfg, ctx.seed, train_cfg.prng_impl)
        state = trainer.init_state(seed=ctx.seed, params=params)
        del params
        jax.block_until_ready(state.opt_state)
    # The first timed batch, for the comparison after the window.
    first_batch = next(iter(trainer.epoch_batches(train, WARM_ROUNDS * E, bs)))

    def one_round(r: int) -> dict:
        nonlocal state
        trainer.last_route = None
        with ctx.rec.span("round", r=r) as rec:
            with ctx.rec.span(
                "fit", rows=rows_per_fit, steps=rows_per_fit // bs, tokens=rows_per_fit * L,
                # every train window goes through each epoch when the batch divides them
                tokens_real=int(train.attention_mask.sum()) * rows_per_fit // train_windows,
            ) as fit:
                state, losses = trainer.fit(state, train, batch_size=bs, epochs=E, epoch_offset=r * E)
                jax.block_until_ready(state.params)
            with ctx.rec.span("eval", rows=len(held)):
                metrics = trainer.evaluate(state.params, held, batch_size=ebs, collect_probs=False)
        route = trainer.last_route or {"slots": np.zeros(1, np.int64), "overflow": 0}
        fit["routed_slots_here"] = int(route["slots"].sum())
        rec["slots"] = route["slots"]
        rec["overflow"] = int(route["overflow"]) + int(metrics.get("routed_overflow", 0))
        rec["losses_finite"] = bool(np.isfinite(losses).all())
        rec["loss_mean"] = float(np.mean(losses))
        rec["acc"] = float(metrics["Accuracy"])
        return rec

    rounds = harness.run_rounds(ctx, one_round, warm_rounds=WARM_ROUNDS)
    if ctx.trace:
        ctx.rec.data["hlo_texts"] = program_texts(trainer, state, first_batch, held, ebs)
    params = state.params
    del state  # the moments go before the comparisons make the seed's weights again
    t0 = time.perf_counter()
    ref = harness.check_trained(ctx, params, held, what="window model")
    ctx.say(f"correct/window model took {time.perf_counter() - t0:.0f} s")
    del params
    overflow = sum(x["overflow"] for x in rounds)
    if not ctx.compare("routed_overflow", overflow, 0):
        ctx.fail(f"{overflow} token-slot(s) of the rounds' fits and evaluations were beyond a held expert's buffer and not computed")
    ref["step"] = check_step(ctx, trainer, model_cfg, first_batch, held, ebs)
    out = harness.round_results(ctx, rounds, ("fit", "eval"))
    slots = np.sum([x["slots"] for x in rounds if not x.get("traced")], axis=0)
    ctx.say(
        f"window: {len(rounds)} rounds ({len(ctx.rec.select('round'))} untraced); round_s median "
        f"{ctx.num(out['end_to_end']['round_s'])}; held-out accuracy last round {rounds[-1]['acc']:.1f}%; "
        f"slots routed to the held experts {slots.tolist()} (overflow {overflow})"
    )
    ctx.rec.data.update(chips=1, reference=ref, rounds=len(rounds), route_slots=slots)
    return out


def program_texts(trainer, state, batch: dict, held, ebs: int) -> list[str]:
    """The compiled text of the two programs the window ran, for
    reduce/scope_ops.py (each instruction's ``op_name`` path). Lowered from
    the shapes the window used: the executables are the ones that ran, and
    nothing is traced or compiled again."""
    import jax

    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=getattr(x, "sharding", None)), tree
    )
    jitted = lambda step: getattr(step, "__wrapped__", step)  # noqa: E731
    padded, valid = pkg("data.pipeline").pad_split_to_batch(held, ebs, pad_id=trainer.pad_id)
    eval_batch = {
        "input_ids": padded.input_ids[:ebs], "attention_mask": padded.attention_mask[:ebs],
        "labels": padded.labels[:ebs],
    }
    return [
        jitted(trainer.train_step).lower(abstract(state), abstract(batch)).compile().as_text(),
        jitted(trainer.eval_step).lower(
            batch=abstract(eval_batch), params=abstract(state.params), valid=abstract(valid[:ebs])
        ).compile().as_text(),
    ]


def timed_step(ctx: Context, trainer, params, batch: dict) -> dict:
    """ONE launch of the window's own compiled step (``engine.train_step``
    at the cell's batch) from the weights ``params`` and a fresh optimizer
    state, read back on the host: its loss, its gradient (Adam's first
    moment after a first step is ``(1 - b1) g``), the parameters' change, and
    the experts the program's router chose on the batch. ``params`` is
    donated to the step."""
    import jax
    import optax

    before = jax.device_get(params)
    routes = jax.device_get(
        jax.jit(ctx.family.routing(trainer.model_cfg))(params, batch["input_ids"], batch["attention_mask"])
    )
    state = trainer.init_state(seed=ctx.seed, params=params)
    del params
    state, loss = trainer.train_step(state, batch)
    b1 = trainer.train_cfg.b1
    grads = jax.tree.map(
        lambda m: np.asarray(m) / np.float32(1.0 - b1), optax.tree_utils.tree_get(state.opt_state, "mu")
    )
    update = jax.tree.map(lambda new, old: np.asarray(new) - old, state.params, before)
    overflow = int(state.route["overflow"]) if state.route else 0
    return {"loss": float(loss), "grads": grads, "update": update, "overflow": overflow, "routes": routes}


def reference_step(ctx: Context, params, batch: dict, train_cfg, forced: list, **rnd) -> dict:
    """What :func:`timed_step` reads, from the family's plain reference: the
    loss and the gradient over every window of the batch, computed under the
    choice of experts ``forced`` (the program's: a step's ``routes``), a
    first Adam step on that gradient, and the reference's OWN choices."""
    import jax

    family = ctx.family
    loss, grads, routes = family.reference_loss_and_grads(
        params, batch["input_ids"], batch["attention_mask"], batch["labels"], ctx.model, forced=forced, **rnd
    )
    grads = jax.device_get(grads)
    routes = [np.asarray(idx) for idx, _ in routes]
    update = family.reference_adam_step(
        grads, learning_rate=train_cfg.learning_rate, b1=train_cfg.b1, b2=train_cfg.b2, eps=train_cfg.eps
    )
    return {"loss": loss, "grads": grads, "update": update, "routes": routes}


def judge_step(ctx: Context, got: dict, want: dict, mask, *, what: str) -> dict:
    """``got`` (a step's loss, gradient, parameter change and choices of
    experts on the batch whose attention mask is ``mask``) against the
    reference's ``want``, each number through ``ctx.compare`` with the
    family's limit: the loss in absolute terms; the gradient as the relative
    L2 of the whole tree and of the worst leaf (among the leaves whose
    reference norm is at least ``grad_floor`` of the largest); the
    parameters' change as the relative L2 of the whole tree, which reads 1
    for a state left unchanged; and the share of the real token-slots on
    which ``got``'s router and the reference's own chose differently (the
    reference computed everything else under ``got``'s choices)."""
    tol = ctx.family.TOLERANCES
    leaves, whole = leaf_errors(got["grads"], want["grads"])
    largest = max(w for _, _, w in leaves)
    judged = sorted(((d / w, name) for name, d, w in leaves if w >= tol["grad_floor"] * largest), reverse=True)
    _, update = leaf_errors(got["update"], want["update"])
    flips = routing_flips(ctx.model, got["routes"], want["routes"], mask)
    out = {
        "loss": got["loss"], "loss_ref": want["loss"], "grad_rel_whole": whole,
        "grad_rel_leaf": judged[0][0], "update_rel": update, **flips,
    }
    ctx.say(
        f"correct/step, {what}: loss {got['loss']:.6f} against the reference's {want['loss']:.6f} (limit "
        f"{tol['loss_abs']:g}); gradient: the whole tree differs by {100 * whole:.3f}% (relative L2; limit "
        f"{100 * tol['grad_rel']:g}%), the worst of {len(judged)} leaves {judged[0][1]} by {100 * judged[0][0]:.3f}% "
        f"(limit {100 * tol['grad_leaf_rel']:g}%; then "
        + ", ".join(f"{name} {100 * r:.2f}%" for r, name in judged[1:3])
        + f"; {len(leaves) - len(judged)} of {len(leaves)} leaves are under {tol['grad_floor']:g} of the largest norm "
        f"and not judged); the parameters' change after the step differs by {100 * update:.2f}% of its norm (limit "
        f"{100 * tol['update_rel']:g}%; a state left unchanged reads 100%); of {flips['slots']} real token-slots "
        f"({len(got['routes'])} expert layers) the two routers chose differently on {flips['flipped']} "
        f"({100 * flips['flip_share']:.3f}%; limit {100 * tol['flip_share']:g}%), {flips['flipped_held']} of them "
        f"({100 * flips['flipped_held_share']:.4f}% of the slots) naming an expert held here"
    )
    if not ctx.compare("step.loss_abs", abs(got["loss"] - want["loss"]), tol["loss_abs"]):
        ctx.fail(f"{what}: the step's loss {got['loss']:.6f} differs from the reference's {want['loss']:.6f}")
    if not ctx.compare("step.grad_rel", whole, tol["grad_rel"]):
        ctx.fail(f"{what}: the step's gradient differs from the reference's by {whole:.4f} (relative L2 over the whole tree)")
    if not ctx.compare("step.grad_leaf_rel", judged[0][0], tol["grad_leaf_rel"]):
        ctx.fail(f"{what}: the gradient of {judged[0][1]} differs from the reference's by {judged[0][0]:.4f} (relative L2)")
    if not ctx.compare("step.update_rel", update, tol["update_rel"]):
        ctx.fail(f"{what}: the parameters' change differs from a reference Adam step's by {update:.4f} of its norm")
    if not ctx.compare("step.flip_share", flips["flip_share"], tol["flip_share"]):
        ctx.fail(f"{what}: the router chose other experts than the reference's on {flips['flip_share']:.4f} of the token-slots")
    return out


def leaf_errors(got, want):
    """Two trees of host arrays: ``[(leaf's path, |got - want|, |want|),
    ...]`` and the whole tree's relative L2 error."""
    import jax

    flat = zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))
    leaves = [
        (
            jax.tree_util.keystr(path),
            float(np.linalg.norm((np.asarray(a, np.float64) - b).ravel())),
            float(np.linalg.norm(np.asarray(b, np.float64).ravel())),
        )
        for (path, a), b in flat
    ]
    whole = float(np.sqrt(sum(d * d for _, d, _ in leaves)) / np.sqrt(sum(w * w for _, _, w in leaves)))
    return leaves, whole


def check_step(ctx: Context, trainer, model_cfg, batch: dict, held, ebs: int) -> dict:
    """The timed step's own arithmetic: one launch of the window's compiled
    ``engine.train_step`` from the seed's weights on the first timed batch
    against the reference's loss, gradient and first Adam step on the same
    windows (:func:`judge_step`); the slots the program's expert buffers
    could not take, in that step and in an evaluation of the held-out
    windows at the seed's weights (the forward ``check_trained`` compared),
    against 0."""
    train_cfg = trainer.train_cfg
    fresh = lambda: harness.init_params_on_device(ctx.family, model_cfg, ctx.seed, train_cfg.prng_impl)  # noqa: E731
    t0 = time.perf_counter()
    params = fresh()
    overflow = trainer.evaluate(params, held, batch_size=ebs, collect_probs=False).get("routed_overflow", 0)
    got = timed_step(ctx, trainer, params, batch)
    del params
    overflow += got["overflow"]
    if not ctx.compare("seed.routed_overflow", overflow, 0):
        ctx.fail(f"{overflow} token-slot(s) were beyond a held expert's buffer at the seed's weights")
    t1 = time.perf_counter()
    want = reference_step(ctx, fresh(), batch, train_cfg, got["routes"])
    t2 = time.perf_counter()
    out = judge_step(
        ctx, got, want, batch["attention_mask"],
        what=f"engine.train_step at the seed's weights on the first timed batch ({len(batch['labels'])} windows)",
    )
    ctx.say(
        f"correct/step took {time.perf_counter() - t0:.0f} s: the program's evaluation, routing and step {t1 - t0:.0f}, "
        f"the reference's {len(batch['labels'])} gradients and Adam step {t2 - t1:.0f}, the comparison "
        f"{time.perf_counter() - t2:.0f}"
    )
    return out


def routing_flips(model: dict, got: list, want: list, mask) -> dict:
    """Two routers' choices ``[idx [B, L, k] per expert layer]`` on the rows
    whose attention mask is ``mask``: the real token-slots, those on which
    the choices differ, and those that differ AND name an expert held here on
    either side, as counts and as shares of the slots."""
    real = np.asarray(mask, bool)
    lo, hi = model["expert_offset"], model["expert_offset"] + model["experts_held"]
    slots = flipped = touched = 0
    for g, w in zip(got, want):
        g, w = np.asarray(g)[real], np.asarray(w)[real]  # [tokens, k]
        lost = ~(g[:, :, None] == w[:, None, :]).any(-1)  # got's choices that want did not make
        gained = ~(w[:, :, None] == g[:, None, :]).any(-1)
        slots += g.size
        flipped += int(lost.sum())
        touched += int((lost & (g >= lo) & (g < hi)).sum() + (gained & (w >= lo) & (w < hi)).sum())
    return {
        "slots": slots, "flipped": flipped, "flipped_held": touched,
        "flip_share": flipped / max(slots, 1), "flipped_held_share": touched / max(slots, 1),
    }
