"""Traffic kind ``client_fit``: one site's work in one round on its own chip,
as a TCP-tier client does it: ``Trainer.fit`` for the round's local epochs,
then ``Trainer.evaluate`` on the site's held-out rows. Repeats until the
window ends; a round here is fit + evaluate, and only whole rounds count.

Parameters of a mix: ``train_rows``, ``eval_rows``, ``batch``,
``eval_batch``, ``epochs``, ``learning_rate``.
"""

from __future__ import annotations

import numpy as np

from .. import harness
from ..harness import Context, pkg

#: Whole rounds before the window; one compiles every program of a round
#: (a shorter one would not: the epoch's loss mean is a program of its own
#: per number of batches).
WARM_ROUNDS = 1


def run(ctx: Context) -> dict:
    import jax

    config = pkg("config")
    t = ctx.traffic
    train_rows = ctx.scaled("train_rows", 32)
    eval_rows = ctx.scaled("eval_rows", 16)
    bs = ctx.scaled("batch", 8)
    ebs = ctx.scaled("eval_batch", 8)
    E = int(t["epochs"])
    model_cfg = ctx.model_config()
    train_cfg = config.TrainConfig(
        epochs_per_round=E, learning_rate=float(t["learning_rate"]), seed=ctx.seed, log_every=0
    )
    tok = pkg("data").default_tokenizer()
    trainer = pkg("train.engine").Trainer(model_cfg, train_cfg, pad_id=tok.pad_id)
    _, pool = harness.tokenised_flows(ctx, train_rows + eval_rows, ctx.seed, tok)
    order = np.random.default_rng(ctx.seed + 7).permutation(len(pool))
    train, held = pool.take(order[:train_rows]), pool.take(order[train_rows:])
    rows_per_fit = E * (train_rows // bs) * bs
    with ctx.rec.span("init_state"):
        params = harness.init_params_on_device(
            ctx.family, model_cfg, ctx.seed, train_cfg.prng_impl
        )
        state = trainer.init_state(seed=ctx.seed, params=params)
        del params
        jax.block_until_ready(state.opt_state)

    def one_round(r: int) -> dict:
        nonlocal state
        with ctx.rec.span("round", r=r) as rec:
            with ctx.rec.span("fit", rows=rows_per_fit, steps=rows_per_fit // bs):
                state, losses = trainer.fit(
                    state, train, batch_size=bs, epochs=E, epoch_offset=r * E
                )
                # fit ends in a host read of each epoch's mean loss; the
                # state is fenced too, so the span holds every step's work.
                jax.block_until_ready(state.params)
            with ctx.rec.span("eval", rows=len(held)):
                metrics = trainer.evaluate(
                    state.params, held, batch_size=ebs, collect_probs=False
                )
        rec["losses_finite"] = bool(np.isfinite(losses).all())
        rec["loss_mean"] = float(np.mean(losses))
        rec["acc"] = float(metrics["Accuracy"])
        return rec

    rounds = harness.run_rounds(ctx, one_round, warm_rounds=WARM_ROUNDS)
    params = state.params
    # At batch 64 the step's scratch leaves 2.6 GB of the chip free: the
    # moments go before the comparison makes the seed's weights again.
    del state
    ref = harness.check_trained(ctx, params, held, what="client model")
    out = harness.round_results(ctx, rounds, ("fit", "eval"))
    ctx.say(
        f"window: {len(rounds)} rounds ({len(ctx.rec.select('round'))} untraced); round_s median "
        f"{ctx.num(out['end_to_end']['round_s'])}; held-out accuracy last round {rounds[-1]['acc']:.1f}%"
    )
    ctx.rec.data.update(chips=1, reference=ref, rounds=len(rounds))
    return out
