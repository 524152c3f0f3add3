"""Single-client train/eval engine.

Replaces the reference's per-batch Python loop (reference client1.py:96-115:
``zero_grad -> forward -> CE loss -> backward -> Adam step`` at ~2.5 batch/s
on CPU) with one jitted, donated train step: ``value_and_grad`` +
``optax.adam(2e-5)`` traced once, every batch a single device dispatch.
Evaluation (reference client1.py:118-150) becomes a jitted step accumulating
sufficient statistics on device; the five reference metrics and the confusion
matrix finalize on host from eight scalars.
"""

from __future__ import annotations

from dataclasses import replace
from functools import lru_cache
from typing import Any, Callable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import ModelConfig, TrainConfig
from ..data.pipeline import TokenizedSplit, batch_iterator, pad_split_to_batch
from ..models import build_classifier, init_params
from ..models.distilbert import DDoSClassifier
from ..models.routing import ROUTE, route_totals, route_zeros
from ..obs.profile import (
    default_ledger,
    maybe_step_profiler,
    note_memory,
    profiled_step_iter,
)
from ..obs.metrics import publish_route
from ..obs.trace import annotate, annotate_iter
from ..ops.metrics import (
    BinaryCounts,
    ClassCounts,
    binary_counts,
    class_counts,
    finalize_class_metrics,
    finalize_metrics,
)
from .batches import PrefetchSlot
from ..utils.logging import get_logger

log = get_logger()


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray  # int32 scalar
    rng: jax.Array  # dropout PRNG key, folded per step
    # Routing counters of a model with expert layers (models/routing.py),
    # accumulated by the step on the device since fit last read them; None
    # (no leaf: the same compiled program) for a model that routes nothing.
    route: Any = None


def warmup_factor(step: jnp.ndarray, warmup_steps: int) -> jnp.ndarray:
    """Linear LR warmup multiplier driven by the GLOBAL step counter.

    Scaling the optimizer's update is equivalent to scaling Adam's learning
    rate; keying on ``state.step`` (never reset) instead of an optax
    schedule count (which lives in opt_state) means per-round optimizer
    resets (FedConfig.reset_optimizer_each_round) restart the moments — the
    reference's fresh-Adam semantics — without restarting the warmup ramp.
    """
    if warmup_steps <= 0:
        return jnp.float32(1.0)
    return jnp.minimum(1.0, (step.astype(jnp.float32) + 1.0) / warmup_steps)


def apply_warmup(updates: Any, step: jnp.ndarray, warmup_steps: int) -> Any:
    """Scale an optimizer update tree by the warmup factor (no-op traced
    away at warmup_steps=0). The single shared implementation for the
    engine, federated, and distillation steps."""
    if warmup_steps <= 0:
        return updates
    w = warmup_factor(step, warmup_steps)
    return jax.tree.map(lambda u: u * w, updates)


def prox_sq(params: Any, anchor: Any) -> jnp.ndarray:
    """FedProx squared distance ``sum ||p - anchor||^2`` over a param
    pytree — the proximal term's single shared implementation for the
    dense (train/fedsteps.py) and sequence-parallel (parallel/fedseq.py)
    federated steps, so their trajectories can't silently diverge."""
    return sum(
        jnp.sum(jnp.square(a - b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(anchor))
    )


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """Adam(lr=2e-5) as the reference (client1.py:380); optional grad clip
    and decoupled weight decay the reference lacks. LR warmup is applied by
    the train step (see :func:`warmup_factor`), not here."""
    tx: list[optax.GradientTransformation] = []
    if cfg.max_grad_norm is not None:
        tx.append(optax.clip_by_global_norm(cfg.max_grad_norm))
    if cfg.weight_decay > 0.0:
        tx.append(
            optax.adamw(
                cfg.learning_rate,
                b1=cfg.b1,
                b2=cfg.b2,
                eps=cfg.eps,
                weight_decay=cfg.weight_decay,
            )
        )
    else:
        tx.append(optax.adam(cfg.learning_rate, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps))
    opt = optax.chain(*tx)
    if cfg.trainable == "head":
        # FedPer-style scope: zero every update outside the classifier
        # head. Labels derive from the params' top-level structure
        # ({"encoder": ..., "classifier": ...}, models/distilbert.py), so
        # the same optimizer serves the single-client engine and the
        # stacked federated steps unchanged.
        opt = optax.multi_transform(
            {"train": opt, "freeze": optax.set_to_zero()},
            param_labels=lambda params: {
                k: jax.tree.map(
                    lambda _: "train" if k == "classifier" else "freeze", v
                )
                for k, v in params.items()
            },
        )
    if cfg.grad_accum_steps > 1:
        opt = optax.MultiSteps(opt, cfg.grad_accum_steps)
    return opt


def loss_fn(model: DDoSClassifier, params, batch, rng) -> jnp.ndarray:
    return loss_and_route_fn(model, params, batch, rng)[0]


def loss_and_route_fn(model: DDoSClassifier, params, batch, rng):
    """The train step's objective and, beside it, the routing counters the
    model's expert layers sowed in this forward pass (``{}`` for a model
    without: nothing is added to its program)."""
    logits, sown = model.apply(
        {"params": params},
        batch["input_ids"],
        batch["attention_mask"],
        False,  # train mode: dropout active
        rngs={"dropout": rng},
        mutable=[ROUTE],
    )
    loss = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["labels"]
    ).mean()
    return loss, route_totals(sown)


def masked_loss_fn(model: DDoSClassifier, params, batch, rng) -> jnp.ndarray:
    """Training CE over the batch's ``valid`` rows only (mean over valid;
    0 for an all-padding batch). Equals :func:`loss_fn` on the valid subset
    — the ragged federated path's per-batch objective, so a padded stacked
    client optimizes exactly what an independent run on its own rows would
    (reference DataLoader semantics incl. the short final batch,
    client1.py:370 with torch's drop_last=False default)."""
    logits = model.apply(
        {"params": params},
        batch["input_ids"],
        batch["attention_mask"],
        False,
        rngs={"dropout": rng},
    )
    per_example = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["labels"]
    )
    v = batch["valid"].astype(jnp.float32)
    return (per_example * v).sum() / jnp.maximum(v.sum(), 1.0)


def eval_counts(
    model: DDoSClassifier, params, batch, valid
) -> tuple[BinaryCounts | ClassCounts, jnp.ndarray]:
    """Shared eval body: masked batch-mean loss + sufficient statistics +
    a scalar score per row. Single source of truth for both the
    single-client and the vmapped federated eval paths (their metrics must
    never diverge). The branch on the head width is STATIC (a trace-time
    Python int), so K = 2 keeps the binary kernels verbatim — bit-identical
    to the pre-K-class path — and K > 2 accumulates the [K, K] confusion
    matrix with ``P(any attack) = 1 - P(class 0)`` as the scalar score the
    serving/drift plane consumes (one [0, 1] score axis for every K)."""
    return eval_counts_and_route(model, params, batch, valid)[:2]


def eval_counts_and_route(model: DDoSClassifier, params, batch, valid):
    """:func:`eval_counts`' two results and, third, the routing counters the
    model's expert layers sowed in this forward pass (``{}`` for a model
    without: nothing is added to its program), so that an evaluation counts
    the slots its expert buffers could not take as a fit does."""
    logits, sown = model.apply(
        {"params": params}, batch["input_ids"], batch["attention_mask"], True,
        mutable=[ROUTE],
    )
    routed = route_totals(sown)
    per_example = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["labels"]
    )
    v = valid.astype(jnp.float32)
    # Batch-mean over valid rows (reference averages per batch then over
    # batches, client1.py:135,144; padded rows must not contribute).
    loss = (per_example * v).sum() / jnp.maximum(v.sum(), 1.0)
    if int(logits.shape[-1]) == 2:
        counts = binary_counts(logits, batch["labels"], loss, valid)
        probs = jax.nn.softmax(logits, axis=-1)[:, 1]
        return counts, probs, routed
    counts = class_counts(logits, batch["labels"], loss, valid)
    probs = 1.0 - jax.nn.softmax(logits, axis=-1)[:, 0]
    return counts, probs, routed


def make_step_telemetry(
    log_every: int, *, prefix: str = "", label: str = "loss"
) -> Callable:
    """Per-step telemetry closure shared by the single-client and federated
    fit loops (the reference's tqdm per-batch loss/rate line,
    client1.py:101,112). Returns ``emit(loss, n_samples, active=None)``:
    every ``log_every`` calls it logs the step, the mean loss — over
    ``active`` clients only when given (idle ragged clients carry masked
    loss 0 and must not understate the fleet mean) — and samples/s since
    the previous log point. Each log point syncs the device once; between
    them losses stay device-side so async dispatch never stalls.
    ``log_every=0`` disables."""
    import time

    acc = {"steps": 0, "samples": 0, "t": time.perf_counter()}

    def emit(loss, n_samples: int, active=None) -> None:
        if not log_every:
            return
        acc["steps"] += 1
        acc["samples"] += int(n_samples)
        if acc["steps"] % log_every:
            return
        if active is None:
            mean = float(jnp.mean(loss))
        else:
            mean = float(jnp.sum(loss) / jnp.maximum(jnp.sum(active), 1.0))
        now = time.perf_counter()
        sps = acc["samples"] / max(now - acc["t"], 1e-9)
        acc["t"], acc["samples"] = now, 0
        log.info(
            f"{prefix}Step {acc['steps']}: {label} {mean:.4f} "
            f"({sps:.1f} samples/s)"
        )

    return emit


def make_train_step(
    model: DDoSClassifier,
    optimizer: optax.GradientTransformation,
    warmup_steps: int = 0,
    *,
    prox_mu: float = 0.0,
    gather: Callable | None = None,
    constrain: Callable | None = None,
    site: str = "engine.train_step",
) -> Callable[[TrainState, dict], tuple[TrainState, jnp.ndarray]]:
    """One jitted SGD step; params/opt_state buffers are donated.

    ``gather``/``constrain`` spec-parameterize the step for FSDP
    shard-at-rest state (see :func:`make_fsdp_train_step`, the named
    entry): gather runs inside a :func:`fsdp_remat_loss` region so the
    backward re-gathers; constrain reduce-scatters grads and pins the
    updated params/opt leaves back onto their shards. None/None (the
    default) is the literal replicated step — ONE update-math
    implementation, the replicated/FSDP trajectories can't drift.

    ``prox_mu > 0`` is the FedProx client step (strategies/ fedprox):
    the returned callable takes ``(state, batch, anchor)`` and adds
    ``mu/2 * ||p - anchor||^2`` (:func:`prox_sq`) to the loss — on the
    RAW (possibly shard-at-rest) params outside the remat region, so
    its gradient ``mu * (p - anchor)`` needs no gather and inherits the
    params' sharding, composing with ``--fsdp`` for free. The anchor is
    a call argument, not a closure: it changes every round and must not
    retrace."""
    ledger = default_ledger()
    note_compile = ledger.hook(site)
    if gather is not None:
        tagged = _tag_gather(gather)
        loss_rm = fsdp_remat_loss(
            lambda p, batch, step_rng: loss_and_route_fn(
                model, tagged(p), batch, step_rng
            )
        )
    else:
        def loss_rm(p, batch, step_rng):
            return loss_and_route_fn(model, p, batch, step_rng)

    def _apply_grads(state, loss, grads, routed):
        # The ONE update tail (constrain -> optimizer -> warmup -> apply)
        # shared by the plain and prox entries — the update math cannot
        # drift between them.
        if constrain is not None:
            grads = constrain(grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            updates = apply_warmup(updates, state.step, warmup_steps)
            params = optax.apply_updates(state.params, updates)
        if constrain is not None:
            params, opt_state = constrain(params), constrain(opt_state)
        route = state.route
        if route is not None:
            route = jax.tree.map(jnp.add, route, routed)
        return TrainState(params, opt_state, state.step + 1, state.rng, route), loss

    if prox_mu > 0.0:
        mu = float(prox_mu)

        def train_step_prox(
            state: TrainState, batch, anchor
        ) -> tuple[TrainState, jnp.ndarray]:
            note_compile(tuple(batch["input_ids"].shape))
            step_rng = jax.random.fold_in(state.rng, state.step)

            def prox_loss(p, batch, step_rng):
                loss, routed = loss_rm(p, batch, step_rng)
                return loss + 0.5 * mu * prox_sq(p, anchor), routed

            (loss, routed), grads = jax.value_and_grad(prox_loss, has_aux=True)(
                state.params, batch, step_rng
            )
            return _apply_grads(state, loss, grads, routed)

        return ledger.jit(site, train_step_prox, donate_argnums=(0,))

    def train_step(state: TrainState, batch) -> tuple[TrainState, jnp.ndarray]:
        # Compile-ledger trace hook (obs/profile.py): this body runs once
        # per traced shape, so the note IS a compile event, never a call.
        note_compile(tuple(batch["input_ids"].shape))
        step_rng = jax.random.fold_in(state.rng, state.step)
        (loss, routed), grads = jax.value_and_grad(loss_rm, has_aux=True)(
            state.params, batch, step_rng
        )
        return _apply_grads(state, loss, grads, routed)

    return ledger.jit(site, train_step, donate_argnums=(0,))


def make_eval_step(
    model: DDoSClassifier,
    *,
    gather: Callable | None = None,
    site: str = "engine.eval_step",
) -> Callable:
    """Jitted eval step -> (BinaryCounts, P(class 1) probs for ROC/PR,
    the routing counters of a model with expert layers or ``{}``).
    ``gather`` places shard-at-rest params replicated at use (the FSDP
    entry :func:`make_fsdp_eval_step`); no remat needed — eval saves no
    residuals."""
    ledger = default_ledger()
    note_compile = ledger.hook(site)

    def eval_step(params, batch, valid) -> tuple[BinaryCounts, jnp.ndarray, dict]:
        note_compile(tuple(batch["input_ids"].shape))
        if gather is not None:
            params = gather(params)
        return eval_counts_and_route(model, params, batch, valid)

    return ledger.jit(site, eval_step)


# ----------------------------------------------------- FSDP (sharded) steps
#: checkpoint_name tag on every FSDP all-gather output: the remat policy
#: below saves EVERYTHING ELSE, so the backward pass re-runs only the
#: gathers instead of retaining full-size gathered weights as residuals
#: — ZeRO-3's recompute-the-gather, not full activation remat.
FSDP_GATHER_NAME = "fsdp_gathered"


def _tag_gather(gather: Callable) -> Callable:
    """checkpoint_name-tag every gathered leaf — the value the FSDP
    remat policy refuses to save (re-gathered in the backward)."""
    from jax.ad_checkpoint import checkpoint_name

    def tagged(params):
        return jax.tree.map(
            lambda x: checkpoint_name(x, FSDP_GATHER_NAME), gather(params)
        )

    return tagged


def _fsdp_policy() -> Callable:
    """Remat policy for the FSDP loss region: save every forward
    intermediate EXCEPT the all-gathered weights — the checkpoint_name-
    tagged gather outputs AND the sharding-constraint outputs feeding
    them. The stock except-these-names policy alone is NOT enough: the
    un-named constraint output is the same full-size array and the
    policy happily saves it, so the backward would retain the gathered
    weights anyway (verified against the saved-residual list; the
    partial eval saves the nearest policy-saveable producer). The
    constraint primitive is a jax internal: a JAX that moves it fails
    this import loudly rather than quietly retaining gathered weights."""
    from jax._src.pjit import sharding_constraint_p

    base = jax.checkpoint_policies.save_anything_except_these_names(
        FSDP_GATHER_NAME
    )

    def policy(prim, *args, **params):
        if prim is sharding_constraint_p:
            return False
        return base(prim, *args, **params)

    return policy


def fsdp_remat_loss(fn: Callable) -> Callable:
    """Wrap the WHOLE loss computation (the gather runs inside ``fn``)
    in ``jax.remat`` under the FSDP policy, so the only values the
    backward recomputes are the all-gathers: full-size gathered weights
    are never retained as residuals and the activations stay saved (no
    forward replay). The remat must wrap the loss, not just the gather
    — a remat region's outputs consumed by un-rematted downstream code
    are always saved, which would defeat the policy."""
    return jax.remat(fn, policy=_fsdp_policy())


def make_fsdp_train_step(
    model: DDoSClassifier,
    optimizer: optax.GradientTransformation,
    warmup_steps: int,
    *,
    prox_mu: float = 0.0,
    gather: Callable,
    constrain: Callable,
    site: str = "engine.fsdp_train_step",
) -> Callable:
    """The engine train step, spec-parameterized for FSDP shard-at-rest:

    * ``gather(params) -> params`` places every leaf replicated (the
      all-gather-at-use); it runs inside a ``jax.remat`` region tagged so
      the backward RE-GATHERS instead of retaining full-size weights.
    * ``constrain(tree) -> tree`` pins a tree back onto its per-leaf
      shard specs — applied to the grads (the reduce-scatter feeding
      sharded Adam), the updated params, and the new optimizer state, so
      the static state never exists full-size outside the gather window.

    SAME implementation as :func:`make_train_step` — this is a thin
    named entry (its own compile-ledger site) over the base builder's
    gather=/constrain= parameterization, so the PRNG stream, warmup,
    and update arithmetic CANNOT drift; the trajectory matches the
    replicated mesh to fp32 reduction-order ulps (the grad
    reduce-scatter may sum partials in a different order than the
    all-reduce; documented and A/B allclose-pinned like the PR-2
    meshed-vs-single contract)."""
    return make_train_step(
        model,
        optimizer,
        warmup_steps,
        prox_mu=prox_mu,
        gather=gather,
        constrain=constrain,
        site=site,
    )


def make_fsdp_eval_step(
    model: DDoSClassifier,
    *,
    gather: Callable,
    site: str = "engine.fsdp_eval_step",
) -> Callable:
    """:func:`make_eval_step` over shard-at-rest params: one gather at
    use, no remat needed (eval saves no residuals)."""
    return make_eval_step(model, gather=gather, site=site)


@lru_cache(maxsize=None)
def _cached_engine_steps(model_cfg: ModelConfig, train_cfg: TrainConfig):
    """Process-wide memo of the jitted single-client programs, keyed on
    the frozen configs they are pure functions of: every Trainer built
    with equal configs (multi-round CLI flows, warm starts, the test
    suite) shares one set of compiled executables. Callers go through
    :func:`_engine_steps`, which canonicalizes step-irrelevant fields out
    of the key."""
    model = build_classifier(model_cfg)
    optimizer = make_optimizer(train_cfg)
    return (
        model,
        optimizer,
        make_train_step(
            model,
            optimizer,
            warmup_steps=train_cfg.warmup_steps,
            prox_mu=train_cfg.prox_mu,
        ),
        make_eval_step(model),
    )


def step_key_cfg(train_cfg: TrainConfig) -> TrainConfig:
    """Zero the TrainConfig fields the compiled programs don't read (host
    loop/init/telemetry knobs) so e.g. seed-only variations share one
    cache entry. Conservative direction: a newly added field defaults to
    being part of the key (worst case a lost share, never wrong sharing).
    The ONE canonicalizer for every compiled-program memo key — the FSDP
    step cache (train/client_mesh._fsdp_steps) keys on it too, so the
    field list can't drift between the two caches."""
    return replace(train_cfg, seed=0, epochs_per_round=1, log_every=0)


def _engine_steps(model_cfg: ModelConfig, train_cfg: TrainConfig):
    """Memo entry point: canonicalize the key, then hit the cache."""
    return _cached_engine_steps(model_cfg, step_key_cfg(train_cfg))


def adopt_aggregate_with_fresh_opt(trainer: Any, state: Any, aggregated: Any) -> Any:
    """The aggregate-adoption semantics every TCP-client trainer shares:
    fresh optimizer from the received aggregate (``trainer.init_state``
    owns placement — engine, meshed, or C=1 fedseq), continuing step
    counter. One implementation so the plain, data-parallel, and
    seq-parallel clients can never drift apart here."""
    trained_steps = int(state.step)
    state = trainer.init_state(params=aggregated)
    return state._replace(step=jnp.asarray(trained_steps, jnp.int32))


class Trainer:
    """Single-client engine: fit for E epochs, evaluate with full metrics."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        *,
        pad_id: int = 0,
        drop_remainder: bool = True,
    ):
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        self.pad_id = pad_id
        self.drop_remainder = drop_remainder
        # One-slot epoch prefetch (train/batches.PrefetchSlot): the
        # TCP round loop arms it before the federated exchange so the
        # next epoch's first batches materialize while the client waits
        # on the aggregate reply. Keyed on (split id, epoch, batch_size)
        # so a mismatched consume falls back to the live iterator.
        self._prefetch = PrefetchSlot()
        self.model, self.optimizer, self.train_step, self.eval_step = (
            _engine_steps(model_cfg, train_cfg)
        )
        # FedProx anchor (train_cfg.prox_mu > 0): the round-start params
        # the proximal term pulls toward — the last adopted aggregate,
        # or the fit-entry params before any round completed. Fresh
        # buffers always (jnp.copy): the train step donates the state,
        # so an aliased anchor would be invalidated mid-epoch.
        self._prox_anchor = None
        # Step-time attribution (obs/profile.py): None unless profiling
        # is armed process-wide (--profile-stride / ObsConfig) — the hot
        # loop then runs the literal pre-profiling path. Re-checked at
        # fit time because the CLI installs the stride after trainers
        # are built.
        self.step_profiler = maybe_step_profiler("train")
        # What fit read of the routing counters (a model with expert
        # layers), summed since the caller last set it to None: slots by
        # held expert and the overflow that must stay 0.
        self.last_route: dict | None = None

    def init_state(self, seed: int | None = None, params: Any | None = None) -> TrainState:
        seed = self.train_cfg.seed if seed is None else seed
        rng = jax.random.key(seed, impl=self.train_cfg.prng_impl)
        if params is None:
            params = init_params(self.model, self.model_cfg, rng)
        params = self._place_init_params(params)
        return TrainState(
            params=params,
            opt_state=self._init_opt_state(params),
            step=jnp.zeros((), jnp.int32),
            rng=jax.random.fold_in(rng, 1),
            route=route_zeros(self.model_cfg),
        )

    def _place_init_params(self, params: Any) -> Any:
        """Hook: where freshly built/adopted params live BEFORE the
        optimizer init sees them. The seed/PRNG/param-init sequence
        above is the ONE trajectory-defining implementation; subclasses
        override only placement (the FSDP trainer scatters onto shards
        so the moments inherit the layout)."""
        return params

    def _init_opt_state(self, params: Any) -> Any:
        """Hook: optimizer-state construction (the FSDP trainer jits it
        so sharding propagation keeps zeros_like moments sharded)."""
        return self.optimizer.init(params)

    def evaluate_state(
        self, state: TrainState, split: TokenizedSplit, **kw: Any
    ) -> dict:
        """Metrics from the live training state — the uniform entry the
        TCP client uses so meshed trainers (whose state params are stacked
        or sharded) evaluate without a host round-trip."""
        return self.evaluate(state.params, split, **kw)

    def host_params(self, state: TrainState) -> Any:
        """Gather the state's params to host numpy — the wire-upload form
        the TCP client feeds FederatedClient.exchange. The single-device
        engine's gather is a plain readback; the replicated mesh trainer
        keeps this (one replica reads back); the FSDP trainer overrides
        it to return device-backed shards so the streamed upload's
        pack-time gather stays lazy."""
        return jax.tree.map(np.asarray, state.params)

    def adopt_aggregate(self, state: TrainState, aggregated: Any) -> TrainState:
        """Continue the next round FROM a received aggregate with a fresh
        Adam (every reference re-launch constructs a new optimizer,
        client1.py:380) but a continuing step counter (LR warmup). The
        single shared implementation for the plain and meshed TCP clients
        — ``init_state`` places the aggregate, so a meshed subclass
        scatters it straight onto its device mesh with no intermediate
        full-replica state. Under FedProx the adopted aggregate IS the
        next round's proximal anchor (w_round_start)."""
        state = adopt_aggregate_with_fresh_opt(self, state, aggregated)
        if self.train_cfg.prox_mu > 0.0:
            self._prox_anchor = jax.tree.map(jnp.copy, state.params)
        return state

    def _round_anchor(self, state: TrainState) -> Any:
        """The FedProx anchor for this fit: the last adopted aggregate,
        or (first round — no aggregate exists yet) a copy of the
        fit-entry params, for which the proximal term starts at zero
        exactly as FedProx prescribes."""
        if self._prox_anchor is None:
            self._prox_anchor = jax.tree.map(jnp.copy, state.params)
        return self._prox_anchor

    def epoch_batches(
        self, split: TokenizedSplit, epoch: int, batch_size: int
    ) -> Iterator[dict]:
        # A matching armed prefetch (prefetch_epoch) serves this epoch's
        # head from the background-materialized buffer; the tail — and
        # any mismatched key — is the live iterator below, so the batch
        # sequence is identical either way.
        it = self._prefetch.consume((id(split), int(epoch), int(batch_size)))
        if it is not None:
            return it
        return self._epoch_iterator(split, epoch, batch_size)

    def _epoch_iterator(self, split, epoch: int, batch_size: int):
        """The epoch's shuffled iterator — the SINGLE derivation of its
        permutation seed, shared by the live path and the armed prefetch
        so a prefetched head can never train on different batches.

        drop_remainder=False (DataConfig.drop_remainder): the final short
        batch trains at its own shape (one extra XLA compilation) — the
        reference DataLoader's drop_last=False semantics (client1.py:370),
        exact per-batch mean loss included. The default drops it for a
        single compiled shape."""
        return batch_iterator(
            split,
            batch_size,
            shuffle=True,
            seed=self.train_cfg.seed * 100_003 + epoch,
            drop_remainder=self.drop_remainder,
        )

    def prefetch_epoch(
        self, split: TokenizedSplit, epoch: int, batch_size: int, *, k: int = 2
    ):
        """Arm the one-slot prefetch for ``epoch``: its permutation and
        first ``k`` batch gathers run on a background thread NOW (the TCP
        client calls this right before blocking on the round exchange, so
        reply latency is hidden behind next-round input-pipeline work).
        The next matching ``epoch_batches`` consumes it; determinism is
        unchanged (same iterator, evaluated early). Returns the
        EpochPrefetcher so the caller can report its measured span."""
        return self._prefetch.arm(
            (id(split), int(epoch), int(batch_size)),
            lambda: self._epoch_iterator(split, epoch, batch_size),
            k=k,
        )

    def _armed_profiler(self):
        """The fit loop's step profiler: the one built at construction,
        or a late arm when the CLI installed the stride afterwards, with
        a fresh reporting window either way. None = profiling off (the
        zero-overhead path)."""
        prof = self.step_profiler
        if prof is None:
            prof = self.step_profiler = maybe_step_profiler("train")
        if prof is not None:
            prof.begin_window()
        return prof

    def step_profile_attrs(self) -> dict:
        """Sampled step p50/p95 attrs of the last fit window (ms) for
        stamping on the client-local span; {} when profiling is off."""
        prof = self.step_profiler
        return prof.span_attrs() if prof is not None else {}

    def fit(
        self,
        state: TrainState,
        split: TokenizedSplit,
        *,
        batch_size: int = 16,
        epochs: int | None = None,
        epoch_offset: int = 0,
        tag: str = "",
    ) -> tuple[TrainState, list[float]]:
        """Train for E epochs. ``epoch_offset`` decorrelates the shuffle
        order across repeated fit() calls (e.g. pass ``round * E`` from a
        multi-round driver); without it every round would replay the same
        batch permutations."""
        step_fn = self.train_step
        if self.train_cfg.prox_mu > 0.0:
            # FedProx: the prox-variant step takes the round anchor as a
            # third argument (same jitted program across rounds — the
            # anchor is data, not a closure constant).
            anchor = self._round_anchor(state)

            def step_fn(s, b, _step=self.train_step, _a=anchor):
                return _step(s, b, _a)

        return self._fit_loop(
            state,
            split,
            step_fn,
            batch_size=batch_size,
            epochs=epochs,
            epoch_offset=epoch_offset,
            tag=tag,
        )

    def _fit_loop(
        self,
        state: TrainState,
        split: TokenizedSplit,
        step_fn: Callable[[TrainState, dict], tuple[TrainState, jnp.ndarray]],
        *,
        batch_size: int,
        epochs: int | None,
        epoch_offset: int,
        tag: str,
        loss_label: str = "Average Loss",
    ) -> tuple[TrainState, list[float]]:
        """Shared epoch loop (plain fit and the KD step both ride it)."""
        epochs = self.train_cfg.epochs_per_round if epochs is None else epochs
        epoch_losses: list[float] = []
        telemetry = make_step_telemetry(
            self.train_cfg.log_every, prefix=tag, label=loss_label
        )
        prof = self._armed_profiler()
        first_memory = prof is not None
        last_loss = None  # carried ACROSS epochs: the drain fence target
        with annotate("fit"):
            for epoch in range(epoch_offset, epoch_offset + epochs):
                # Collect device scalars and sync once per epoch — float(loss)
                # per step would block async dispatch and stall the TPU.
                losses: list[jnp.ndarray] = []
                for batch, sampled in profiled_step_iter(
                    prof,
                    annotate_iter(
                        "fit/next_batch",
                        self.epoch_batches(split, epoch, batch_size),
                    ),
                ):
                    if sampled:
                        # Fenced sampled step: drain the async backlog so
                        # the measurement is this step's own device work,
                        # then split dispatch from device-execute.
                        prof.drain(last_loss)
                        t0 = prof.clock()
                        state, loss = step_fn(state, batch)
                        prof.note_dispatch(prof.clock() - t0)
                        prof.fence(loss)
                    else:
                        state, loss = step_fn(state, batch)
                    losses.append(loss)
                    last_loss = loss
                    telemetry(loss, batch_size)
                    if first_memory:
                        first_memory = False
                        note_memory("post-first-step")
                with annotate("fit/loss_read"):
                    avg = float(jnp.stack(losses).mean()) if losses else 0.0
                epoch_losses.append(avg)
                if state.route is not None:
                    state = self._read_route(state)
                log.info(
                    f"{tag}Epoch [{epoch - epoch_offset + 1}/{epochs}], "
                    f"{loss_label}: {avg:.4f}"
                )
        return state, epoch_losses

    def _read_route(self, state: TrainState) -> TrainState:
        """Read the routing counters the steps accumulated since the last
        read (beside the epoch's loss: the host is waiting there anyway),
        publish them (obs/metrics.py), keep them for the caller
        (``last_route``, summed over a fit's epochs by ``fit``'s callers
        that need it) and start the next count from zero."""
        with annotate("fit/route_read"):
            read = jax.device_get(state.route)
        slots = np.asarray(read["slots"], np.int64)
        overflow, rows = int(read["overflow"]), int(read["rows"])
        self._note_route(slots, overflow, rows, "fit")
        prev = self.last_route or {"slots": 0, "overflow": 0, "rows": 0}
        self.last_route = {
            "slots": prev["slots"] + slots,
            "overflow": prev["overflow"] + overflow,
            "rows": prev["rows"] + rows,
        }
        return state._replace(route=jax.tree.map(jnp.zeros_like, state.route))

    def _note_route(self, slots: np.ndarray, overflow: int, rows: int, where: str) -> None:
        """Publish what ``where`` (``fit`` or ``evaluate``) read of the
        routing counters, and say so loudly when the expert buffers could
        not take every slot: those slots are NOT in the model's result
        (ops/moe.py), so the epoch trained, or the evaluation judged,
        another function than the model's."""
        publish_route(slots, overflow, rows, first_expert=self.model_cfg.expert_offset)
        if overflow:
            log.error(
                f"{where}: {overflow} of {int(slots.sum())} token-slots routed to the "
                f"experts held here were beyond their row buffer and NOT computed "
                f"(ops/moe.py::CAPACITY_FACTOR sizes it; "
                f"fedtpu_moe_overflow_slots_total counts them)"
            )

    def evaluate(
        self,
        params: Any,
        split: TokenizedSplit,
        *,
        batch_size: int = 16,
        collect_probs: bool = True,
    ) -> dict:
        """Five reference metrics + confusion matrix (+ labels/probs for
        ROC & PR curves, the reference's evaluate_model return shape,
        client1.py:150). For a model with expert layers also
        ``routed_overflow``: the token-slots its expert buffers could not
        take in this evaluation (published and logged like a fit's)."""
        with annotate("eval"):
            padded, valid = pad_split_to_batch(split, batch_size, pad_id=self.pad_id)
            # None-init: the first batch's counts type (BinaryCounts for K=2,
            # ClassCounts for K>2) decides the accumulator — eval_counts'
            # static branch keeps the binary path bit-identical.
            totals: BinaryCounts | ClassCounts | None = None
            # Device arrays accumulate; host conversion happens once after the
            # loop so eval pipelines like fit() does.
            probs_dev: list[jnp.ndarray] = []
            valid_slices: list[np.ndarray] = []
            route: dict | None = None  # {} all along for a model that routes nothing
            for start in range(0, len(padded), batch_size):
                sl = slice(start, start + batch_size)
                batch = {
                    "input_ids": padded.input_ids[sl],
                    "attention_mask": padded.attention_mask[sl],
                    "labels": padded.labels[sl],
                }
                counts, probs, routed = self.eval_step(batch=batch, params=params, valid=valid[sl])
                totals = counts if totals is None else totals + counts
                route = routed if route is None else jax.tree.map(jnp.add, route, routed)
                if collect_probs:
                    probs_dev.append(probs)
                    valid_slices.append(valid[sl])
            if totals is None:
                totals = BinaryCounts.zero()
            with annotate("eval/read"):
                metrics = (
                    finalize_class_metrics(totals)
                    if isinstance(totals, ClassCounts)
                    else finalize_metrics(totals)
                )
                if route:
                    read = jax.device_get(route)
                    metrics["routed_overflow"] = int(read["overflow"])
                    self._note_route(
                        np.asarray(read["slots"], np.int64), metrics["routed_overflow"],
                        int(read["rows"]), "evaluate",
                    )
            if collect_probs:
                if probs_dev:
                    all_probs = np.asarray(jnp.concatenate(probs_dev))
                    metrics["probs"] = all_probs[np.concatenate(valid_slices) == 1]
                else:
                    metrics["probs"] = np.array([])
                metrics["labels"] = split.labels.copy()
        return metrics
