"""Knowledge distillation: teacher -> student (the "Distilled" capability).

The reference's entire relationship to distillation is consuming a
pre-distilled checkpoint (HF DistilBERT, reference client1.py:56) — it
cannot produce one. Here the DistilBERT recipe itself is a first-class
trainer: a (typically 2x-deeper) teacher's soft targets supervise the
student through a temperature-T KL term blended with hard-label CE
(``DistillConfig.alpha``), and the student can be initialized from every
other teacher layer — the published DistilBERT init.

TPU shape: one jitted step runs teacher forward (no grad, eval mode) and
student forward/backward back-to-back — both matmul stacks stay on the MXU
with no host round-trip between them. The distilled student's params feed
the ordinary :class:`~..train.engine.Trainer` / federated stack unchanged,
so "distill once, then federate the student" composes out of the box.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax

from ..config import DistillConfig, ModelConfig, TrainConfig
from ..data.pipeline import TokenizedSplit
from ..models import build_classifier
from .engine import Trainer, TrainState, apply_warmup


def distillation_loss(
    student_logits: jnp.ndarray,
    teacher_logits: jnp.ndarray,
    labels: jnp.ndarray,
    *,
    temperature: float,
    alpha: float,
) -> jnp.ndarray:
    """``alpha * T^2 * KL(teacher_T || student_T) + (1-alpha) * CE(labels)``.

    The T^2 factor keeps the soft-target gradient magnitude independent of
    temperature (Hinton et al.'s convention, which the DistilBERT recipe
    follows). Computed in fp32.
    """
    s = student_logits.astype(jnp.float32)
    t = teacher_logits.astype(jnp.float32)
    log_p_t = jax.nn.log_softmax(t / temperature, axis=-1)
    log_p_s = jax.nn.log_softmax(s / temperature, axis=-1)
    kl = (jnp.exp(log_p_t) * (log_p_t - log_p_s)).sum(axis=-1).mean()
    ce = optax.softmax_cross_entropy_with_integer_labels(s, labels).mean()
    return alpha * temperature * temperature * kl + (1.0 - alpha) * ce


def init_student_from_teacher(
    student_params: Any, teacher_params: Any, *, stride: int
) -> Any:
    """DistilBERT init: student layer ``i`` <- teacher layer ``i * stride``;
    embeddings and classifier head copied verbatim. Widths must match
    (depth-only distillation); raises on any shape mismatch so a silently
    un-initialized student can't train.
    """
    out = jax.tree.map(lambda x: x, student_params)  # structural copy
    t_enc = teacher_params["encoder"]
    s_enc = student_params["encoder"]
    n_student = sum(1 for k in s_enc if k.startswith("layer_"))
    n_teacher = sum(1 for k in t_enc if k.startswith("layer_"))
    if (n_student - 1) * stride >= n_teacher:
        raise ValueError(
            f"stride {stride} maps student layer {n_student - 1} to teacher "
            f"layer {(n_student - 1) * stride}, but teacher has {n_teacher}"
        )

    def _copy(dst, src, where):
        def _leaf(d, s):
            if jnp.shape(d) != jnp.shape(s):
                raise ValueError(
                    f"{where}: teacher leaf {jnp.shape(s)} != student "
                    f"{jnp.shape(d)} — depth-only distillation requires "
                    "matching widths"
                )
            # Materialize a distinct buffer: the student state is donated by
            # the distill step while the teacher is passed alongside it —
            # aliased buffers would poison the donation.
            return jnp.array(s)

        return jax.tree.map(_leaf, dst, src)

    new_enc = dict(out["encoder"])
    new_enc["embeddings"] = _copy(
        s_enc["embeddings"], t_enc["embeddings"], "embeddings"
    )
    for i in range(n_student):
        new_enc[f"layer_{i}"] = _copy(
            s_enc[f"layer_{i}"], t_enc[f"layer_{i * stride}"], f"layer_{i}"
        )
    out = dict(out)
    out["encoder"] = new_enc
    out["classifier"] = _copy(
        student_params["classifier"], teacher_params["classifier"], "classifier"
    )
    return out


class DistillTrainer(Trainer):
    """Student trainer whose step distills from a frozen teacher.

    Inherits init/eval/reporting from :class:`Trainer`; only the train step
    differs (teacher forward + KD loss instead of plain CE).
    """

    def __init__(
        self,
        student_cfg: ModelConfig,
        teacher_cfg: ModelConfig,
        train_cfg: TrainConfig,
        distill_cfg: DistillConfig,
        *,
        pad_id: int = 0,
    ):
        super().__init__(student_cfg, train_cfg, pad_id=pad_id)
        if teacher_cfg.dim != student_cfg.dim:
            raise ValueError(
                f"teacher dim {teacher_cfg.dim} != student dim "
                f"{student_cfg.dim}: depth-only distillation"
            )
        self.teacher_cfg = teacher_cfg
        self.distill_cfg = distill_cfg
        self.teacher_model = build_classifier(teacher_cfg)
        self.distill_step = self._make_distill_step()

    def _make_distill_step(self):
        model, teacher = self.model, self.teacher_model
        dcfg = self.distill_cfg

        @partial(jax.jit, donate_argnums=(0,))
        def step(state: TrainState, teacher_params, batch):
            step_rng = jax.random.fold_in(state.rng, state.step)
            # Teacher: eval mode, no grad — soft targets only.
            t_logits = jax.lax.stop_gradient(
                teacher.apply(
                    {"params": teacher_params},
                    batch["input_ids"],
                    batch["attention_mask"],
                    True,
                )
            )

            def loss_fn(p):
                s_logits = model.apply(
                    {"params": p},
                    batch["input_ids"],
                    batch["attention_mask"],
                    False,
                    rngs={"dropout": step_rng},
                )
                return distillation_loss(
                    s_logits,
                    t_logits,
                    batch["labels"],
                    temperature=dcfg.temperature,
                    alpha=dcfg.alpha,
                )

            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            updates = apply_warmup(updates, state.step, self.train_cfg.warmup_steps)
            params = optax.apply_updates(state.params, updates)
            return TrainState(
                params, opt_state, state.step + 1, state.rng, state.route
            ), loss

        return step

    def init_student_state(
        self, teacher_params: Any, seed: int | None = None
    ) -> TrainState:
        """Fresh student state, layer-initialized from the teacher when
        ``DistillConfig.init_from_teacher``. The stride is
        ``teacher_layers // student_layers`` (floored — non-divisible depths
        take the first strided layers, e.g. 5 -> 2 copies teacher layers
        0 and 2)."""
        state = self.init_state(seed=seed)
        if not self.distill_cfg.init_from_teacher:
            return state
        stride = max(1, self.teacher_cfg.n_layers // self.model_cfg.n_layers)
        params = init_student_from_teacher(
            state.params, teacher_params, stride=stride
        )
        return state._replace(params=params, opt_state=self.optimizer.init(params))

    def distill(
        self,
        state: TrainState,
        teacher_params: Any,
        split: TokenizedSplit,
        *,
        batch_size: int = 16,
        epochs: int | None = None,
        epoch_offset: int = 0,
        tag: str = "",
    ) -> tuple[TrainState, list[float]]:
        """KD epochs over the split — rides ``Trainer._fit_loop`` (same
        shuffle decorrelation via ``epoch_offset`` for multi-round drivers)."""
        return self._fit_loop(
            state,
            split,
            lambda s, b: self.distill_step(s, teacher_params, b),
            batch_size=batch_size,
            epochs=epochs,
            epoch_offset=epoch_offset,
            tag=tag,
            loss_label="KD loss",
        )
