"""The parts the decoder classes share (``models/kimi_linear.py``,
``models/laguna.py``, ``models/qwen3_next.py``): the bias-free projection and
the RMSNorm in a configuration's types, the SwiGLU, the short causal
convolution and the initialisers of a delta-rule mixer, the sparse expert
layer of which this chip holds a share, the stack of per-layer recomputed
blocks that keeps the router's choice, and the paper's head on each row's last
real token.

They read from a configuration object what every configuration type holds
under one name (``dim``, ``compute_dtype``, ``param_dtype``,
``initializer_range``, ``rms_norm_eps``, and for the expert layer
``n_experts``, ``experts_per_token``, ``routed_scale``, ``expert_dim``,
``shared_dim``, ``experts_held``, ``expert_offset``). The parameter names and
initialisers are those the Kimi class had when these parts lived in its
module (PR 28): a checkpoint or a seed gives the tree it gave.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.causal_attention import ATTENTION_RESULT
from ..ops.moe import ROUTE_CHOICE, expert_rungs, held_experts_ffn, route_topk
from .routing import ROUTE


def dense(cfg, features: int, name: str) -> nn.Dense:
    return nn.Dense(
        features,
        use_bias=False,
        dtype=jnp.dtype(cfg.compute_dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range),
        name=name,
    )


def rms(cfg, name: str, zero_centred: bool = False) -> nn.Module:
    """``x / rms(x) * scale``; ``zero_centred``: the leaf ``scale`` holds ``w``
    of ``x / rms(x) * (1 + w)`` and starts at 0."""
    kw = dict(
        epsilon=cfg.rms_norm_eps,
        dtype=jnp.dtype(cfg.compute_dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
        name=name,
    )
    return ZeroCentredRMSNorm(**kw) if zero_centred else nn.RMSNorm(**kw)


class ZeroCentredRMSNorm(nn.Module):
    epsilon: float
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + self.epsilon)
        return (y * (1.0 + w.astype(jnp.float32))).astype(self.dtype)


def conv_init(key, shape, dtype):
    """Depthwise kernel ``[K, channels]``: uniform in +-1/sqrt(K), the
    family's (torch Conv1d's) default for a fan-in of K."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def dt_bias_init(key, shape, dtype):
    """Inverse softplus of a step ``dt`` log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_conv(x, kernel):
    """Depthwise causal convolution over time: ``y_t = sum_j kernel[j] *
    x_{t-K+1+j}`` with zeros before the row's start. ``x``: ``[B, L, C]``;
    ``kernel``: ``[K, C]``."""
    K = kernel.shape[0]
    L = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j : j + L] * kernel[j].astype(x.dtype) for j in range(K))


class SwiGLU(nn.Module):
    cfg: object
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = jax.nn.silu(dense(cfg, self.width, "gate_proj")(x)) * dense(cfg, self.width, "up_proj")(x)
        return dense(cfg, cfg.dim, "down_proj")(h)


class SparseMoE(nn.Module):
    """``Shared(x) + sum over the chosen experts this chip holds of w_e
    Expert_e(x)``; the router scores all ``n_experts`` in float32. What a
    family's router is, its class says: ``select_bias``: the router has a
    selection bias (a buffer added to the scores for the choice only);
    ``score``: the scores are the logits' ``"sigmoid"`` or their
    ``"softmax"`` over the experts; ``shared_gate``: the shared expert's
    result is multiplied by ``sigmoid(x w_s)``, one number a token."""

    cfg: object
    select_bias: bool = True
    score: str = "sigmoid"
    shared_gate: bool = False

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        B, L, D = x.shape
        pd = jnp.dtype(cfg.param_dtype)
        init = nn.initializers.normal(cfg.initializer_range)
        held, F = cfg.experts_held, cfg.expert_dim
        flat = x.reshape(B * L, D)
        with jax.named_scope("moe/router"):
            w_router = self.param("router", init, (D, cfg.n_experts), pd)
            # A buffer, not a weight: it steers the selection only, gets no
            # gradient, and is zero at the seed.
            bias = (
                self.param("select_bias", nn.initializers.zeros, (cfg.n_experts,), pd)
                if self.select_bias else jnp.zeros((), jnp.float32)
            )
            logits = jnp.dot(
                flat.astype(jnp.float32), w_router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            )
            scores = jax.nn.sigmoid(logits) if self.score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
            idx, w = route_topk(scores, bias, cfg.experts_per_token, cfg.routed_scale)
        self.sow("intermediates", "chosen", idx)
        rungs = expert_rungs(B * L, cfg.experts_per_token, cfg.n_experts, held)
        with jax.named_scope("moe/experts"):
            y, slots, overflow, rows = held_experts_ffn(
                flat, idx, w, attention_mask.reshape(B * L) > 0,
                self.param("experts_gate", init, (held, D, F), pd),
                self.param("experts_up", init, (held, D, F), pd),
                self.param("experts_down", init, (held, F, D), pd),
                offset=cfg.expert_offset,
                capacity=rungs[-1],
                dtype=jnp.dtype(cfg.compute_dtype),
                rungs=rungs,
            )
        add = lambda a, b: a + b  # noqa: E731
        self.sow(ROUTE, "slots", slots, reduce_fn=add, init_fn=lambda: jnp.zeros_like(slots))
        self.sow(ROUTE, "overflow", overflow, reduce_fn=add, init_fn=lambda: jnp.zeros_like(overflow))
        # The rows of its buffer this call moved: ``slots`` filled some, the rest is padding.
        self.sow(ROUTE, "rows", rows, reduce_fn=add, init_fn=lambda: jnp.zeros((), jnp.int32))
        with jax.named_scope("moe/shared"):
            shared = SwiGLU(cfg, cfg.shared_dim, name="shared")(x)
            if self.shared_gate:
                gate = jax.nn.sigmoid(dense(cfg, 1, "shared_gate")(x).astype(jnp.float32))
                shared = (shared * gate).astype(shared.dtype)
        return shared + y.reshape(B, L, D).astype(x.dtype)


def decoder(cfg, block, input_ids, attention_mask, zero_centred_norm: bool = False):
    """Inside an encoder's ``@nn.compact``: the embedding, ``block(cfg, i,
    name="layer_i")`` for every layer and the final RMSNorm (of the family's
    kind: :func:`rms`). Under
    ``cfg.remat`` every block is recomputed in the backward pass, but for the
    router's choice of experts, which is kept (``ops/moe.py::ROUTE_CHOICE``),
    and an attention's result where a class names it
    (``ops/causal_attention.py::ATTENTION_RESULT``)."""
    x = nn.Embed(
        cfg.vocab_size,
        cfg.dim,
        dtype=jnp.dtype(cfg.compute_dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
        embedding_init=nn.initializers.normal(cfg.initializer_range),
        name="word_embeddings",
    )(input_ids)
    if cfg.remat:
        keep = jax.checkpoint_policies.save_only_these_names(ROUTE_CHOICE, ATTENTION_RESULT)
        block = nn.remat(block, policy=keep)
    for i in range(cfg.n_layers):
        x = block(cfg, i, name=f"layer_{i}")(x, attention_mask)
    return rms(cfg, "final_norm", zero_centred_norm)(x)


def last_real_token_head(cfg, hidden, attention_mask):
    """Inside a classifier's ``@nn.compact``: the paper's head (reference
    client1.py:57-64) where a causal model has seen the whole row: the hidden
    state of each row's LAST REAL token (by the attention mask) ->
    Linear(dim, n_classes), fp32."""
    last = jnp.maximum(attention_mask.sum(-1).astype(jnp.int32) - 1, 0)
    pooled = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0, :]
    return nn.Dense(
        cfg.n_classes,
        dtype=jnp.float32,  # head + loss in fp32
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range),
        name="classifier",
    )(pooled.astype(jnp.float32))
