"""The parts the decoder classes share (``models/kimi_linear.py``,
``models/laguna.py``): the bias-free projection and the RMSNorm in a
configuration's types, the SwiGLU, the sparse expert layer of which this chip
holds a share, the stack of per-layer recomputed blocks that keeps the
router's choice, and the paper's head on each row's last real token.

They read from a configuration object what both configuration types hold
under one name (``dim``, ``compute_dtype``, ``param_dtype``,
``initializer_range``, ``rms_norm_eps``, and for the expert layer
``n_experts``, ``experts_per_token``, ``routed_scale``, ``expert_dim``,
``shared_dim``, ``experts_held``, ``expert_offset``). The parameter names and
initialisers are those the Kimi class had when these parts lived in its
module (PR 28): a checkpoint or a seed gives the tree it gave.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.causal_attention import ATTENTION_RESULT
from ..ops.moe import ROUTE_CHOICE, expert_capacity, held_experts_ffn, route_topk
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


def rms(cfg, name: str) -> nn.RMSNorm:
    return nn.RMSNorm(
        epsilon=cfg.rms_norm_eps,
        dtype=jnp.dtype(cfg.compute_dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
        name=name,
    )


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
    Expert_e(x)``; the router scores all ``n_experts`` in float32.
    ``select_bias``: the router has a selection bias (a buffer added to the
    scores for the choice only)."""

    cfg: object
    select_bias: bool = True

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
            scores = jax.nn.sigmoid(
                jnp.dot(
                    flat.astype(jnp.float32), w_router.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )
            )
            idx, w = route_topk(scores, bias, cfg.experts_per_token, cfg.routed_scale)
        self.sow("intermediates", "chosen", idx)
        with jax.named_scope("moe/experts"):
            y, slots, overflow = held_experts_ffn(
                flat, idx, w, attention_mask.reshape(B * L) > 0,
                self.param("experts_gate", init, (held, D, F), pd),
                self.param("experts_up", init, (held, D, F), pd),
                self.param("experts_down", init, (held, F, D), pd),
                offset=cfg.expert_offset,
                capacity=expert_capacity(B * L, cfg.experts_per_token, cfg.n_experts, held),
                dtype=jnp.dtype(cfg.compute_dtype),
            )
        add = lambda a, b: a + b  # noqa: E731
        self.sow(ROUTE, "slots", slots, reduce_fn=add, init_fn=lambda: jnp.zeros_like(slots))
        self.sow(ROUTE, "overflow", overflow, reduce_fn=add, init_fn=lambda: jnp.zeros_like(overflow))
        with jax.named_scope("moe/shared"):
            shared = SwiGLU(cfg, cfg.shared_dim, name="shared")(x)
        return shared + y.reshape(B, L, D).astype(x.dtype)


def decoder(cfg, block, input_ids, attention_mask):
    """Inside an encoder's ``@nn.compact``: the embedding, ``block(cfg, i,
    name="layer_i")`` for every layer and the final RMSNorm. Under
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
    return rms(cfg, "final_norm")(x)


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
