"""Flax DistilBERT encoder + DDoS classification head.

Re-implements, TPU-first, what the reference gets from HF PyTorch
(``DistilBertModel`` at reference client1.py:56,61): embeddings (word +
learned position, LayerNorm eps 1e-12), N post-LayerNorm transformer blocks
(MHA -> residual -> LN -> exact-GELU FFN -> residual -> LN), followed by the
reference's head: CLS pooling -> Dropout(0.3) -> Linear(dim, 2) (reference
client1.py:57-58,62-64).

Design notes (TPU):
* depth/width come from ``ModelConfig`` — the same module is DistilBERT-base
  (6 layers) or BERT-base scale-up (12 layers, BASELINE.json config 4).
* activations in ``cfg.compute_dtype`` (bf16 by default) keep the MXU fed;
  params stay fp32; softmax and LayerNorm statistics run in fp32.
* no data-dependent control flow — one ``jit`` trace, static shapes.
* optional ``jax.checkpoint`` (remat) per block trades FLOPs for HBM.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.attention import dot_product_attention, make_attention_bias


def _dtype(name: str):
    return jnp.dtype(name)


def _axis_bound(axis_name: str) -> bool:
    """Trace-time check: are we inside shard_map with ``axis_name`` bound?

    Lets ``attention_impl="ring"`` degrade to the mathematically identical
    unsharded path outside shard_map — in particular ``init_params`` (which
    traces the forward on dummy data with no mesh axes) would otherwise die
    on an unbound axis name.
    """
    try:
        jax.lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def _drop_offsets(cfg: ModelConfig, batch_len: int, *, pos_len: int | None):
    """Global-coordinate offsets for hash-dropout masks inside shard_map:
    axis 0 (batch rows) offsets by the data-shard index — rows on
    different data shards must not reuse one mask — and the position axis
    by the seq-shard index. Unbound axes contribute offset 0."""
    offsets: dict[int, Any] = {}
    if _axis_bound(cfg.data_axis):
        offsets[0] = jax.lax.axis_index(cfg.data_axis) * batch_len
    if pos_len is not None:
        offsets[1] = jax.lax.axis_index(cfg.ring_axis) * pos_len
    return offsets


def _seq_dropout(mod: nn.Module, cfg: ModelConfig, x, rate: float,
                 deterministic: bool, *, pos: bool):
    """Dropout whose mask survives sequence AND batch sharding: on the
    ring path (inside shard_map over cfg.ring_axis) the keep mask is a
    hash of the GLOBAL element coordinates (ops/hash_dropout.py), so
    seq=1 and seq=N runs train identical trajectories and data shards
    draw independent row masks; everywhere else it is plain nn.Dropout.
    ``pos``: axis 1 of x is the (sharded) position axis."""
    if deterministic or rate == 0.0:
        return x
    if cfg.attention_impl == "ring" and _axis_bound(cfg.ring_axis):
        from ..ops.hash_dropout import hash_dropout

        return hash_dropout(
            x, rate, mod.make_rng("dropout"),
            offsets=_drop_offsets(
                cfg, x.shape[0], pos_len=x.shape[1] if pos else None
            ),
        )
    return nn.Dropout(rate)(x, deterministic=False)


class MultiHeadSelfAttention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, bias, deterministic: bool):
        cfg = self.cfg
        dense = lambda name: nn.Dense(  # noqa: E731
            cfg.dim,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name=name,
        )
        B, L, _ = x.shape
        heads = cfg.n_heads
        d = cfg.head_dim

        def split(t):  # [B, L, dim] -> [B, H, L, d]
            return t.reshape(B, L, heads, d).transpose(0, 2, 1, 3)

        if cfg.fused_qkv:
            qd, kd, vd = dense("q"), dense("k"), dense("v")
            if self.is_initializing():
                # Materialize the SAME parameter tree the unfused path
                # builds (child Dense modules named q/k/v) — checkpoints
                # and HF conversion see an identical layout either way.
                probe = jnp.zeros((1, 1, cfg.dim), x.dtype)
                qd(probe), kd(probe), vd(probe)
            p = self.variables["params"]
            cd = _dtype(cfg.compute_dtype)
            W = jnp.concatenate(
                [p["q"]["kernel"], p["k"]["kernel"], p["v"]["kernel"]], axis=-1
            ).astype(cd)  # [D, 3D] — one MXU dispatch instead of three
            bias3 = jnp.concatenate(
                [p["q"]["bias"], p["k"]["bias"], p["v"]["bias"]]
            ).astype(cd)
            qkv = x @ W + bias3
            q, k, v = (split(t) for t in jnp.split(qkv, 3, axis=-1))
        else:
            q, k, v = (
                split(dense("q")(x)),
                split(dense("k")(x)),
                split(dense("v")(x)),
            )
        dropout_rng = (
            None
            if deterministic or cfg.attention_dropout == 0.0
            else self.make_rng("dropout")
        )
        if cfg.attention_impl == "flash":
            from ..ops.flash_attention import flash_attention

            ctx = flash_attention(
                q, k, v, bias,
                dropout_rate=cfg.attention_dropout,
                dropout_rng=dropout_rng,
                deterministic=deterministic,
            )
        elif cfg.attention_impl == "ring" and _axis_bound(cfg.ring_axis):
            # Sequence-sharded forward inside shard_map over cfg.ring_axis.
            from ..parallel.ring_attention import ring_attention

            batch_off = (
                jax.lax.axis_index(cfg.data_axis) * B
                if _axis_bound(cfg.data_axis)
                else 0
            )
            ctx = ring_attention(
                q, k, v, bias,
                axis_name=cfg.ring_axis,
                dropout_rate=cfg.attention_dropout,
                dropout_rng=dropout_rng,
                deterministic=deterministic,
                batch_offset=batch_off,
            )
        elif cfg.attention_impl in ("dot", "ring"):
            # "ring" outside shard_map (e.g. init_params, unsharded eval)
            # runs the identical unsharded math.
            ctx = dot_product_attention(
                q, k, v, bias,
                dropout_rate=cfg.attention_dropout,
                dropout_rng=dropout_rng,
                deterministic=deterministic,
            )
        else:
            raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, L, cfg.dim)
        return dense("o")(ctx)


class TransformerBlock(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, bias, deterministic: bool):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=cfg.layer_norm_eps,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            name=name,
        )
        attn_out = MultiHeadSelfAttention(cfg, name="attn")(x, bias, deterministic)
        attn_out = _seq_dropout(
            self, cfg, attn_out, cfg.dropout, deterministic, pos=True
        )
        x = ln("sa_ln")(x + attn_out)

        h = nn.Dense(
            cfg.hidden_dim,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lin1",
        )(x)
        # cfg.gelu: "exact" = HF's erf GELU (fp32 parity); "tanh" = the
        # tanh form, within a few bf16 ulps of erf and cheaper on the
        # chip; every BERT cell runs erf (config.py ModelConfig.gelu).
        h = jax.nn.gelu(h, approximate=(cfg.gelu == "tanh"))
        h = nn.Dense(
            cfg.dim,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lin2",
        )(h)
        h = _seq_dropout(self, cfg, h, cfg.dropout, deterministic, pos=True)
        return ln("out_ln")(x + h)


class Embeddings(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool):
        cfg = self.cfg
        word = nn.Embed(
            cfg.vocab_size,
            cfg.dim,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="word_embeddings",
        )(input_ids)
        L = input_ids.shape[-1]
        pos_table = nn.Embed(
            cfg.max_position_embeddings,
            cfg.dim,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="position_embeddings",
        )
        if cfg.attention_impl == "ring" and _axis_bound(cfg.ring_axis):
            # Sequence-sharded forward (inside shard_map over cfg.ring_axis):
            # this shard embeds global positions [shard*L_local, ...), not
            # [0, L_local).
            offset = jax.lax.axis_index(cfg.ring_axis) * L
            pos_ids = offset + jnp.arange(L, dtype=jnp.int32)
            pos = pos_table(pos_ids)[None, :, :]
        else:
            pos = pos_table(jnp.arange(L, dtype=jnp.int32))[None, :, :]
        x = word + pos
        x = nn.LayerNorm(
            epsilon=cfg.layer_norm_eps,
            dtype=_dtype(cfg.compute_dtype),
            param_dtype=_dtype(cfg.param_dtype),
            name="ln",
        )(x)
        return _seq_dropout(self, cfg, x, cfg.dropout, deterministic, pos=True)


class DistilBertEncoder(nn.Module):
    """Token ids + attention mask -> last hidden states ``[B, L, dim]``."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        x = Embeddings(cfg, name="embeddings")(input_ids, deterministic)
        bias = make_attention_bias(attention_mask)
        block = TransformerBlock
        if cfg.remat:
            # static_argnums counts self: (self, x, bias, deterministic)
            block = nn.remat(TransformerBlock, static_argnums=(3,))
        for i in range(cfg.n_layers):
            x = block(cfg, name=f"layer_{i}")(x, bias, deterministic)
        return x


class DDoSClassifier(nn.Module):
    """Encoder + the reference's classification head (client1.py:53-65)."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        hidden = DistilBertEncoder(cfg, name="encoder")(
            input_ids, attention_mask, deterministic
        )
        pooled = hidden[:, 0, :]  # CLS token (reference client1.py:62)
        if cfg.attention_impl == "ring" and _axis_bound(cfg.ring_axis):
            # Under sequence sharding only shard 0's token 0 is the global
            # CLS; broadcast it so every shard computes identical logits.
            is_first = (jax.lax.axis_index(cfg.ring_axis) == 0).astype(pooled.dtype)
            pooled = jax.lax.psum(pooled * is_first, cfg.ring_axis)
        # Head dropout ([B, dim], no position axis): still hash-keyed on
        # the ring path so the [C]-vmapped fedseq step stays shard-count-
        # invariant; the reference's Dropout(0.3) site (client1.py:57,63).
        pooled = _seq_dropout(
            self, cfg, pooled, cfg.head_dropout, deterministic, pos=False
        )
        logits = nn.Dense(
            cfg.n_classes,
            dtype=jnp.float32,  # head + loss in fp32
            param_dtype=_dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="classifier",
        )(pooled.astype(jnp.float32))
        return logits


def init_params(
    model: nn.Module, cfg: ModelConfig, rng: jax.Array, batch_size: int = 2
) -> Any:
    # No parameter's shape depends on the row length: a long-context
    # configuration is initialised on a short dummy row.
    length = min(cfg.max_len, 128)
    dummy_ids = jnp.zeros((batch_size, length), jnp.int32)
    dummy_mask = jnp.ones((batch_size, length), jnp.int32)
    return model.init({"params": rng}, dummy_ids, dummy_mask, True)["params"]


def param_count(params: Any) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
