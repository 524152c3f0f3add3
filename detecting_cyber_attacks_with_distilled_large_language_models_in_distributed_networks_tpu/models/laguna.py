"""Flax Laguna decoder + the DDoS classification head.

The third model class (``LagunaConfig``, ``models.build_classifier``): a
pre-norm decoder whose attention is, by layer, full or cut to a sliding
window, with grouped key/value heads under a count of query heads that differs
by layer kind, rotary positions of two kinds (``ops/rope.py``), a sigmoid gate
a head on the attention's output, and a dense SwiGLU or the sparse expert
layer the Kimi class has (``models/blocks.py``, ``ops/moe.py``). Block:

    n = RMSNorm(x)
    q = n Wq [T, H_l, d];  k = n Wk, v = n Wv [T, Hkv, d];  q, k rotated
    o = softmax(q k^T / sqrt(d)) v  over the real keys j <= i, and in a
        sliding layer i - j < window;  query head h reads key head h // (H_l / Hkv)
    x = x + concat_h(sigmoid(n Wg)_h o_h) Wo
    x = x + FFN(RMSNorm(x))

then a final RMSNorm and the paper's head on each row's last real token.

Read from the published ``config.json`` alone (its modelling code is not on
this machine); three readings are inferences, each one line here:
``gating: true`` as the per-head sigmoid gate (the published parameter count
fits it and not an element-wise gate; the sibling Laguna-S-2.1 says
``per-head``); the router as a sigmoid with the top 8 renormalised, no
selection bias; SiLU, and no query/key norm.

Design notes (TPU): as ``models/kimi_linear.py``'s (bf16 activations; float32
parameters, RMS statistics, rotation, softmax, gate and router scores; the
routing counters; per-layer recomputation that keeps the router's choice; the
tree's top level). ``jax.named_scope``s (``obs/trace.py::SCOPES``):
``attn/window`` or ``attn/full`` around a layer's attention (the module's name
and a scope inside it), beneath each ``qkv`` (the four projections and the
gate's sigmoid), ``rope``, ``scores`` (``ops/causal_attention.py``: the causal
attention, nothing else; its Pallas kernels under ``causal_flash``) and ``out``
(the gate's product and the output projection); the FFN's as the Kimi class
names them.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..config import LagunaConfig
from ..ops.causal_attention import ATTENTION_RESULT, causal_attention
from ..ops.rope import apply_rope, rope_tables
from .blocks import SparseMoE, SwiGLU, decoder, dense, last_real_token_head, rms


def rotary_tables(cfg: LagunaConfig, kind: str, length: int):
    """``(cos, sin)`` of the layer kind ``kind`` for ``length`` positions."""
    if kind == "sliding":
        return rope_tables(length, int(cfg.head_dim * cfg.sliding_rotary_share), cfg.sliding_rope_theta)
    return rope_tables(
        length, int(cfg.head_dim * cfg.full_rotary_share), cfg.full_rope_theta, cfg.full_rope_factor,
        cfg.full_rope_original_len, cfg.full_rope_beta_fast, cfg.full_rope_beta_slow,
        cfg.full_rope_attention_factor,
    )


class Attention(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        B, L, _ = x.shape
        kind = cfg.layer_types[self.layer]
        H, Hkv, d = cfg.heads_per_layer[self.layer], cfg.n_kv_heads, cfg.head_dim
        with jax.named_scope("window" if kind == "sliding" else "full"):
            with jax.named_scope("qkv"):
                q = dense(cfg, H * d, "q_proj")(x).reshape(B, L, H, d)
                k = dense(cfg, Hkv * d, "k_proj")(x).reshape(B, L, Hkv, d)
                v = dense(cfg, Hkv * d, "v_proj")(x).reshape(B, L, Hkv, d)
                gate = jax.nn.sigmoid(dense(cfg, H, "g_proj")(x).astype(jnp.float32))  # [B, L, H]
            with jax.named_scope("rope"):
                cos, sin = rotary_tables(cfg, kind, L)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            with jax.named_scope("scores"):
                t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
                o = causal_attention(
                    t(q), t(k), t(v), attention_mask, cfg.sliding_window if kind == "sliding" else None
                )
                o = checkpoint_name(o, ATTENTION_RESULT)  # kept across the layer's recomputation
            with jax.named_scope("out"):
                o = (o.transpose(0, 2, 1, 3) * gate[..., None]).astype(x.dtype)
                return dense(cfg, cfg.dim, "o_proj")(o.reshape(B, L, H * d))


class LagunaBlock(nn.Module):
    cfg: LagunaConfig
    layer: int

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        x = x + Attention(cfg, self.layer, name="attn")(rms(cfg, "attn_norm")(x), attention_mask)
        h = rms(cfg, "ffn_norm")(x)
        if cfg.is_moe(self.layer):
            return x + SparseMoE(cfg, select_bias=False, name="moe")(h, attention_mask)
        with jax.named_scope("ffn_dense"):
            return x + SwiGLU(cfg, cfg.hidden_dim, name="ffn")(h)


class LagunaEncoder(nn.Module):
    """Token ids + attention mask -> last hidden states ``[B, L, dim]``."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        return decoder(self.cfg, LagunaBlock, input_ids, attention_mask)


class LagunaClassifier(nn.Module):
    """Encoder + the paper's head on each row's last real token; no dropout,
    and ``deterministic`` taken as ``KimiLinearClassifier`` takes it."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        hidden = LagunaEncoder(cfg, name="encoder")(input_ids, attention_mask, deterministic)
        return last_real_token_head(cfg, hidden, attention_mask)


#: What ``models.family_module`` hands out of this module.
Classifier = LagunaClassifier


def score_keys(kind: str, length: int, window: int) -> float:
    """Keys the queries of one row of ``length`` tokens meet, summed over the
    row: ``i + 1`` for query ``i`` of a full layer, at most ``window`` of a
    sliding one. The count of the mathematics (the band itself), not of the
    blocks the program rounds it to."""
    if kind == "full" or window >= length:
        return length * (length + 1) / 2
    return window * (window + 1) / 2 + (length - window) * window


def forward_flops(
    cfg: LagunaConfig, batch_size: int, seq_len: int | None = None,
    routed_slots_here: float | None = None,
) -> float:
    """Matmul FLOPs of one ``LagunaConfig`` forward pass over ``batch_size``
    rows. Per token and layer: the attention's projections (q and output of
    the layer's heads, k and v of the key/value heads, the gate), its scores
    and values over the keys of :func:`score_keys` (``4 * d`` a query head
    and key); the dense SwiGLU (``6*D*F``) or an expert layer's router and
    shared expert, plus ``6*D*F_e`` per token-slot routed to an expert held
    here (``routed_slots_here``; default the mean ``tokens*k*held/n_experts``
    a layer); and the head a row."""
    L = seq_len if seq_len is not None else cfg.max_len
    D, d = cfg.dim, cfg.head_dim
    rows = float(batch_size)
    tokens = rows * L
    total = rows * 2 * D * cfg.n_classes
    n_moe = 0
    for kind, H, ffn in zip(cfg.layer_types, cfg.heads_per_layer, cfg.ffn_types):
        total += tokens * (2 * D * (2 * H * d + 2 * cfg.n_kv_heads * d) + 2 * D * H)
        total += rows * score_keys(kind, L, cfg.sliding_window) * H * 4 * d
        if ffn == "dense":
            total += tokens * 6 * D * cfg.hidden_dim
        else:
            n_moe += 1
            total += tokens * (2 * D * cfg.n_experts + 6 * D * cfg.shared_dim)
    if routed_slots_here is None:
        routed_slots_here = n_moe * tokens * cfg.experts_per_token * cfg.experts_held / cfg.n_experts
    return total + float(routed_slots_here) * 6 * D * cfg.expert_dim

