"""Flax Kimi-Linear decoder + the DDoS classification head.

The second model class beside ``models/distilbert.py`` (``KimiLinearConfig``,
``models.build_classifier``): a pre-norm decoder whose mixer is, by layer,
Kimi Delta Attention (a gated delta-rule linear attention with a short causal
convolution, ``ops/kda.py``: chunks of 64 tokens; one Pallas kernel does a
chunk's pair matrices, inverse and recurrence in VMEM, one launch for the
batch, and where a gradient is asked for a second one walks the chunks
backwards and transposes all of it in VMEM; both interpreted off the TPU) or
full latent attention without positions (MLA,
``ops/causal_attention.py``), and whose FFN is a dense SwiGLU or a sparse
mixture of SwiGLU experts with a sigmoid router and a shared expert
(``ops/moe.py``), of which this chip holds ``cfg.experts_held``. Block:

    h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))

then a final RMSNorm. The head is the paper's (reference client1.py:57-64)
moved to where a causal model has seen the whole row: the hidden state of
each row's LAST REAL token (by the attention mask) -> Linear(dim, 2), fp32.
A row's padding follows its real tokens, so it changes no real token's state.

Design notes (TPU):
* activations in ``cfg.compute_dtype``; parameters, RMS statistics, softmax,
  the router's scores, the decay gate and the recurrent state in float32;
* ``jax.named_scope``s (``obs/trace.py::SCOPES``) name every operation of a
  part, for the trace: ``kda`` around a linear layer's mixer and beneath it
  ``proj`` (the products with a weight), ``conv``, ``prep`` (projections to
  the kernels' operands), ``chunks`` (``ops/kda.py``: the recurrence, nothing
  else) and ``norm_gate``; ``mla``; ``moe/router``, ``moe/experts`` (beneath it
  ``dispatch``, ``grouped``, ``combine``: ``ops/moe.py``), ``moe/shared``;
  ``ffn_dense``;
* every expert layer sows, in the collection :data:`ROUTE`, the slots routed
  to each held expert and the slots its buffers could not take; a caller
  that applies the model with ``mutable=[ROUTE]`` gets them (the train step
  accumulates them on the device);
* ``cfg.remat``: every block is recomputed in the backward pass, but for the
  router's choice of experts, which is kept (``ops/moe.py::ROUTE_CHOICE``):
  chosen again from a recomputed residual stream it fell otherwise on some
  slots, and an expert's gradient then missed or gained whole tokens.
* the parameter tree keeps the top level ``{"encoder", "classifier"}`` that
  ``make_optimizer``'s head-only scope and the checkpoints assume.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..config import KimiLinearConfig
from ..ops.causal_attention import causal_attention
from ..ops.kda import CHUNK as KDA_CHUNK
from ..ops.kda import kda_chunked
from .blocks import SparseMoE, SwiGLU, causal_conv, conv_init, decoder, dt_bias_init, last_real_token_head
from .blocks import dense as _dense
from .blocks import rms as _rms


def _a_log_init(key, shape, dtype):
    """log of a decay rate uniform in [1, 16], one a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class KDAMixer(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, L, _ = x.shape
        H, d = cfg.kda_heads, cfg.kda_head_dim
        pd = jnp.dtype(cfg.param_dtype)

        def heads(t):  # [B, L, H*d] -> [B, H, L, d]
            return t.reshape(B, L, H, d).transpose(0, 2, 1, 3)

        def conv_proj(name):
            kernel = self.param(f"{name}_conv", conv_init, (cfg.conv_kernel, H * d), pd)
            with jax.named_scope("proj"):
                t = _dense(cfg, H * d, f"{name}_proj")(x)
            with jax.named_scope("conv"):
                t = jax.nn.silu(causal_conv(t, kernel))
            with jax.named_scope("prep"):
                return heads(t)

        q, k, v = conv_proj("q"), conv_proj("k"), conv_proj("v")

        def l2(t):
            t = t.astype(jnp.float32)
            return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

        with jax.named_scope("prep"):
            q, k = l2(q) * d**-0.5, l2(k)
        a_log = self.param("A_log", _a_log_init, (H,), pd)
        dt_bias = self.param("dt_bias", dt_bias_init, (H * d,), pd)
        with jax.named_scope("proj"):
            f = _dense(cfg, H * d, "f_b_proj")(_dense(cfg, cfg.gate_rank, "f_a_proj")(x))
        with jax.named_scope("prep"):
            g = -jnp.exp(a_log.astype(jnp.float32))[None, :, None, None] * heads(
                jax.nn.softplus(f.astype(jnp.float32) + dt_bias.astype(jnp.float32))
            )
        with jax.named_scope("proj"):
            b = _dense(cfg, H, "b_proj")(x)
        with jax.named_scope("prep"):
            beta = jax.nn.sigmoid(b.astype(jnp.float32)).transpose(0, 2, 1)
        o = kda_chunked(q, k, v, g, beta, dtype=jnp.dtype(cfg.compute_dtype))  # [B, H, L, d] float32
        scale = self.param("o_norm", nn.initializers.ones, (d,), pd)
        with jax.named_scope("norm_gate"):
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_norm_eps) * scale
        with jax.named_scope("proj"):
            gate = _dense(cfg, H * d, "g_b_proj")(_dense(cfg, cfg.gate_rank, "g_a_proj")(x))
        with jax.named_scope("norm_gate"):
            o = o.transpose(0, 2, 1, 3).reshape(B, L, H * d) * jax.nn.sigmoid(gate.astype(jnp.float32))
        with jax.named_scope("proj"):
            return _dense(cfg, cfg.dim, "o_proj")(o.astype(x.dtype))


class MLAMixer(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        B, L, _ = x.shape
        H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        q = _dense(cfg, H * (dn + dr), "q_proj")(x).reshape(B, L, H, dn + dr)
        kv = _dense(cfg, cfg.kv_lora_rank + dr, "kv_a_proj")(x)
        c, k_r = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank :]
        kv = _dense(cfg, H * (dn + dv), "kv_b_proj")(_rms(cfg, "kv_a_norm")(c))
        kv = kv.reshape(B, L, H, dn + dv)
        # The 64 "rope" dims carry no rotation (mla_use_nope); one k_r for all heads.
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (B, L, H, dr))], axis=-1
        )
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        o = causal_attention(t(q), t(k), t(kv[..., dn:]), attention_mask)
        return _dense(cfg, cfg.dim, "o_proj")(o.transpose(0, 2, 1, 3).reshape(B, L, H * dv))


class KimiBlock(nn.Module):
    cfg: KimiLinearConfig
    layer: int

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        h = _rms(cfg, "mixer_norm")(x)
        if cfg.mixer(self.layer) == "mla":
            with jax.named_scope("mla"):
                x = x + MLAMixer(cfg, name="mla")(h, attention_mask)
        else:
            with jax.named_scope("kda"):
                x = x + KDAMixer(cfg, name="kda")(h)
        h = _rms(cfg, "ffn_norm")(x)
        if cfg.is_moe(self.layer):
            return x + SparseMoE(cfg, name="moe")(h, attention_mask)
        with jax.named_scope("ffn_dense"):
            return x + SwiGLU(cfg, cfg.hidden_dim, name="ffn")(h)


class KimiLinearEncoder(nn.Module):
    """Token ids + attention mask -> last hidden states ``[B, L, dim]``."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        return decoder(self.cfg, KimiBlock, input_ids, attention_mask)


class KimiLinearClassifier(nn.Module):
    """Encoder + the paper's head on each row's last real token. The model
    has no dropout (its source has none), so ``deterministic`` changes
    nothing; it is taken so that every caller of ``DDoSClassifier`` calls
    this class alike."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        hidden = KimiLinearEncoder(cfg, name="encoder")(input_ids, attention_mask, deterministic)
        return last_real_token_head(cfg, hidden, attention_mask)


#: What ``models.family_module`` hands out of this module.
Classifier = KimiLinearClassifier


def forward_flops(
    cfg: KimiLinearConfig, batch_size: int, seq_len: int | None = None,
    routed_slots_here: float | None = None,
) -> float:
    """Matmul FLOPs of one ``KimiLinearConfig`` forward pass over
    ``batch_size`` rows. Per token and layer: a KDA mixer's projections
    (q, k, v, output, two low-rank gates, write strength), its short
    convolutions and its chunk recurrence (per head ``5*C*d + 6*d^2``: the
    two pair matrices, the substitution, the in-chunk product and the three
    state products); an MLA mixer's projections and causal scores/values
    (``H*(L+1)*(dqk+dv)``); the dense SwiGLU (``6*D*F``); an expert layer's
    router and shared expert, plus ``6*D*F_e`` per token-slot routed to an
    expert held here (``routed_slots_here``; default the mean
    ``tokens*k*held/n_experts`` a layer); and the head a row."""
    L = seq_len if seq_len is not None else cfg.max_len
    D = cfg.dim
    tokens = float(batch_size) * L
    layers = range(cfg.n_layers)
    n_mla = sum(1 for i in layers if cfg.mixer(i) == "mla")
    n_kda = cfg.n_layers - n_mla
    n_moe = sum(1 for i in layers if cfg.is_moe(i))
    n_dense = cfg.n_layers - n_moe
    Hd, r = cfg.kda_heads * cfg.kda_head_dim, cfg.gate_rank
    kda = (
        2 * D * Hd * 4 + 2 * (2 * D * r + 2 * r * Hd) + 2 * D * cfg.kda_heads
        + 3 * 2 * cfg.conv_kernel * Hd
    )
    chunks = cfg.kda_heads * (
        5 * KDA_CHUNK * cfg.kda_head_dim + 6 * cfg.kda_head_dim**2
    )
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    mla = (
        2 * D * H * (dn + dr) + 2 * D * (cfg.kv_lora_rank + dr)
        + 2 * cfg.kv_lora_rank * H * (dn + dv) + 2 * H * dv * D
        + H * (L + 1) * (dn + dr + dv)
    )
    dense = 6 * D * cfg.hidden_dim
    moe = 2 * D * cfg.n_experts + 6 * D * cfg.expert_dim * cfg.n_shared_experts
    per_token = n_kda * kda + n_mla * mla + n_dense * dense + n_moe * moe
    if routed_slots_here is None:
        routed_slots_here = (
            n_moe * tokens * cfg.experts_per_token * cfg.experts_held / cfg.n_experts
        )
    return (
        tokens * per_token + n_kda * tokens * chunks
        + float(routed_slots_here) * 6 * D * cfg.expert_dim
        + float(batch_size) * 2 * D * cfg.n_classes
    )
