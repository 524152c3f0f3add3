"""Flax Qwen3-Next decoder + the DDoS classification head.

The fourth model class (``Qwen3NextConfig``, ``models.build_classifier``): a
pre-norm decoder under the family's zero-centred RMSNorm ``N(x) = x / rms(x) *
(1 + w)`` whose mixer is, by layer, a Gated DeltaNet or a gated softmax
attention, three to one, and whose FFN is in every layer the sparse expert
layer the other decoder classes have (``models/blocks.py``, ``ops/moe.py``)
with a softmax router and a gate on the shared expert. Block:

    x = x + Mixer(N(x));  x = x + MoE(N(x))

*Gated DeltaNet* (``Hk`` key heads under ``Hv`` value heads, ``dk``, ``dv``):

    [q|k|v|z] = n W_qkvz;  [b|a] = n W_ba
    [q|k|v] = silu(causal depthwise conv([q|k|v]))       one kernel, no bias
    value head h reads key head h // (Hv / Hk);  q = l2(q) / sqrt(dk), k = l2(k)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   ONE number a value head and token
    S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T;  o_t = S_t^T q_t
    y = ((o / rms(o)) w_o silu(z)) W_out          this norm's weight is plain

The recurrence is ``ops/kda.py``'s with the decay constant over a head's
channels: ``g`` is broadcast to ``[B, Hv, L, dk]`` and ``kda_chunked`` runs
its two Pallas kernels as they are, ``kda_fwd`` and under ``jax.grad``
``kda_bwd``, each one launch for the row (the cell's shapes are the Kimi
cell's: 32 heads of 128 / 128). The broadcast is not work: a scalar-gate entry that
skips the per-channel pair factors is queued (ROADMAP.md, Queue 2 B).

*Gated attention* (``H`` query heads on ``Hkv`` key/value heads of ``d``):

    [q|gate] = n W_q  split inside each head;  k = n W_k;  v = n W_v
    q = N_d(q), k = N_d(k) per head;  the first ``rotary_share * d`` dimensions rotated
    o = softmax(q k^T / sqrt(d)) v  over the real keys j <= i
    y = (o * sigmoid(gate)) W_o                          element-wise

Read from the published ``config.json`` alone (its modelling code is not on
this machine); what no key states is listed under ``assumed`` in
benchmark/configs/qwen3-next-80b-a3b-ep16.json.

Design notes (TPU): as ``models/kimi_linear.py``'s (bf16 activations; float32
parameters, norms, rotation, softmax, gates, the recurrence's decay and state,
the router's scores; the routing counters; per-layer recomputation that keeps
the router's choice and the attention's result; the tree's top level).
``jax.named_scope``s (``obs/trace.py::SCOPES``): ``gdn`` around a linear
layer's mixer, beneath it what ``kda`` has in ``models/kimi_linear.py``:
``proj`` (``qkvz_proj``, ``ba_proj``, ``o_proj``), ``conv``, ``prep`` (the
head-major copies, l2 norms, repeated keys, ``beta``, the decay and its
broadcast), ``chunks`` (``ops/kda.py``: the chunked recurrence, nothing else)
and ``norm_gate``; ``attn/gated`` around an attention layer's (the module's
name and a scope inside it, as ``models/laguna.py`` has them), beneath it
``scores`` (``ops/causal_attention.py``, nothing else); the FFN's as the other
classes name them. Nothing here runs under a scope named ``kda``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..config import Qwen3NextConfig
from ..ops.causal_attention import ATTENTION_RESULT, causal_attention
from ..ops.kda import CHUNK as KDA_CHUNK
from ..ops.kda import kda_chunked
from ..ops.rope import apply_rope, rope_tables
from .blocks import SparseMoE, causal_conv, conv_init, decoder, dense, dt_bias_init, last_real_token_head, rms


def _a_log_init(key, shape, dtype):
    """log of a decay rate uniform in (0, 16], one a value head."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, dtype)))


class GatedDeltaNet(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, L, _ = x.shape
        Hk, Hv, dk, dv = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim, cfg.linear_value_dim
        pd = jnp.dtype(cfg.param_dtype)
        qk, vz = Hk * dk, Hv * dv
        with jax.named_scope("proj"):
            proj = dense(cfg, 2 * qk + 2 * vz, "qkvz_proj")(x)
            ba = dense(cfg, 2 * Hv, "ba_proj")(x).astype(jnp.float32)
        with jax.named_scope("conv"):
            kernel = self.param("conv", conv_init, (cfg.conv_kernel, 2 * qk + vz), pd)
            qkv = jax.nn.silu(causal_conv(proj[..., : 2 * qk + vz], kernel))

        def heads(t, H):  # [B, L, H * d] -> [B, H, L, d]
            return t.reshape(B, L, H, -1).transpose(0, 2, 1, 3)

        def l2(t):
            t = t.astype(jnp.float32)
            return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

        a_log = self.param("A_log", _a_log_init, (Hv,), pd)
        dt_bias = self.param("dt_bias", dt_bias_init, (Hv,), pd)
        with jax.named_scope("prep"):
            # A key head serves Hv / Hk value heads: value head h reads key head h // (Hv / Hk).
            q = jnp.repeat(l2(heads(qkv[..., :qk], Hk)) * dk**-0.5, Hv // Hk, axis=1)
            k = jnp.repeat(l2(heads(qkv[..., qk : 2 * qk], Hk)), Hv // Hk, axis=1)
            v = heads(qkv[..., 2 * qk :], Hv)
            beta = jax.nn.sigmoid(ba[..., :Hv]).transpose(0, 2, 1)  # [B, Hv, L]
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(ba[..., Hv:] + dt_bias.astype(jnp.float32))
            # One decay a head and token, over every channel of the head's state.
            g = jnp.broadcast_to(g.transpose(0, 2, 1)[..., None], (B, Hv, L, dk))
        o = kda_chunked(q, k, v, g, beta, dtype=jnp.dtype(cfg.compute_dtype))  # [B, Hv, L, dv] float32
        with jax.named_scope("norm_gate"):
            scale = self.param("o_norm", nn.initializers.ones, (dv,), pd)
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_norm_eps) * scale
            z = proj[..., 2 * qk + vz :].astype(jnp.float32)
            o = o.transpose(0, 2, 1, 3).reshape(B, L, vz) * jax.nn.silu(z)
        with jax.named_scope("proj"):
            return dense(cfg, cfg.dim, "o_proj")(o.astype(x.dtype))


class GatedAttention(nn.Module):
    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        B, L, _ = x.shape
        H, Hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        with jax.named_scope("gated"):  # under the module's name: attn/gated
            q_gate = dense(cfg, H * 2 * d, "q_proj")(x).reshape(B, L, H, 2 * d)
            q, gate = q_gate[..., :d], q_gate[..., d:]
            k = dense(cfg, Hkv * d, "k_proj")(x).reshape(B, L, Hkv, d)
            v = dense(cfg, Hkv * d, "v_proj")(x).reshape(B, L, Hkv, d)
            q, k = rms(cfg, "q_norm", True)(q), rms(cfg, "k_norm", True)(k)
            cos, sin = rope_tables(L, int(d * cfg.rotary_share), cfg.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            with jax.named_scope("scores"):
                t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
                o = causal_attention(t(q), t(k), t(v), attention_mask)
                o = checkpoint_name(o, ATTENTION_RESULT)  # kept across the layer's recomputation
            o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(gate.astype(jnp.float32))
            return dense(cfg, cfg.dim, "o_proj")(o.astype(x.dtype).reshape(B, L, H * d))


class Qwen3NextBlock(nn.Module):
    cfg: Qwen3NextConfig
    layer: int

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        h = rms(cfg, "mixer_norm", True)(x)
        if cfg.mixer(self.layer) == "full":
            x = x + GatedAttention(cfg, name="attn")(h, attention_mask)
        else:
            with jax.named_scope("gdn"):
                x = x + GatedDeltaNet(cfg, name="gdn")(h)
        h = rms(cfg, "ffn_norm", True)(x)
        moe = SparseMoE(cfg, select_bias=False, score="softmax", shared_gate=True, name="moe")
        return x + moe(h, attention_mask)


class Qwen3NextEncoder(nn.Module):
    """Token ids + attention mask -> last hidden states ``[B, L, dim]``."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        return decoder(self.cfg, Qwen3NextBlock, input_ids, attention_mask, zero_centred_norm=True)


class Qwen3NextClassifier(nn.Module):
    """Encoder + the paper's head on each row's last real token; no dropout,
    and ``deterministic`` taken as ``KimiLinearClassifier`` takes it."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask, deterministic: bool = True):
        cfg = self.cfg
        hidden = Qwen3NextEncoder(cfg, name="encoder")(input_ids, attention_mask, deterministic)
        return last_real_token_head(cfg, hidden, attention_mask)


#: What ``models.family_module`` hands out of this module.
Classifier = Qwen3NextClassifier


def forward_flops(
    cfg: Qwen3NextConfig, batch_size: int, seq_len: int | None = None,
    routed_slots_here: float | None = None,
) -> float:
    """Matmul FLOPs of one ``Qwen3NextConfig`` forward pass over
    ``batch_size`` rows, of the published mathematics. Per token and layer: a
    Gated DeltaNet's projections, its convolution and the scalar-gate delta
    rule in chunks of ``C`` (per value head ``2 * C * dk`` for the two pair
    matrices, ``C * (dk + dv)`` for the substitution, ``C * dv`` for the
    in-chunk product and ``6 * dk * dv`` for the three state products); a
    gated attention's projections and query ``i`` against ``i + 1`` keys
    (``4 * d`` a query head and key); the router, the shared expert and its
    gate, plus ``6*D*F_e`` per token-slot routed to an expert held here
    (``routed_slots_here``; default the mean ``tokens*k*held/n_experts`` a
    layer); and the head a row."""
    L = seq_len if seq_len is not None else cfg.max_len
    D = cfg.dim
    rows = float(batch_size)
    tokens = rows * L
    n_full = sum(1 for i in range(cfg.n_layers) if cfg.mixer(i) == "full")
    n_linear = cfg.n_layers - n_full
    Hk, Hv, dk, dv = cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim, cfg.linear_value_dim
    qk, vz = Hk * dk, Hv * dv
    linear = (
        2 * D * (2 * qk + 2 * vz) + 2 * D * 2 * Hv + 2 * cfg.conv_kernel * (2 * qk + vz) + 2 * vz * D
        + Hv * (KDA_CHUNK * (3 * dk + 2 * dv) + 6 * dk * dv)
    )
    H, Hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    full = 2 * D * (2 * H * d + 2 * Hkv * d) + 2 * H * d * D
    scores = rows * (L * (L + 1) / 2) * H * 4 * d
    moe = 2 * D * cfg.n_experts + 6 * D * cfg.shared_dim + 2 * D
    if routed_slots_here is None:
        routed_slots_here = cfg.n_layers * tokens * cfg.experts_per_token * cfg.experts_held / cfg.n_experts
    return (
        tokens * (n_linear * linear + n_full * full + cfg.n_layers * moe) + n_full * scores
        + float(routed_slots_here) * 6 * D * cfg.expert_dim + rows * 2 * D * cfg.n_classes
    )
