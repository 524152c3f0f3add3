"""The plain reference: the Qwen3-Next decoder and the paper's head in
straightforward float32 ``jax.numpy``: forward, loss and gradients.

Follows the published ``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct
(``model_type: qwen3_next``): pre-norm blocks ``x = x + Mixer(N(x)); x = x +
MoE(N(x))`` and a final ``N``, where ``N(x) = x / rms(x) * (1 + w)`` is the
family's zero-centred RMSNorm (eps 1e-6); layer ``i`` is gated attention where
``(i + 1) % full_attention_interval == 0`` and Gated DeltaNet elsewhere; every
layer's FFN is the expert layer.

- *Gated DeltaNet* (16 key heads under 32 value heads, 128 / 128): ``[q|k|v|z]
  = n W_qkvz``, ``[b|a] = n W_ba``; ``[q|k|v] <- silu(conv4([q|k|v]))`` (ONE
  causal depthwise kernel over the 8,192 channels, left-padded, no bias); value
  head ``h`` reads key head ``h // 2``; ``q <- l2(q) / sqrt(128)``, ``k <-
  l2(k)``; ``beta = sigmoid(b)``; ``g = -exp(A_log) softplus(a + dt_bias)``,
  ONE number a value head and token; state ``S_t = (I - beta_t k_t k_t^T)
  e^{g_t} S_{t-1} + beta_t k_t v_t^T``, ``S_0 = 0``; ``o_t = S_t^T q_t``; ``o
  <- (o / rms(o)) w_o silu(z)`` per head (this norm's weight is plain); ``W_out``.
  Computed as that recurrence, TOKEN BY TOKEN (``lax.scan``, checkpointed in
  two levels so that its backward pass fits; nothing is chunked).
- *Gated attention* (16 query heads on 2 key/value heads of 256): ``[q|gate] =
  n W_q`` split inside each head, ``k = n W_k``, ``v = n W_v``; ``q <-
  N_256(q)``, ``k <- N_256(k)`` per head; the first 64 of a head's 256
  dimensions rotated in pairs ``(x_i, x_{i + 32})`` (rotate-half) by ``position
  * 1e7^(-2i/64)``; query head ``h`` reads key head ``h // 8``; scores ``q k^T /
  sqrt(256)`` over the real keys ``j <= i``; softmax; ``o = P v``; ``o <- o *
  sigmoid(gate)`` element-wise; ``W_o``. HEAD BY HEAD and, inside a head, QUERY
  BLOCK BY QUERY BLOCK (two ``lax.map``s, each block checkpointed, its mask made
  from positions), so that 16,384 keys fit; every key of a row is scored.
- *Expert layer*: ``p = softmax(n W_r)`` over all the experts, the top k by
  ``p``, ``w = p_chosen / sum(p_chosen)``; ``sigmoid(n w_s) Shared(n) + sum
  over the chosen experts THAT THIS SHARE HOLDS of w_e Expert_e(n)``, SwiGLU
  all. Every held expert is applied to every token and weighted (zero where not
  chosen), one after the other: no buffer, no capacity, nothing dropped.
- Head: the hidden state of each row's last real token -> Linear.
- ``forced``: a choice of experts to compute under, as ``kimi_linear_fp32``
  takes it (a top-k is a discrete decision; continuous numbers are compared
  under ONE choice, and the reference's own choice is handed back beside them,
  to be compared as a choice).

What no key of the config states (the zero-centred norm and which norms are,
the one convolution, the split of ``W_q`` inside each head, the float32
softmax before the top k, the l2 eps) is the family's public implementation as
the issue's author knows it, and is listed under ``assumed`` in
benchmark/configs/qwen3-next-80b-a3b-ep16.json.

Departures from the published model, because the program under test makes the
same ones (the configuration's ``departures``): no LM head and no MTP module;
only the held experts' part.

Each layer is checkpointed (the backward pass of one window then holds one
layer's intermediates), which changes no number. On a TPU a float32 matmul
runs at reduced precision unless ``default_matmul_precision("highest")`` is
set, so every entry point sets it. Reads the parameter tree by the names
``models/qwen3_next.py`` gives its leaves; nothing of the program is imported.
Rows are processed one window at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kimi_linear_fp32 import SEGMENT, _conv, adam_first_step  # noqa: F401  (no architecture's: the conv, the scan's segment, Adam)
from .laguna_fp32 import NEG_INF, _f32, _jitted, _rotate, _rows, _same, _softmax, _swiglu, rotary_tables

#: Query rows a block of a head's scores (64 MB of float32 at 16,384 keys).
QUERY_BLOCK = 1024


def _norm(x, w, eps):
    """The zero-centred RMSNorm: the leaf holds ``w`` of ``1 + w``."""
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _delta_rule(q, k, v, g, b):
    """The recurrence over tokens, one decay a head. ``q``, ``k``: ``[L, H,
    dk]``; ``v``: ``[L, H, dv]``; ``g``, ``b``: ``[L, H]``. Returns ``[L, H,
    dv]``."""
    L, H, dk = q.shape
    pad = -L % SEGMENT  # tokens that write nothing and are cut off again
    if pad:
        q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, b = (jnp.pad(a, ((0, pad), (0, 0))) for a in (g, b))

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = tuple(a.reshape((-1, SEGMENT) + a.shape[1:]) for a in (q, k, v, g, b))
    _, o = jax.lax.scan(segment, jnp.zeros((H, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape((L + pad,) + o.shape[2:])[:L]


def _gdn(x, p, m, rnd):
    L = x.shape[0]
    Hk, Hv, dk, dv = m["linear_key_heads"], m["linear_value_heads"], m["linear_key_dim"], m["linear_value_dim"]
    qk, vz = Hk * dk, Hv * dv
    proj = x @ p["qkvz_proj"]["kernel"]
    ba = x @ p["ba_proj"]["kernel"]
    qkv = rnd(jax.nn.silu(_conv(proj[:, : 2 * qk + vz], p["conv"])))
    z = proj[:, 2 * qk + vz :]
    l2 = lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = jnp.repeat(l2(qkv[:, :qk].reshape(L, Hk, dk)) * dk**-0.5, Hv // Hk, axis=1)
    k = jnp.repeat(l2(qkv[:, qk : 2 * qk].reshape(L, Hk, dk)), Hv // Hk, axis=1)
    v = qkv[:, 2 * qk :].reshape(L, Hv, dv)
    b = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, Hv:] + p["dt_bias"])  # [L, Hv]
    o = rnd(_delta_rule(q, k, v, g, b))
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + m["rms_norm_eps"]) * p["o_norm"]
    return rnd(o.reshape(L, vz) * jax.nn.silu(z)) @ p["o_proj"]["kernel"]


def _attention(x, mask, p, m, rnd):
    L = x.shape[0]
    H, Hkv, d, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["rms_norm_eps"]
    q_gate = rnd(x @ p["q_proj"]["kernel"]).reshape(L, H, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    k = rnd(x @ p["k_proj"]["kernel"]).reshape(L, Hkv, d)
    v = rnd(x @ p["v_proj"]["kernel"]).reshape(L, Hkv, d)
    q, k = _norm(q, p["q_norm"]["scale"], eps), _norm(k, p["k_norm"]["scale"], eps)
    cos, sin = rotary_tables(L, int(d * m["rotary_share"]), {"theta": m["rope_theta"]})
    q, k = rnd(_rotate(q, cos, sin)), rnd(_rotate(k, cos, sin))
    group = H // Hkv
    rows = min(QUERY_BLOCK, L)
    n = -(-L // rows)
    q = jnp.pad(q, ((0, n * rows - L), (0, 0), (0, 0)))  # a last block's rows past the row's end are cut off again

    def head(args):
        q_h, h = args  # [n * rows, d]
        k_h, v_h = k[:, h // group], v[:, h // group]

        @jax.checkpoint
        def block(args):
            q_b, start = args
            i, j = start + jnp.arange(rows)[:, None], jnp.arange(L)[None, :]
            bias = jnp.where((j <= i) & (mask[None, :] > 0), 0.0, NEG_INF)
            s = q_b @ k_h.T / jnp.sqrt(jnp.float32(d)) + bias
            return rnd(_softmax(s)) @ v_h

        return jax.lax.map(block, (q_h.reshape(n, rows, d), rows * jnp.arange(n))).reshape(n * rows, d)

    o = jax.lax.map(head, (q.transpose(1, 0, 2), jnp.arange(H)))  # [H, n * rows, d]
    o = o.transpose(1, 0, 2)[:L] * jax.nn.sigmoid(gate)
    return rnd(o.reshape(L, H * d)) @ p["o_proj"]["kernel"]


def route(x, p, m, forced=None):
    """The router's choice for tokens ``x`` ``[L, D]``: ``(idx [L, k], w [L,
    k])``, over ALL the layer's experts; with ``forced`` ``[L, k]`` the
    weights of THOSE experts (the choice is given, the scores are the
    reference's own). Softmax scores, the top k renormalised, no scale."""
    s = jax.nn.softmax(x @ p["router"], axis=-1)
    idx = jax.lax.top_k(s, m["experts_per_token"])[1] if forced is None else forced
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True)


def _shared(x, p, rnd):
    """The shared expert under its own gate, one number a token."""
    return jax.nn.sigmoid(x @ p["shared_gate"]["kernel"]) * _swiglu(x, p["shared"], rnd)


def _moe(x, p, m, rnd, forced=None):
    """The layer's result and the router's OWN choice; with ``forced`` the
    result is computed under that choice instead."""
    own = route(x, p, m)
    idx, w = own if forced is None else route(x, p, m, forced)

    @jax.checkpoint
    def expert(args):  # one of this share's experts on every token, weighted
        e, w_gate, w_up, w_down = args
        w_e = jnp.where(idx == m["expert_offset"] + e, w, 0.0).sum(-1)
        h = rnd(jax.nn.silu(x @ w_gate) * (x @ w_up))
        return w_e[:, None] * (h @ w_down)

    held = jnp.arange(m["experts_held"])
    y, _ = jax.lax.scan(
        lambda y, args: (y + expert(args), None), _shared(x, p, rnd),
        (held, p["experts_gate"], p["experts_up"], p["experts_down"]),
    )
    return y, own


def is_full(m: dict, i: int) -> bool:
    return (i + 1) % m["full_attention_interval"] == 0


def _layer(m, i, rnd):
    """Layer ``i`` as ``(x, mask, its parameters, a forced choice or None) ->
    (x, the router's own choice)``."""
    eps = m["rms_norm_eps"]

    @jax.checkpoint
    def layer(x, mask, lp, choice):
        h = rnd(_norm(x, lp["mixer_norm"]["scale"], eps))
        x = rnd(x + (_attention(h, mask, lp["attn"], m, rnd) if is_full(m, i) else _gdn(h, lp["gdn"], m, rnd)))
        h = rnd(_norm(x, lp["ffn_norm"]["scale"], eps))
        y, chosen = _moe(h, lp["moe"], m, rnd, choice)
        return rnd(x + y), chosen

    return layer


def _window(params, ids, mask, m, rnd, forced=None):
    """One row: last hidden states ``[L, D]``, logits ``[n_classes]`` and
    every layer's own routing ``[(idx [L, k], w [L, k]), ...]``; ``forced``: a
    choice of experts ``[L, k]`` for every layer, to compute under."""
    enc = params["encoder"]
    x = enc["word_embeddings"]["embedding"][ids]
    routes = []
    for i in range(m["n_layers"]):
        x, chosen = _layer(m, i, rnd)(x, mask, enc[f"layer_{i}"], None if forced is None else forced[i])
        routes.append(chosen)
    x = rnd(_norm(x, enc["final_norm"]["scale"], m["rms_norm_eps"]))
    last = jnp.maximum(mask.sum() - 1, 0)
    head = params["classifier"]
    return x, x[last] @ head["kernel"] + head["bias"], routes


def forward(params, input_ids, attention_mask, model: dict, rnd=_same, forced=None):
    """Float32 last hidden states ``[B, L, dim]`` and logits ``[B,
    n_classes]`` of the configuration ``model`` (the ``model`` object of a
    ``benchmark/configs/<config>.json``), one window at a time. ``rnd``
    rounds every weight and every sub-layer's output
    (tools/window_probe.py's lower precision). ``forced``: for every layer a
    choice of experts ``[B, L, k]`` to compute under."""
    fn = _jitted(
        f"qwen3_next forward {forced is not None}", model, rnd,
        lambda: lambda p, i, a, f: _window(_f32(p, rnd), i, a, model, rnd, f)[:2],
    )
    hidden, logits = [], []
    with jax.default_matmul_precision("highest"):
        for i, a, f in _rows(input_ids, attention_mask, forced):
            h, z = fn(params, i, a, f)
            hidden.append(h)
            logits.append(z)
    return jnp.stack(hidden), jnp.stack(logits)


def _row_loss(params, ids, mask, label, m, rnd, forced=None):
    _, z, routes = _window(_f32(params, rnd), ids, mask, m, rnd, forced)
    return jax.nn.logsumexp(z) - z[label], routes


def loss_and_grads(params, input_ids, attention_mask, labels, model: dict, rnd=_same, forced=None):
    """The mean cross-entropy over the rows, its gradient with respect to
    every parameter (a tree like ``params``), float32, row by row, and the
    router's own choices on the way, per layer ``[(idx [B, L, k], w [B, L,
    k]), ...]``. ``rnd`` and ``forced`` as in :func:`forward` (the gradient
    passes through a rounding as through the identity)."""
    fn = _jitted(
        f"qwen3_next loss_and_grads {forced is not None}", model, rnd,
        lambda: jax.value_and_grad(lambda p, i, a, y, f: _row_loss(p, i, a, y, model, rnd, f), has_aux=True),
    )
    n = len(input_ids)
    total, grads, rows = 0.0, None, []
    with jax.default_matmul_precision("highest"):
        for (i, a, f), y in zip(_rows(input_ids, attention_mask, forced), jnp.asarray(labels)):
            (value, routes), g = fn(params, i, a, y, f)
            total += float(value)
            rows.append(routes)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    routes = [tuple(jnp.stack(part) for part in zip(*layer)) for layer in zip(*rows)]
    return total / n, jax.tree.map(lambda g: g / n, grads), routes
