"""The plain reference: the Kimi-Linear decoder and the paper's head in
straightforward float32 ``jax.numpy``: forward, loss and gradients.

Follows the published description (Kimi Linear, Moonshot AI 2025; its
``config.json``): pre-norm RMSNorm blocks ``h = x + Mixer(RMSNorm(x)); y = h +
FFN(RMSNorm(h))``, a final RMSNorm, no position encoding anywhere.

- *KDA* (per head, ``dk = dv``): ``q, k, v = SiLU(CausalConv(W x))`` (depthwise
  over time, left-padded), ``q, k`` L2-normalised, ``q`` scaled by
  ``dk^-1/2``; log-decay ``g_t = -exp(A_log_h) softplus(W_f2 W_f1 x_t +
  dt_bias)``; ``b_t = sigmoid(W_b x_t)``; state ``S_t = (I - b_t k_t k_t^T)
  Diag(exp g_t) S_{t-1} + b_t k_t v_t^T``, ``S_0 = 0``; ``o_t = S_t^T q_t``;
  output ``W_o [RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x_t)]``. Computed as
  that recurrence, TOKEN BY TOKEN (``lax.scan``; the scan is checkpointed in
  two levels so that its backward pass fits; nothing is chunked).
- *MLA without positions*: ``q = W_q x``; ``[c, k_r] = W_kva x``, ``c <-
  RMSNorm(c)``; per head ``[k_n, v] = W_kvb c``, ``k = [k_n, k_r]``; causal and
  key-padding mask; ``softmax(q k^T / sqrt(dqk)) v``; ``W_o``. Head by head,
  full ``[L, L]`` scores, no cache.
- *FFN*: SwiGLU; after the leading dense layers ``Shared(x) + sum over the
  chosen experts THAT THIS SHARE HOLDS of w_e Expert_e(x)``: sigmoid router
  over all experts, top-k by score + selection bias, weights renormalised and
  scaled. Every held expert is applied to every token and weighted (zero
  where not chosen): no buffer, no capacity, nothing dropped.
- Head: the hidden state of each row's last real token -> Linear.
- A router's top-k is a discrete decision: two sound computations that differ
  by rounding make it differently on a few tokens, and every continuous number
  downstream then differs by a whole expert's share. ``forced`` hands the
  entry points a choice of experts to compute under (the caller's: the
  program's own), so that continuous numbers are compared under ONE choice;
  the reference's own choice is handed back beside them, to be compared as a
  choice.

Departures from the published model, because the program under test makes the
same ones (benchmark/configs/kimi-linear-48b-a3b-ep32.json lists them): no LM
head, the selection bias a constant, only the held experts' part.

On a TPU a float32 matmul runs at reduced precision unless
``default_matmul_precision("highest")`` is set, so every entry point sets it.
Reads the parameter tree by the names ``models/kimi_linear.py`` gives its
leaves; nothing of the program is imported. Rows are processed one window at
a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9
#: Tokens of the inner level of the checkpointed scan.
SEGMENT = 64


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _conv(x, kernel):
    """Depthwise causal convolution, ``x`` ``[L, C]``, ``kernel`` ``[K, C]``."""
    K, L = kernel.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[j : j + L] * kernel[j] for j in range(K))


def _delta_rule(q, k, v, g, b):
    """The recurrence over tokens. ``q``, ``k``, ``g``: ``[L, H, dk]``;
    ``v``: ``[L, H, dv]``; ``b``: ``[L, H]``. Returns ``[L, H, dv]``."""
    L, H, dk = q.shape
    pad = -L % SEGMENT  # tokens that write nothing and are cut off again
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v, g))
        b = jnp.pad(b, ((0, pad), (0, 0)))

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = tuple(a.reshape((-1, SEGMENT) + a.shape[1:]) for a in (q, k, v, g, b))
    _, o = jax.lax.scan(segment, jnp.zeros((H, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape((L + pad,) + o.shape[2:])[:L]


def _kda(x, p, m, rnd):
    L = x.shape[0]
    H, d = m["kda_heads"], m["kda_head_dim"]
    heads = lambda a: a.reshape(L, H, d)  # noqa: E731
    q, k, v = (
        heads(rnd(jax.nn.silu(_conv(x @ p[f"{n}_proj"]["kernel"], p[f"{n}_conv"])))) for n in "qkv"
    )
    l2 = lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = l2(q) * d**-0.5, l2(k)
    f = (x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"]
    g = -jnp.exp(p["A_log"])[None, :, None] * heads(jax.nn.softplus(f + p["dt_bias"]))
    b = jax.nn.sigmoid(x @ p["b_proj"]["kernel"])
    o = rnd(_delta_rule(q, k, v, g, b))
    o = _rms(o, p["o_norm"], m["rms_norm_eps"])
    gate = jax.nn.sigmoid((x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"])
    return rnd(o.reshape(L, H * d) * gate) @ p["o_proj"]["kernel"]


def _mla(x, mask, p, m, rnd):
    L = x.shape[0]
    H, dn, dr, dv = m["n_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    r = m["kv_lora_rank"]
    q = rnd(x @ p["q_proj"]["kernel"]).reshape(L, H, dn + dr)
    kv = rnd(x @ p["kv_a_proj"]["kernel"])
    c, k_r = rnd(_rms(kv[:, :r], p["kv_a_norm"]["scale"], m["rms_norm_eps"])), kv[:, r:]
    kv = rnd(c @ p["kv_b_proj"]["kernel"]).reshape(L, H, dn + dv)
    allowed = (jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]) & (mask[None, :] > 0)
    bias = jnp.where(allowed, 0.0, NEG_INF)

    @jax.checkpoint
    def head(args):
        q_h, kv_h = args
        k_h = jnp.concatenate([kv_h[:, :dn], k_r], axis=-1)  # k_r shared, not rotated
        s = q_h @ k_h.T / jnp.sqrt(jnp.float32(dn + dr)) + bias
        return rnd(jax.nn.softmax(s, axis=-1)) @ kv_h[:, dn:]

    o = jax.lax.map(head, (q.transpose(1, 0, 2), kv.transpose(1, 0, 2)))  # [H, L, dv]
    return rnd(o.transpose(1, 0, 2).reshape(L, H * dv)) @ p["o_proj"]["kernel"]


def _swiglu(x, p, rnd):
    h = rnd(jax.nn.silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"]))
    return h @ p["down_proj"]["kernel"]


def route(x, p, m, forced=None):
    """The router's choice for tokens ``x`` ``[L, D]``: ``(idx [L, k], w [L,
    k])``, over ALL the layer's experts; with ``forced`` ``[L, k]`` the
    weights of THOSE experts (the choice is given, the scores are the
    reference's own)."""
    s = jax.nn.sigmoid(x @ p["router"])
    idx = jax.lax.top_k(s + p["select_bias"], m["experts_per_token"])[1] if forced is None else forced
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True) * m["routed_scale"]


def _moe(x, p, m, rnd, forced=None):
    """The layer's result and the router's OWN choice. With ``forced`` the
    result is computed under that choice of experts instead (a top-k is a
    discrete decision, and a caller that compares continuous numbers gives
    both sides the same one; the own choice is still handed back, to be
    compared as a choice)."""
    own = route(x, p, m)
    idx, w = own if forced is None else route(x, p, m, forced)
    y = _swiglu(x, p["shared"], rnd)
    for e in range(m["experts_held"]):  # this share's experts, every token
        w_e = jnp.where(idx == m["expert_offset"] + e, w, 0.0).sum(-1)
        h = rnd(jax.nn.silu(x @ p["experts_gate"][e]) * (x @ p["experts_up"][e]))
        y = y + w_e[:, None] * (h @ p["experts_down"][e])
    return y, own


def _window(params, ids, mask, m, rnd, forced=None):
    """One row: last hidden states ``[L, D]``, logits ``[n_classes]`` and
    every expert layer's own routing ``[(idx [L, k], w [L, k]), ...]``;
    ``forced``: a choice of experts ``[L, k]`` for every expert layer, to
    compute under."""
    enc = params["encoder"]
    eps = m["rms_norm_eps"]
    x = enc["word_embeddings"]["embedding"][ids]
    routes = []
    for i in range(m["n_layers"]):
        lp = enc[f"layer_{i}"]
        h = rnd(_rms(x, lp["mixer_norm"]["scale"], eps))
        if i + 1 in m["full_attn_layers"]:
            x = rnd(x + _mla(h, mask, lp["mla"], m, rnd))
        else:
            x = rnd(x + _kda(h, lp["kda"], m, rnd))
        h = rnd(_rms(x, lp["ffn_norm"]["scale"], eps))
        if i >= m["first_dense_layers"]:
            y, chosen = _moe(h, lp["moe"], m, rnd, None if forced is None else forced[len(routes)])
            routes.append(chosen)
            x = rnd(x + y)
        else:
            x = rnd(x + _swiglu(h, lp["ffn"], rnd))
    x = rnd(_rms(x, enc["final_norm"]["scale"], eps))
    last = jnp.maximum(mask.sum() - 1, 0)
    head = params["classifier"]
    return x, x[last] @ head["kernel"] + head["bias"], routes


def _f32(params, rnd):
    return jax.tree.map(lambda a: rnd(jnp.asarray(a, jnp.float32)), params)


def _same(a):
    return a


#: (entry point, the model's items, the rounding) -> its jitted function, so
#: that a second call with the same configuration (the comparison runs on two
#: sets of weights) compiles nothing again.
_JITTED: dict = {}


def _jitted(kind: str, model: dict, rnd, make):
    key = (kind, repr(sorted(model.items())), rnd)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(make())
    return _JITTED[key]


def _rows(input_ids, attention_mask, forced):
    """Per row: ``(ids, mask, that row's forced choices or None)``."""
    ids, mask = jnp.asarray(input_ids), jnp.asarray(attention_mask)
    per_row = [None] * len(ids) if forced is None else zip(*(jnp.asarray(layer) for layer in forced))
    return [(i, a, None if f is None else list(f)) for i, a, f in zip(ids, mask, per_row)]


def forward(params, input_ids, attention_mask, model: dict, rnd=_same, forced=None):
    """Float32 last hidden states ``[B, L, dim]`` and logits ``[B,
    n_classes]`` of the configuration ``model`` (the ``model`` object of a
    ``benchmark/configs/<config>.json``), one window at a time. ``rnd``
    rounds every weight and every sub-layer's output
    (tools/tolerance_probe.py's lower precision). ``forced``: for every
    expert layer a choice of experts ``[B, L, k]`` to compute under (see
    :func:`_moe`)."""
    fn = _jitted(
        f"forward {forced is not None}", model, rnd,
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
    router's own choices on the way, per expert layer ``[(idx [B, L, k], w
    [B, L, k]), ...]``. ``rnd``
    and ``forced`` as in :func:`forward` (the gradient passes through a
    rounding as through the identity)."""
    fn = _jitted(
        f"loss_and_grads {forced is not None}", model, rnd,
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
    return total / n, jax.tree.map(lambda g: g / n, grads), _stack_routes(rows)


def _stack_routes(rows: list) -> list:
    return [tuple(jnp.stack(part) for part in zip(*layer)) for layer in zip(*rows)]


def adam_first_step(grads, *, learning_rate: float, b1: float, b2: float, eps: float):
    """The change of every parameter in Adam's FIRST step from zero moments
    on the gradients ``grads`` (a tree of host arrays), in float64: ``m =
    (1 - b1) g``, ``v = (1 - b2) g^2``, both divided by their bias
    corrections ``1 - b1`` and ``1 - b2``, and ``-learning_rate * m / (sqrt(v)
    + eps)``: the gradient's sign times the learning rate wherever ``|g|`` is
    well above ``eps``. No weight decay, no clipping, no warm-up: the
    optimizer the training cells run."""
    import numpy as np

    def leaf(g):
        g = np.asarray(g, np.float64)
        m_hat = (1.0 - b1) * g / (1.0 - b1)
        v_hat = (1.0 - b2) * g * g / (1.0 - b2)
        return (-learning_rate * m_hat / (np.sqrt(v_hat) + eps)).astype(np.float32)

    return jax.tree.map(leaf, grads)
