"""Differentially private FedAvg (DP-FedAvg) as an XLA collective.

The reference ships each client's raw fp32 state dict to the server
(reference client1.py:276-295) — the aggregate leaks every client's exact
update and the wire carries unprotected model weights; it has no privacy
mechanism of any kind. Here the round boundary can run the Gaussian
mechanism of DP-FedAvg (McMahan et al., "Learning Differentially Private
Recurrent Language Models", 2018):

1. each client's round update ``delta_c = params_c - anchor`` is clipped to
   a global L2 norm of at most ``clip``,
2. the uniform mean over the ``n`` participating clients is taken,
3. Gaussian noise with std ``noise_multiplier * clip / n`` is added to the
   mean update before it is applied to the anchor and broadcast back.

Adjacency notion (what the reported epsilon means): **zeroed-contribution
adjacency with a fixed divisor** — neighboring executions differ in one
client's clipped update being replaced by the zero vector while the
divisor ``n`` stays fixed, giving L2 sensitivity ``clip / n``. This is the
McMahan et al. convention (their fixed denominator ``qW``). Under the
stricter replace-one adjacency (one client's update swapped for an
arbitrary other) the mean's sensitivity is ``2 * clip / n`` and the same
noise yields roughly 4x weaker (epsilon, delta); halve the effective
noise multiplier fed to the accountant for that conservative bound.

Everything is one jitted function over the ``[C, ...]`` stacked pytree
sharded on the ``clients`` mesh axis — the clip/mean/noise pipeline lowers
to an all-reduce on ICI exactly like plain FedAvg (parallel/fedavg.py),
with the noise generated on device from a replicated key.

``dp_epsilon`` converts (rounds, noise_multiplier) into an (epsilon, delta)
guarantee by Renyi-DP composition. With full participation it composes the
plain Gaussian mechanism; with ``sampling_rate < 1`` it uses the
subsampled-Gaussian-mechanism RDP bound (Mironov, Talwar & Zhang 2019,
integer orders), which is the privacy-amplification-tight accountant —
the plain bound stays valid under subsampling but wastes the
amplification exactly where small-cohort DP needs it. The SGM bound
assumes Poisson sampling: with ``FedConfig.participation_mode="poisson"``
(the default whenever DP is on) ``participation_mask`` draws each client
independently with probability q, so the bound's assumption holds EXACTLY;
the legacy fixed-size sampler remains available, accounted with the
standard q = cohort/C approximation (the banner says which applies).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from .mesh import FedShardings


def client_update_norms(stacked_params: Any, anchor: Any) -> jnp.ndarray:
    """Per-client global L2 norm of ``params - anchor`` across all leaves,
    shape ``[C]``. Computed in fp32 regardless of param dtype."""
    deltas = jax.tree.map(
        lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32),
        stacked_params,
        anchor,
    )
    leaves = jax.tree.leaves(deltas)
    C = leaves[0].shape[0]
    sq = sum(jnp.sum(jnp.square(d.reshape(C, -1)), axis=1) for d in leaves)
    return jnp.sqrt(sq)


def dp_fedavg(
    stacked_params: Any,
    anchor: Any,
    key: jax.Array,
    mask: jnp.ndarray | None,
    *,
    clip: float,
    noise_multiplier: float,
) -> tuple[Any, jnp.ndarray]:
    """Clipped-mean-plus-noise aggregation.

    ``anchor`` is the stacked round-start params (identical along axis 0 —
    the previous round's replicated FedAvg output). Returns the new stacked
    params (every client receives the identical noised global) and the [C]
    pre-clip update norms for observability.

    Masked-out clients (``mask`` 0/1 of shape [C]) contribute nothing and
    both the mean divisor and the noise std shrink to the survivor count,
    keeping the sensitivity bound tight for the clients that did
    participate.
    """
    leaves = jax.tree.leaves(stacked_params)
    C = leaves[0].shape[0]
    m = (
        jnp.ones((C,), jnp.float32)
        if mask is None
        else mask.astype(jnp.float32)
    )
    n = jnp.maximum(m.sum(), 1.0)

    norms = client_update_norms(stacked_params, anchor)
    # Per-client contribution factor: clip-scale * participation / n.
    factor = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12)) * m / n
    sigma = noise_multiplier * clip / n

    flat, treedef = jax.tree.flatten(stacked_params)
    flat_anchor = jax.tree.leaves(anchor)
    out = []
    for i, (p, a) in enumerate(zip(flat, flat_anchor)):
        a32 = a.astype(jnp.float32)
        d = p.astype(jnp.float32) - a32
        fshape = (C,) + (1,) * (d.ndim - 1)
        mean = (d * factor.reshape(fshape)).sum(axis=0)
        noise = sigma * jax.random.normal(
            jax.random.fold_in(key, i), mean.shape, jnp.float32
        )
        # anchor rows are identical; broadcasting the noised mean update
        # over axis 0 IS the FedAvg broadcast back to every client.
        out.append((a32 + mean + noise).astype(p.dtype))
    return jax.tree.unflatten(treedef, out), norms


def make_dp_fedavg_step(
    shardings: FedShardings, *, clip: float, noise_multiplier: float
) -> Callable:
    """Jitted DP round boundary over the mesh: params/anchor sharded
    ``P('clients')``; key and mask replicated. The clip and noise scale are
    trace-time constants (from FedConfig) — one compilation per config."""

    @partial(
        jax.jit,
        in_shardings=(shardings.client, shardings.client, None, None),
        out_shardings=(shardings.client, None),
    )
    def dp_fedavg_step(stacked_params, anchor, key, mask):
        return dp_fedavg(
            stacked_params,
            anchor,
            key,
            mask,
            clip=clip,
            noise_multiplier=noise_multiplier,
        )

    return dp_fedavg_step


DEFAULT_RDP_ORDERS: tuple[float, ...] = tuple(
    [1.0 + x / 10.0 for x in range(1, 100)] + list(range(11, 512))
)


def sgm_rdp(alpha: int, q: float, sigma: float) -> float:
    """RDP of one subsampled-Gaussian-mechanism step at INTEGER order
    ``alpha >= 2`` (Mironov, Talwar & Zhang 2019, eq. for integer orders):

        RDP(alpha) = log( sum_{k=0..alpha} C(alpha,k) (1-q)^(alpha-k) q^k
                          * exp(k (k-1) / (2 sigma^2)) ) / (alpha - 1)

    Computed in log space (the exp(k(k-1)/2sigma^2) terms overflow float64
    near alpha ~ sigma * 50)."""
    if not (isinstance(alpha, int) or float(alpha).is_integer()) or alpha < 2:
        raise ValueError(f"sgm_rdp needs an integer order >= 2, got {alpha}")
    alpha = int(alpha)
    if not 0.0 < q <= 1.0:
        raise ValueError(f"sampling rate q={q} must be in (0, 1]")
    if q == 1.0:
        return alpha / (2.0 * sigma**2)
    log_terms = []
    log_q, log_1q = math.log(q), math.log1p(-q)
    for k in range(alpha + 1):
        log_terms.append(
            math.lgamma(alpha + 1)
            - math.lgamma(k + 1)
            - math.lgamma(alpha - k + 1)
            + (alpha - k) * log_1q
            + k * log_q
            + k * (k - 1) / (2.0 * sigma**2)
        )
    m = max(log_terms)
    log_sum = m + math.log(sum(math.exp(t - m) for t in log_terms))
    return log_sum / (alpha - 1)


def dp_epsilon(
    rounds: int,
    noise_multiplier: float,
    delta: float,
    orders: Sequence[float] = DEFAULT_RDP_ORDERS,
    *,
    sampling_rate: float = 1.0,
) -> float:
    """(epsilon, delta)-DP after ``rounds`` adaptive compositions, via
    Renyi DP: per-step RDP at order alpha composes additively over rounds,
    and conversion to approximate DP takes the minimum of
    ``R * RDP(alpha) + log(1/delta) / (alpha - 1)`` over orders.

    ``sampling_rate=1`` (full participation): the Gaussian mechanism is
    (alpha, alpha / (2 sigma^2))-RDP at every real order. With
    ``sampling_rate < 1`` (partial participation, FedConfig.participation)
    the subsampled-Gaussian bound applies at integer orders >= 2
    (:func:`sgm_rdp`) — privacy amplification by subsampling, the tight
    accounting for small cohorts.

    Client-level guarantee (the clipped unit is one client's whole round
    update). Fixed-size cohorts are accounted as Poisson sampling with
    q = participation (the standard approximation).
    """
    if rounds < 0:
        raise ValueError(f"rounds={rounds} must be >= 0")
    if not 0.0 < delta < 1.0:
        raise ValueError(f"delta={delta} must be in (0, 1)")
    if not 0.0 < sampling_rate <= 1.0:
        raise ValueError(f"sampling_rate={sampling_rate} must be in (0, 1]")
    if noise_multiplier <= 0.0:
        return math.inf
    if rounds == 0:
        return 0.0
    log_delta_inv = math.log(1.0 / delta)
    best = math.inf
    # The full-participation Gaussian bound stays valid under subsampling
    # (removing clients from a round never weakens privacy) and holds at
    # every REAL order — it wins when the optimal order is fractional
    # (< 2), where the integer-order SGM bound cannot go.
    for a in orders:
        if a <= 1.0:
            continue
        eps = rounds * a / (2.0 * noise_multiplier**2) + log_delta_inv / (
            a - 1.0
        )
        best = min(best, eps)
    if sampling_rate == 1.0:
        return best
    for a in orders:
        if a < 2.0 or not float(a).is_integer():
            continue
        eps = rounds * sgm_rdp(int(a), sampling_rate, noise_multiplier)
        eps += log_delta_inv / (a - 1.0)
        best = min(best, eps)
    return best


def dp_epsilon_both(
    rounds: int,
    noise_multiplier: float,
    delta: float,
    *,
    sampling_rate: float = 1.0,
) -> tuple[float, float]:
    """Epsilon under BOTH adjacency notions, same mechanism and noise:

    * zeroed-contribution (McMahan et al. fixed-divisor, sensitivity
      ``clip/n``) — the convention :func:`dp_epsilon` reports;
    * replace-one (one client's update swapped for an arbitrary other,
      sensitivity ``2*clip/n``) — the same noise is only half as many
      sigmas of the doubled sensitivity, i.e. an effective noise
      multiplier of ``noise_multiplier / 2``.

    Operators should see both: the favorable bound alone overstates the
    protection against the stricter, more common adjacency reading."""
    return (
        dp_epsilon(rounds, noise_multiplier, delta, sampling_rate=sampling_rate),
        dp_epsilon(
            rounds, noise_multiplier / 2.0, delta, sampling_rate=sampling_rate
        ),
    )
