"""Server aggregation strategies over flat numpy param dicts.

The comparative study (arXiv:2509.17836) shows plain FedAvg degrading
hard on non-IID cybersecurity partitions; TurboSVM-FL (arXiv:2401.12012)
shows aggregation-side boosting recovering lazy-client fleets. This
module is the registry both the TCP round engine (comm/server.py) and
the scenario replay gates draw from.

Contract
--------
A strategy NEVER touches the fold: ``comm/stream_agg.py`` keeps folding
raw leaves in ascending-id order into the bit-exact weighted mean. At
finalize time the server calls::

    new_global = strategy.apply(prev_global, mean,
                                round_no=r, client_stats=stats)

with ``prev_global`` the previous post-strategy global (None on the
first round), ``mean`` the folded mean, and ``client_stats`` the
per-client fold stats from ``StreamAgg.client_stats()``. ``apply`` is a
pure function of ``(prev_global, mean)`` — ``client_stats`` informs
telemetry only — so a replay fed the same clean means reproduces the
live global bit-for-bit and the crc gates extend to every strategy.

FedOpt strategies treat the round's mean as a pseudo-gradient
``g = prev - mean`` and run a persistent optax server optimizer over it,
reusing ``parallel/fedavg.py::make_server_optimizer`` (Reddi et al.).
At server_lr=1 / momentum=0 this reduces exactly to the mean.
"""

from __future__ import annotations

from typing import Any

import numpy as np

__all__ = [
    "STRATEGIES",
    "Strategy",
    "FedAvg",
    "FedProx",
    "Momentum",
    "FedOpt",
    "HeadBoost",
    "parse_strategy",
    "make_strategy",
]

Flat = dict[str, np.ndarray]


class Strategy:
    """Base: a stateful per-server object applied once per round."""

    name: str = ""

    def params(self) -> dict[str, Any]:
        """Hyperparameters for wire-meta / trace / metrics stamping."""
        return {}

    def client_mu(self) -> float:
        """Proximal term advertised to clients (FedProx); 0 = none."""
        return 0.0

    def reset(self) -> None:
        """Drop optimizer state (model shape changed / replay restart)."""

    def describe(self) -> dict[str, Any]:
        return {"name": self.name, "params": self.params()}

    def export_state(self) -> "list[np.ndarray] | None":
        """Optimizer-state leaves for server-restart checkpointing
        (comm/server.py strategy_state_path); None = stateless."""
        return None

    def restore_state(
        self, leaves: "list[np.ndarray]", template_params: Flat
    ) -> bool:
        """Rebuild optimizer state from exported leaves against the
        restored global. False = leaves don't fit (start fresh)."""
        return False

    def apply(
        self,
        prev: Flat | None,
        mean: Flat,
        *,
        round_no: int = 0,
        client_stats: dict[int, dict[str, float]] | None = None,
    ) -> Flat:
        raise NotImplementedError


def _compatible(prev: Flat | None, mean: Flat) -> bool:
    """prev is usable as the round anchor: same keys, same shapes."""
    if prev is None:
        return False
    if sorted(prev) != sorted(mean):
        return False
    return all(
        np.shape(prev[k]) == np.shape(mean[k]) for k in sorted(mean)
    )


class FedAvg(Strategy):
    """Identity on the folded mean — the historical fold, bit-for-bit."""

    name = "fedavg"

    def apply(self, prev, mean, *, round_no=0, client_stats=None):
        return mean


class FedProx(Strategy):
    """Server-side identity; the proximal term lives on the CLIENT.

    FedProx (Li et al.) anchors each client's local loss with
    ``mu/2 * ||w - w_round_start||^2``. The server's aggregation is the
    plain weighted mean, so ``apply`` is the identity; the strategy
    carries ``mu`` so the round-START wire meta advertises it and the
    scenario harness threads it into the client train-step builders
    (train/engine.py, TrainConfig.prox_mu).
    """

    name = "fedprox"

    def __init__(self, mu: float = 0.01):
        if mu <= 0.0:
            raise ValueError(f"fedprox mu={mu} must be > 0")
        self.mu = float(mu)

    def params(self):
        return {"mu": self.mu}

    def client_mu(self):
        return self.mu

    def apply(self, prev, mean, *, round_no=0, client_stats=None):
        return mean


class _ServerOptStrategy(Strategy):
    """Shared FedOpt machinery: pseudo-gradient + persistent optax tx.

    ``g = prev - mean``; ``new = prev + tx(g)``. The optimizer state
    persists across rounds (unlike the per-round client optimizer
    reset), mirroring parallel/fedavg.py's mesh-tier server_opt.
    """

    def __init__(self, server_opt: str, lr: float, momentum: float = 0.9):
        if lr <= 0.0:
            raise ValueError(f"{self.name} lr={lr} must be > 0")
        if not 0.0 <= momentum < 1.0:
            raise ValueError(
                f"{self.name} momentum={momentum} must be in [0, 1)"
            )
        self._server_opt = server_opt
        self.lr = float(lr)
        self.momentum = float(momentum)
        self._tx = None
        self._opt_state = None

    def _transform(self):
        if self._tx is None:
            # Lazy: keeps `fedtpu serve --strategy fedavg` from paying
            # the jax/optax import at CLI start.
            from ..config import FedConfig
            from ..parallel.fedavg import make_server_optimizer

            self._tx = make_server_optimizer(
                FedConfig(
                    server_opt=self._server_opt,
                    server_lr=self.lr,
                    server_momentum=self.momentum,
                )
            )
        return self._tx

    def reset(self):
        self._opt_state = None

    def export_state(self):
        """The optax state's leaves in tree order (counts, momenta,
        second moments — all dense arrays), host-materialized so the
        server's npz writer can persist them without touching jax."""
        if self._opt_state is None:
            return None
        import jax

        return [
            np.asarray(leaf)
            for leaf in jax.tree_util.tree_leaves(self._opt_state)
        ]

    def restore_state(self, leaves, template_params):
        """Inverse of :func:`export_state`: build a fresh ``tx.init``
        state over the restored global (the structure/treedef donor),
        then substitute the persisted leaves. Leaf count or any
        shape mismatch means the model or optimizer changed — refuse,
        the caller starts with fresh optimizer memory."""
        import jax

        tx = self._transform()
        template = tx.init(
            {
                k: np.asarray(template_params[k], np.float32)
                for k in sorted(template_params)
            }
        )
        t_leaves, treedef = jax.tree_util.tree_flatten(template)
        if len(leaves) != len(t_leaves):
            return False
        cast = []
        for got, want in zip(leaves, t_leaves):
            w = np.asarray(want)
            if np.shape(got) != w.shape:
                return False
            cast.append(np.asarray(got, w.dtype))
        self._opt_state = jax.tree_util.tree_unflatten(treedef, cast)
        return True

    def apply(self, prev, mean, *, round_no=0, client_stats=None):
        if not _compatible(prev, mean):
            # First round (no global yet) or model shape changed: the
            # mean IS the new global; optimizer state restarts.
            self.reset()
            return mean
        import optax  # deferred with the tx build

        tx = self._transform()
        prev32 = {
            k: np.asarray(prev[k], np.float32) for k in sorted(mean)
        }
        grad = {
            k: prev32[k] - np.asarray(mean[k], np.float32)
            for k in sorted(mean)
        }
        if self._opt_state is None:
            self._opt_state = tx.init(prev32)
        updates, self._opt_state = tx.update(grad, self._opt_state, prev32)
        new = optax.apply_updates(prev32, updates)
        return {k: np.asarray(new[k], np.float32) for k in sorted(new)}


class Momentum(_ServerOptStrategy):
    """FedAvgM: heavy-ball memory over round updates (Hsu et al.)."""

    name = "momentum"

    def __init__(self, lr: float = 1.0, momentum: float = 0.9):
        super().__init__("momentum", lr, momentum)

    def params(self):
        return {"lr": self.lr, "momentum": self.momentum}


class FedOpt(_ServerOptStrategy):
    """FedAdam / FedYogi: adaptive per-parameter server steps."""

    name = "fedopt"

    def __init__(self, opt: str = "adam", lr: float = 0.1):
        opt = str(opt)
        if opt not in ("adam", "yogi"):
            raise ValueError(f"fedopt opt={opt!r} must be adam|yogi")
        self.opt = opt
        super().__init__(opt, lr)

    def params(self):
        return {"opt": self.opt, "lr": self.lr}


class HeadBoost(Strategy):
    """TurboSVM-style head-level boost (arXiv:2401.12012, adapted).

    Lazy fleets move the classifier head too slowly: the encoder's mean
    drift is tiny but the head — the only task-specific capacity — gets
    diluted by barely-trained uploads. Boost the head's round update by
    ``gamma`` while the body takes the plain mean::

        head leaf:  new = prev + gamma * (mean - prev)
        body leaf:  new = mean

    Degrades to exact FedAvg when no leaf matches ``match`` or there is
    no previous global to measure the update against.
    """

    name = "headboost"

    def __init__(self, gamma: float = 1.5, match: str = "classifier"):
        if gamma <= 0.0:
            raise ValueError(f"headboost gamma={gamma} must be > 0")
        if not match:
            raise ValueError("headboost match pattern must be non-empty")
        self.gamma = float(gamma)
        self.match = str(match)

    def params(self):
        return {"gamma": self.gamma, "match": self.match}

    def apply(self, prev, mean, *, round_no=0, client_stats=None):
        if not _compatible(prev, mean):
            return mean
        out: Flat = {}
        for k in sorted(mean):
            m = np.asarray(mean[k], np.float32)
            if self.match in k:
                p = np.asarray(prev[k], np.float32)
                out[k] = np.asarray(
                    p + self.gamma * (m - p), np.float32
                )
            else:
                out[k] = m
        return out


STRATEGIES: dict[str, type[Strategy]] = {
    FedAvg.name: FedAvg,
    FedProx.name: FedProx,
    Momentum.name: Momentum,
    FedOpt.name: FedOpt,
    HeadBoost.name: HeadBoost,
}


def parse_strategy(spec: str) -> tuple[str, dict[str, Any]]:
    """``"name:key=val,key=val"`` -> (name, kwargs).

    Values parse as float when they look like one, else stay strings
    (``fedopt:opt=yogi,lr=0.05``). A bare name means defaults.
    """
    spec = str(spec).strip()
    name, _, rest = spec.partition(":")
    name = name.strip()
    if name not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {name!r} "
            f"(choose from {'|'.join(sorted(STRATEGIES))})"
        )
    kwargs: dict[str, Any] = {}
    if rest.strip():
        for item in rest.split(","):
            key, sep, val = item.partition("=")
            key, val = key.strip(), val.strip()
            if not key or not sep or not val:
                raise ValueError(
                    f"bad strategy param {item!r} in {spec!r} "
                    "(want key=value[,key=value...])"
                )
            try:
                kwargs[key] = float(val)
            except ValueError:
                kwargs[key] = val
    return name, kwargs


def make_strategy(spec: "str | Strategy | None") -> Strategy:
    """Build a Strategy from a spec string; None -> fedavg."""
    if spec is None:
        return FedAvg()
    if isinstance(spec, Strategy):
        return spec
    name, kwargs = parse_strategy(spec)
    try:
        return STRATEGIES[name](**kwargs)
    except TypeError as exc:
        raise ValueError(
            f"strategy {name!r} rejected params "
            f"{sorted(kwargs)}: {exc}"
        ) from None
