"""The routing counters an expert layer hands out of a forward pass.

A model with expert layers sows, in the variable collection :data:`ROUTE`,
per layer, the token-slots routed to each expert this chip holds (``slots``,
``[held]`` int32), the slots its buffers could not take (``overflow``,
int32; they are not in the layer's result, so a caller that needs every
token checks it is 0) and the rows its shared buffer MOVED (``rows``,
int32: the length of the prefix the call's gather, grouped operands and
scatter-add ran over, ``ops/moe.py::expert_rungs``; ``slots`` over it is the
fill of what was moved). A model without expert layers
sows nothing and every function here returns its empty value, so a caller
treats both alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: The variable collection the expert layers sow in (``mutable=[ROUTE]``).
ROUTE = "route"


def route_totals(sown) -> dict:
    """``mutable=[ROUTE]``'s second result summed over the layers:
    ``{"slots": [held] int32, "overflow": [] int32, "rows": [] int32}``, or
    ``{}``."""
    layers = jax.tree.leaves(
        dict(sown).get(ROUTE, {}), is_leaf=lambda n: isinstance(n, dict) and "slots" in n
    )
    if not layers:
        return {}
    return {key: sum(layer[key] for layer in layers) for key in ("slots", "overflow", "rows")}


def route_zeros(cfg) -> dict | None:
    """The accumulator a train state starts with for the configuration
    ``cfg``: zeros shaped like :func:`route_totals`' result, or None for a
    model that routes nothing."""
    held = getattr(cfg, "experts_held", None)
    if held is None:
        return None
    return {
        "slots": jnp.zeros((held,), jnp.int32),
        "overflow": jnp.zeros((), jnp.int32),
        "rows": jnp.zeros((), jnp.int32),
    }
