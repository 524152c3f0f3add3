"""Orbax checkpoint/resume for training state.

The reference's checkpoint story is ``torch.save(model.state_dict(), ...)``
after local training and after applying the aggregate, auto-loaded on the
next launch (reference client1.py:375-377,388,403; server.py:77) — and that
warm-start is its *only* multi-round FL mechanism. Optimizer state is never
checkpointed, so every "round" silently restarts Adam moments.

Here checkpointing is first-class and complete:

* the FULL state pytree is saved — params, optimizer state, step counter,
  and per-client RNG keys — so a resumed run continues bit-for-bit;
* restore is sharding-aware: leaves land directly on the mesh shards the
  template dictates (no host-memory spike of the stacked ``[C, ...]`` tree);
* a JSON metadata blob (round number, config) rides along for bookkeeping;
* ``max_to_keep`` garbage-collects old rounds.

Typed JAX PRNG keys are not directly serializable; they are transparently
unwrapped to raw key data on save and re-wrapped (with the impl recorded in
the restore template) on load.
"""

from __future__ import annotations

import os
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

STATE_ITEM = "state"
META_ITEM = "meta"


def _is_prng_key(x: Any) -> bool:
    return isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key)


def _unwrap_keys(tree: Any) -> Any:
    """Typed PRNG key leaves -> raw uint32 key data (serializable)."""
    return jax.tree.map(
        lambda x: jax.random.key_data(x) if _is_prng_key(x) else x, tree
    )


def _rewrap_keys(tree: Any, template: Any) -> Any:
    """Inverse of ``_unwrap_keys``, key impl taken from the template leaf."""

    def _wrap(restored, ref):
        if _is_prng_key(ref):
            impl = jax.random.key_impl(ref)
            return jax.random.wrap_key_data(restored, impl=impl)
        return restored

    return jax.tree.map(_wrap, tree, template, is_leaf=_is_prng_key)


def _abstract(template: Any) -> Any:
    """ShapeDtypeStructs (with shardings when present) for sharded restore."""

    def _leaf(x):
        if _is_prng_key(x):
            x = jax.random.key_data(x)
        elif isinstance(x, jax.ShapeDtypeStruct) and jnp.issubdtype(
            x.dtype, jax.dtypes.prng_key
        ):
            # Abstract (eval_shape) templates carry typed-key leaves too;
            # checkpoints store the raw key data, so describe that shape.
            x = jax.eval_shape(jax.random.key_data, x)
        sharding = getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype,
            sharding=sharding,
        )

    return jax.tree.map(_leaf, _unwrap_keys(template))


class Checkpointer:
    """Save/restore any training-state pytree (TrainState, FedState, ...).

    The restore template — typically a freshly built ``init_state()`` —
    supplies tree structure, dtypes, shardings, and PRNG-key impls; the
    checkpoint supplies the values.
    """

    def __init__(self, directory: str, *, max_to_keep: int = 3):
        self.directory = os.path.abspath(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    # ------------------------------------------------------------------ save
    def save(self, step: int, state: Any, *, meta: Mapping[str, Any] | None = None) -> None:
        unwrapped = _unwrap_keys(state)
        args = {STATE_ITEM: ocp.args.StandardSave(unwrapped)}
        # The saved leaf-shape manifest (internal "_leaf_shapes" key) rides
        # the JSON meta so ANY later manager instance can check template
        # compatibility before restoring — orbax's own array metadata is
        # only readable by the manager that saved (handler registry); see
        # saved_compatible.
        # Tree-leaves order, NOT sorted: a multiset compare would miss two
        # tables swapping sizes (vocab 128/pos 140 -> vocab 140/pos 128 has
        # the identical shape multiset); leaves order is deterministic for
        # a given structure, so the positional compare is exact.
        manifest = [
            [int(d) for d in np.shape(x)] for x in jax.tree.leaves(unwrapped)
        ]
        args[META_ITEM] = ocp.args.JsonSave(
            {**(dict(meta) if meta is not None else {}), "_leaf_shapes": manifest}
        )
        self._mgr.save(step, args=ocp.args.Composite(**args))

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    # --------------------------------------------------------------- restore
    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def restore(self, template: Any, *, step: int | None = None) -> Any:
        """Restore the state saved at ``step`` (default: latest)."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        restored = self._mgr.restore(
            step,
            args=ocp.args.Composite(
                **{STATE_ITEM: ocp.args.StandardRestore(_abstract(template))}
            ),
        )[STATE_ITEM]
        return _rewrap_keys(restored, template)

    def saved_compatible(self, template: Any, *, step: int | None = None) -> bool:
        """Pre-restore compatibility gate: does the checkpoint's saved
        per-leaf shape list (the "_leaf_shapes" manifest save() records,
        in tree-leaves order) match the template's? A restore into
        DIFFERENT template shapes — e.g. a vocab-100 embedding into a
        vocab-140 array — must be refused here, by our own record, not
        left to whatever the restore call does with the mismatch: it
        would mistrain far from the restore site. Checkpoints predating
        the manifest -> True (the restore call itself then decides)."""
        step = self.latest_step() if step is None else step
        if step is None:
            return False
        try:
            recorded = self._restore_meta_raw(step=step).get("_leaf_shapes")
        except Exception:
            recorded = None
        if recorded is None:
            return True
        saved = [tuple(int(d) for d in s) for s in recorded]
        want = [
            tuple(x.shape) for x in jax.tree.leaves(_abstract(template))
        ]
        return saved == want

    def restore_params(self, template: Any, *, step: int | None = None) -> Any:
        """Restore ONLY the ``params`` field of a saved TrainState/FedState.

        Every other field is skipped via ``ocp.PLACEHOLDER``, so optimizer
        moments are never materialized — restoring a C-client FedState just
        to read the (replicated) model would otherwise allocate ~3x C model
        copies. Build ``template`` with ``jax.eval_shape(lambda:
        init_state(...))`` so the template itself materializes nothing.

        NOTE: placeholder skipping is a PyTreeRestore feature, and the
        composite handler registry binds one restore-args class per item
        per manager instance — call this on a Checkpointer that has not
        already restored the full state (predict constructs its own).
        """
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        abstract = _abstract(template)
        masked = abstract._replace(
            **{
                f: jax.tree.map(lambda _: ocp.PLACEHOLDER, getattr(abstract, f))
                for f in abstract._fields
                if f != "params"
            }
        )
        restored = self._mgr.restore(
            step,
            args=ocp.args.Composite(
                **{STATE_ITEM: ocp.args.PyTreeRestore(item=masked)}
            ),
        )[STATE_ITEM]
        return restored.params

    def restore_meta(self, *, step: int | None = None) -> dict:
        """The caller-supplied meta blob; internal bookkeeping keys
        (underscore-prefixed, e.g. the "_leaf_shapes" manifest) are
        stripped — they are save()'s implementation detail."""
        return {
            k: v
            for k, v in self._restore_meta_raw(step=step).items()
            if not str(k).startswith("_")
        }

    def _restore_meta_raw(self, *, step: int | None = None) -> dict:
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        try:
            return dict(
                self._mgr.restore(
                    step, args=ocp.args.Composite(**{META_ITEM: ocp.args.JsonRestore()})
                )[META_ITEM]
            )
        except (KeyError, FileNotFoundError, TypeError):
            return {}

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self) -> "Checkpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _shapes_match(restored: Any, template: Any) -> bool:
    """True when two state pytrees agree on structure and per-leaf shapes
    — the compatibility contract a warm start needs (dtype differences are
    tolerated: orbax already restores into the template's dtypes when the
    shapes agree)."""
    try:
        r_leaves, r_def = jax.tree.flatten(restored)
        t_leaves, t_def = jax.tree.flatten(template)
    except Exception:
        return False
    if r_def != t_def:
        return False
    return all(
        np.shape(r) == np.shape(t) for r, t in zip(r_leaves, t_leaves)
    )


def maybe_warm_start(directory: str, template: Any) -> tuple[Any | None, int | None]:
    """The reference's warm-start pattern (client1.py:375-377): if a
    checkpoint directory exists and holds a saved state, load it; else None.

    Returns ``(state, step)`` — callers decide whether to keep the optimizer
    state or reset it (FedConfig.reset_optimizer_each_round).

    An incompatible checkpoint (different model/vocab shapes or tree
    structure — e.g. the config changed between runs) degrades to a fresh
    start with a warning instead of aborting: warm start is an optimization,
    and the reference likewise proceeds from scratch when its ``.pth`` is
    absent.
    """
    from ..parallel.multihost import allgather_hosts

    def _agree_min(value: int) -> int:
        """Collective minimum of a host int — every warm-start decision must
        be identical on all processes, else their orbax barrier sequences
        diverge (observed as sync_global_devices name mismatches when one
        process saw the directory the other's Checkpointer just created)."""
        return int(allgather_hosts(value).min())

    if not _agree_min(int(os.path.isdir(directory))):
        return None, None
    with Checkpointer(directory) as ckpt:
        step = ckpt.latest_step()
        step_agreed = _agree_min(-1 if step is None else int(step))
        if step_agreed < 0:
            return None, None
        step = step_agreed
        if not ckpt.saved_compatible(template, step=step):
            from ..utils.logging import get_logger

            get_logger().warning(
                f"checkpoint at {directory} (step {step}) was saved under a "
                "different model shape; starting fresh"
            )
            restored: Any | None = None
        else:
            try:
                restored = ckpt.restore(template, step=step)
            except Exception as e:  # orbax raises backend-specific errors
                from ..utils.logging import get_logger

                get_logger().warning(
                    f"checkpoint at {directory} (step {step}) failed to "
                    f"restore ({type(e).__name__}: {e}); starting fresh"
                )
                restored = None
        if restored is not None and not _shapes_match(restored, template):
            # A restore can come back with the CHECKPOINT's shapes
            # instead of raising when the template disagrees (e.g. a
            # manifest-less checkpoint whose vocab grew between runs);
            # adopting those arrays would crash — or silently mistrain —
            # far from here. Same degrade-to-fresh semantics as a
            # restore error.
            from ..utils.logging import get_logger

            get_logger().warning(
                f"checkpoint at {directory} (step {step}) has incompatible "
                "tree/leaf shapes for this config; starting fresh"
            )
            restored = None
        # The outcome must be agreed too: if any process failed to restore,
        # every process starts fresh — a split decision would desync the
        # collective training loops.
        if not _agree_min(int(restored is not None)):
            return None, None
        return restored, step
