"""Model families: everything the yardstick has to know of one architecture.

A configuration file names its family (``"family": "bert_encoder"`` in
``benchmark/configs/<config>.json``) and the harness finds
``benchmark/families/<family>.py`` by that name, as it finds a driver by a
traffic mix's ``kind`` and a reader by a metric's ``reader``. Nothing else
under ``benchmark/`` names a class or a module of the program's model, a
reference forward, a tolerance probed for one architecture or a count of one
architecture's operations: the harness, ``correct``, ``mfu`` and
``train_step_roofline`` go through the family.

A family module holds:

- ``model_config(model)``: the program's configuration object from the
  configuration file's ``model``; ``tiny(model)``: the ``model`` a CPU
  rehearsal runs (the program's small preset, with the keys of the
  configuration that are not sizes);
- ``init_params(model_cfg, key)``: the body of the one jitted call that makes
  the seed's weights on the device, in the type they are trained and served in;
- ``program(model_cfg)``: ``(params, ids, mask) -> (last hidden states,
  logits)`` through the program's own classes in evaluation mode;
- ``reference(params, ids, mask, model, rnd=None)``: the plain float32 forward
  (under ``benchmark/reference/``; it imports nothing of the program and may
  compute in blocks); ``rnd`` rounds every weight and sub-layer output, which
  is how ``tools/tolerance_probe.py`` puts the reference at a lower precision
  in the program's place;
- ``TOLERANCES`` (``hidden_rel``, ``logit_rel``, ``binding``, ``reply_abs``)
  with the comment that carries their evidence, and
  ``logit_scale(params, want)``;
- ``forward_flops``, ``train_step_flops``, ``param_count``,
  ``train_step_bytes``: operations and bytes from shapes alone. They take the
  ``model``, the rows and, as keywords, the sum of every other numeric
  attribute of the spans a reader read (today ``steps``): a family whose
  required work depends on what the traffic did (tokens routed to the experts
  a chip holds, sequence lengths) reads a counter that a driver put on the
  span, with no edit of a reader. A family ignores the keywords it does not
  know.

``benchmark/flops.py`` keeps what is of the chip and not of a model.

**A configuration of another architecture is new files only**:
``families/<family>.py``, ``reference/<name>_fp32.py``,
``configs/<config>.json`` (naming the family), a ``traffic/`` and a ``cells/``
file, ``layer_metrics/*.json`` for its new spans and kernels (with their
readers where a new kind of reading is needed), and entries in
``BENCHMARK.json``, the new cell's name added to the ``workloads`` lists of
the metrics it reports. ``mfu`` and ``train_step_roofline`` then read the new
family's own counts under their one name. What a driver asks of the program's
trainers (``Trainer(model_cfg, train_cfg, pad_id=...)``,
``FederatedTrainer(cfg, mesh=...)``) stays: the system's normal path, through
which a later family's configuration object has to pass, which is that PR's
program work. ``selftest/test_families.py`` installs a second family and shows
the seam holds.
"""

from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(config: dict):
    """The family module that the configuration ``config`` (a
    ``benchmark/configs/<config>.json``) names. A configuration without the
    key, or naming a module that is not there, is an error with the path
    looked for, never a default."""
    name = config.get("family")
    if not name:
        raise KeyError(
            f"configuration {config.get('name')!r} names no \"family\": its file needs the "
            f"key, naming a module {os.path.join(HERE, '<family>.py')}"
        )
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ModuleNotFoundError(
            f"configuration {config.get('name')!r} names the family {name!r}: no "
            f"{os.path.join(HERE, name + '.py')}",
            name=module,
        ) from None
