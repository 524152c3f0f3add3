#!/usr/bin/env python3
"""Compile a training cell's step program at its real size for the real
chip, without the chip: the installed TPU compiler compiles for a described
``v5e:2x2`` topology, refuses what the chip would refuse (a program that does
not fit 16 GB, a sharding it cannot partition), and ``memory_analysis()``
says what one chip would hold. Used to size ``bertlarge-client-fit`` (its
batch) and ``distilbert-fed-round-c8-2x2`` (clients per mesh row) before chip
time is spent. It compiles, it does not run: nothing here is a speed.

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_compile.py \
        --workload distilbert-fed-round-c8-2x2 [--clients 8] [--batch 64]

``--clients`` / ``--batch`` try another size than the traffic file's. Prints
the bytes per chip (arguments, outputs, temporaries, total) and, for a mesh,
the collectives the compiler put into the step.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def jitted(step):
    """The jitted function under the program's compile-ledger wrapper."""
    return getattr(step, "__wrapped__", step)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--clients", type=int)
    ap.add_argument("--batch", type=int)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from benchmark import harness

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"] if w["name"] == args.workload)
    model = harness.load_json("configs", f"{entry['config']}.json")["model"]
    t = harness.load_json("traffic", f"{entry['traffic']}.json")
    config = harness.pkg("config")
    model_cfg = config.ModelConfig(**model)
    bs = args.batch or int(t["batch"])
    L = model_cfg.max_len
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    train_cfg = config.TrainConfig(
        epochs_per_round=int(t["epochs"]), learning_rate=float(t["learning_rate"]), log_every=0
    )
    t0 = time.perf_counter()
    if t["kind"] == "client_fit":
        one = SingleDeviceSharding(topo.devices[0])
        trainer = harness.pkg("train.engine").Trainer(model_cfg, train_cfg)
        state = jax.eval_shape(lambda: trainer.init_state(seed=0))
        place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
        state = jax.tree.map(place, state)
        batch = {
            "input_ids": jax.ShapeDtypeStruct((bs, L), jnp.int32, sharding=one),
            "attention_mask": jax.ShapeDtypeStruct((bs, L), jnp.int32, sharding=one),
            "labels": jax.ShapeDtypeStruct((bs,), jnp.int32, sharding=one),
        }
        what = f"Trainer.train_step, batch {bs}"
        compiled = jitted(trainer.train_step).lower(state, batch).compile()
    elif t["kind"] == "fed_round":
        C = args.clients or int(t["clients"])
        rows, data = int(t["mesh"]["clients"]), int(t["mesh"]["data"])
        devs = np.array(topo.devices[: rows * data]).reshape(rows, data)
        mesh = Mesh(devs, ("clients", "data"))
        cfg = config.ExperimentConfig(
            model=model_cfg,
            data=config.DataConfig(max_len=L, batch_size=bs, eval_batch_size=bs),
            train=train_cfg,
            fed=config.FedConfig(num_clients=C, weighted=True),
            mesh=config.MeshConfig(clients=rows, data=data),
        )
        trainer = harness.pkg("train.federated").FederatedTrainer(cfg, mesh=mesh)
        client, batch_sh, rep = (
            NamedSharding(mesh, P("clients")), NamedSharding(mesh, P("clients", "data")),
            NamedSharding(mesh, P()),
        )
        m = harness.pkg("models.distilbert")
        params = jax.eval_shape(
            lambda: m.init_params(trainer.model, model_cfg, jax.random.key(0, impl="rbg"))
        )
        stacked = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((C, *x.shape), x.dtype, sharding=client), params
        )
        opt = jax.eval_shape(trainer.optimizer.init, params)
        opt = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((C, *x.shape), x.dtype, sharding=client), opt
        )
        rngs = jax.eval_shape(lambda: jax.random.split(jax.random.key(0, impl="rbg"), C))
        FedState = harness.pkg("train.fedsteps").FedState
        state = FedState(
            params=stacked, opt_state=opt,
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            rngs=jax.ShapeDtypeStruct(rngs.shape, rngs.dtype, sharding=client),
        )
        batch = {
            "input_ids": jax.ShapeDtypeStruct((C, bs, L), jnp.int32, sharding=batch_sh),
            "attention_mask": jax.ShapeDtypeStruct((C, bs, L), jnp.int32, sharding=batch_sh),
            "labels": jax.ShapeDtypeStruct((C, bs), jnp.int32, sharding=batch_sh),
        }
        what = f"FederatedTrainer.train_step, {C} clients on {rows}x{data}, batch {bs}"
        compiled = jitted(trainer.train_step).lower(state, batch).compile()
    else:
        sys.stderr.write(f"rehearse_compile: kind {t['kind']!r} has no step to compile\n")
        return 2
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    gb = lambda x: f"{x / 1e9:.3f} GB"  # noqa: E731
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    print(f"[rehearse] {args.workload}: {what}: compiled for v5e:2x2 in {took:.0f} s (a compile, not a run)")
    print(
        f"[rehearse] per chip: arguments {gb(mem.argument_size_in_bytes)}, outputs "
        f"{gb(mem.output_size_in_bytes)} (aliased {gb(mem.alias_size_in_bytes)}), temporaries "
        f"{gb(mem.temp_size_in_bytes)}, generated code {gb(mem.generated_code_size_in_bytes)}; "
        f"live at once about {gb(total)} of 16 GB"
    )
    ops = collections.Counter(
        re.findall(r"= \S+ (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(", compiled.as_text())
    )
    print(f"[rehearse] collectives in the program: {dict(ops) or 'none'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
