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
the collectives the compiler put into the step, with the bytes of their
results by element type.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


COLLECTIVE = re.compile(
    r" = (?P<result>.+?) (?P<op>all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\("
)
ARRAY = re.compile(r"\b([a-z]+\d+[a-z0-9]*)\[([\d,]*)\]")


def collectives(hlo: str) -> dict[str, dict]:
    """The collectives in a compiled program's text, by operation: how many,
    and the bytes of their results by element type. A result is whatever
    stands between ``=`` and the operation's name: one array, or the tuple of
    arrays into which the compiler combines most gradient all-reduces
    (matching an array-typed result only said "1 all-reduce" of a program
    with five). The ``-done`` half of an asynchronous pair is not counted;
    its ``-start`` half is, and where that carries the operands beside the
    results (all-gather, collective-permute) both are in the bytes."""
    out: dict[str, dict] = {}
    for line in hlo.splitlines():
        m = COLLECTIVE.search(line)
        if m is None:
            continue
        op = out.setdefault(m["op"], {"count": 0, "bytes": collections.Counter()})
        op["count"] += 1
        for dtype, dims in ARRAY.findall(m["result"]):
            bits = int(re.search(r"\d+", dtype)[0])
            elements = int(np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64))
            op["bytes"][dtype] += elements * bits // 8
    return out


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
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from benchmark import families, harness

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"] if w["name"] == args.workload)
    conf = harness.load_json("configs", f"{entry['config']}.json")
    family = families.load(conf)
    t = harness.load_json("traffic", f"{entry['traffic']}.json")
    config = harness.pkg("config")
    model_cfg = family.model_config(conf["model"])
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
        params = jax.eval_shape(
            lambda: family.init_params(model_cfg, jax.random.key(0, impl="rbg"))
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
    found = collectives(compiled.as_text())
    if not found:
        print("[rehearse] collectives in the program: none")
    for op, x in found.items():
        by_type = ", ".join(f"{dt} {n / 1e6:.1f} MB" for dt, n in sorted(x["bytes"].items()))
        print(
            f"[rehearse] collectives in the program: {x['count']} {op}, results "
            f"{sum(x['bytes'].values()) / 1e6:.1f} MB ({by_type})"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
