#!/usr/bin/env python3
"""``tolerance_probe.py``'s four lines for a configuration whose rows are
WINDOWS (traffic kind ``window_fit``): what the family's tolerances let
through the harness's own comparison on seeded windows of the cell's own mix.

    python3 benchmark/tools/window_probe.py --workload kimilinear-window-fit-l4k [--sequences 4]

At the configuration's published sizes, on seeded weights, against the plain
float32 reference of the configuration's family: the program's own model and
step (they have to pass); the reference with every weight and every
sub-layer's output rounded to bfloat16 (passes) and to float8 e4m3 (has to
fail); the program fed the NEXT window's tokens (has to fail the binding).
Every reference, rounded or not, computes under the program's own choice of
experts (the family's ``reference``; ``kimi_linear_fp32``'s ``forced``), and
its own choice is compared with the program's as a choice. A
line's verdict is the harness's: every variant stands in for the family's
``program`` (a module beside the family, as ``selftest/test_families.py``
installs one) and goes through ``harness.check_model``, and its step (loss,
gradient, parameters' change after one Adam step on the cell's batch) through
the driver's ``judge_step``: ``ctx.compare`` with the family's
``TOLERANCES``, as a run of the cell decides ``correct``. Arithmetic, not
speed: it runs anywhere; at 4,096 tokens a window the CPU takes minutes a
window, so the published widths are probed on the chip and a CPU run takes
``--max-len`` shorter rows (said in its first line).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequences", type=int, default=4, help="windows the hidden states and logits are compared on")
    ap.add_argument("--only", help="a word: only the lines whose name holds it (program | next | bfloat16 | float8)")
    ap.add_argument("--max-len", type=int, help="shorter rows than the configuration's (and as many fewer flows a window)")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.drivers import window_fit

    base = context(args.workload, args.seed)
    family, model, traffic = base.family, dict(base.config["model"]), dict(base.traffic)
    if args.max_len:
        scale = args.max_len / model["max_len"]
        model["max_len"] = args.max_len
        traffic.update({k: max(1, int(traffic[k] * scale)) for k in ("flows_min", "flows_max")})
    tol = family.TOLERANCES
    config_mod = harness.pkg("config")
    train_cfg = config_mod.TrainConfig(learning_rate=float(traffic["learning_rate"]), seed=args.seed, log_every=0)
    tok = harness.pkg("data").default_tokenizer()
    bs = int(traffic["batch"])
    n = max(args.sequences, bs)
    split = window_fit.make_windows(traffic, model["max_len"], n, args.seed, tok, harness.Recorder())
    real = split.attention_mask.sum(-1)
    batch = {"input_ids": split.input_ids[:bs], "attention_mask": split.attention_mask[:bs], "labels": split.labels[:bs]}

    def variant(name: str, **overrides):
        """A context whose family is the configuration's with ``overrides``
        in place: a module beside it, found by name like any family."""
        mod = types.ModuleType(f"benchmark.families.probe_{name}")
        mod.__dict__.update({k: v for k, v in vars(family).items() if not k.startswith("__")})
        mod.__dict__.update(overrides)
        sys.modules[mod.__name__] = mod
        return dataclasses.replace(
            base, config={**base.config, "model": model, "family": f"probe_{name}"}, traffic=traffic,
            problems=[], compared={},
        )

    def rounded(dtype):
        rnd = lambda a: a.astype(dtype).astype(jnp.float32)  # noqa: E731

        def program(cfg):
            # Window by window, outside the comparison's jit; inside it each
            # row the comparison picks finds its own window's result.
            hidden, logits = family.reference(fresh(), split.input_ids, split.attention_mask, model, rnd=rnd)
            ids = jnp.asarray(split.input_ids)

            def forward(p, i, a):
                row = (i[:, None, :] == ids[None]).all(-1).argmax(-1)
                return hidden[row], logits[row]

            return forward

        return {
            "program": program,
            "step": lambda ctx, trainer, params: window_fit.reference_step(ctx, params, batch, train_cfg, routes(), rnd=rnd),
        }

    timed = lambda ctx, trainer, params: window_fit.timed_step(ctx, trainer, params, batch)  # noqa: E731
    variants = {
        "program (bf16 compute)": {"step": timed},
        "reference rounded to bfloat16": rounded(jnp.bfloat16),
        "reference rounded to float8_e4m3": rounded(jnp.float8_e4m3fn),
        "program fed the next window": {
            "program": lambda cfg: lambda p, i, a: family.program(cfg)(p, jnp.roll(i, 1, 0), jnp.roll(a, 1, 0)),
        },
    }
    cfg = family.model_config(model)
    trainer = harness.pkg("train.engine").Trainer(cfg, train_cfg, pad_id=tok.pad_id)
    fresh = lambda: harness.init_params_on_device(family, cfg, args.seed, train_cfg.prng_impl)  # noqa: E731
    print(
        f"[probe] {args.workload}: {n} windows of {model['max_len']} tokens ({int(real.min())}-{int(real.max())} real), "
        f"a step of {bs}, seed {args.seed}, on {jax.devices()[0].platform}; limits: {tol}",
        flush=True,
    )
    want, chosen = None, []

    def routes():
        """The program's choice of experts on the step's batch, under which
        every variant's reference computes."""
        if not chosen:
            chosen.append(jax.device_get(
                jax.jit(family.routing(cfg))(fresh(), batch["input_ids"], batch["attention_mask"])
            ))
        return chosen[0]

    for name, parts in variants.items():
        if args.only and args.only not in name:
            continue
        step = parts.pop("step", None)
        ctx = variant(str(len(sys.modules)), **parts)
        if step is not None:
            got = step(ctx, trainer, fresh())
            want = want or window_fit.reference_step(ctx, fresh(), batch, train_cfg, routes())
            window_fit.judge_step(ctx, got, want, batch["attention_mask"], what=name)
            del got
        harness.check_model(ctx, fresh(), split, what=name, key="probe", bind=True, n=args.sequences)
        print(
            f"[probe] {name}: {'passes' if not ctx.problems else 'FAILS'} "
            + "; ".join(f"{k} {v[0]:.5g} (limit {v[1]:g})" for k, v in ctx.compared.items()),
            flush=True,
        )
    return 0


def context(workload: str, seed: int):
    """``run.make_context``'s context for a comparison alone: the cell's own
    files at their published sizes on whatever device JAX finds, nothing
    timed."""
    import json
    import tempfile
    import time

    import jax

    from benchmark import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(w for w in json.load(f)["workloads"] if w["name"] == workload)
    rec = harness.Recorder()
    return harness.Context(
        workload=workload, seed=seed, seconds=0.0, trace=False, rehearsal=False, chips=1,
        config=harness.load_json("configs", f"{entry['config']}.json"),
        traffic=harness.load_json("traffic", f"{entry['traffic']}.json"),
        cell=harness.load_json("cells", f"{workload}.json"), t_start=time.perf_counter(),
        workdir=tempfile.mkdtemp(prefix="fedtpu_probe_"), rec=rec, meter=harness.CompileMeter(rec),
        devices=jax.devices(),
    )


if __name__ == "__main__":
    sys.exit(main())
