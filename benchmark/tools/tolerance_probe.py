#!/usr/bin/env python3
"""What a family's tolerances let through ``harness.check_model``, shown once.

    JAX_PLATFORMS=cpu python3 benchmark/tools/tolerance_probe.py --config bert-large-l128

At the configuration's published sizes, on seeded weights and 16 seeded flow
sentences, against the plain float32 reference of the configuration's family
(benchmark/families/<family>.py, benchmark/reference):

- the program's own model (bf16 encoder): it has to pass;
- the reference with every weight and every sub-layer's output rounded to
  bfloat16, and to float8 (e4m3): the second has to fail;
- the program fed the NEXT sequence's tokens: it has to fail the binding.

Each line: the worst relative L2 error of a sequence's last hidden states,
the least distance to another sequence's reference over that error
(``binding``, limit 2), the worst logit error over the logit scale, and the
worst error of P(attack). Arithmetic, not
speed: it runs anywhere, the CPU included.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sequences", type=int, default=16)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmark import families, flows, harness

    config = harness.load_json("configs", f"{args.config}.json")
    family, model = families.load(config), config["model"]
    tol = family.TOLERANCES
    cfg = family.model_config(model)
    params = harness.init_params_on_device(family, cfg, args.seed, "threefry2x32")
    tok = harness.pkg("data").default_tokenizer()
    texts, _ = flows.make_flows(args.sequences, args.seed)
    enc = tok.batch_encode(texts, max_len=model["max_len"])
    ids, mask = enc["input_ids"], enc["attention_mask"]

    def p_attack(z):
        e = np.exp(z - z.max(-1, keepdims=True))
        return (e / e.sum(-1, keepdims=True))[:, 1]

    def reference(**rnd):
        h, z = family.reference(params, ids, mask, model, **rnd)
        return np.asarray(h, np.float32), np.asarray(z, np.float64)

    forward = jax.jit(family.program(cfg))

    def program(i, a):
        h, z = forward(params, i, a)
        return np.asarray(h, np.float32), np.asarray(z, np.float64)

    want, z_want = reference()
    p_want, scale = p_attack(z_want), family.logit_scale(params, z_want)
    rows = {
        "program (bf16 encoder)": program(ids, mask),
        "reference rounded to bfloat16": reference(rnd=lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)),
        "reference rounded to float8_e4m3": reference(rnd=lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)),
        "program fed the next sequence": program(np.roll(ids, 1, 0), np.roll(mask, 1, 0)),
    }
    print(
        f"[probe] {args.config}: {len(texts)} sequences, seed {args.seed}, on {jax.devices()[0].platform}; "
        f"limits: hidden {100 * tol['hidden_rel']:g}%, binding {tol['binding']:g}, logits {100 * tol['logit_rel']:g}% of the "
        f"scale {scale:.3f}; the reference's P(attack) spans "
        f"{p_want.max() - p_want.min():.5f}"
    )
    for name, (got, z_got) in rows.items():
        r = harness.compare_hidden(got, want, mask)
        logit_err = np.abs(z_got - z_want).max() / scale
        ok = (
            r["hidden_rel_err"] <= tol["hidden_rel"] and r["binding"] >= tol["binding"]
            and logit_err <= tol["logit_rel"]
        )
        print(
            f"[probe] {name}: hidden {100 * r['hidden_rel_err']:.3f}%, nearest other "
            f"{100 * r['nearest_other']:.2f}%, binding {r['binding']:.2f}, logits {100 * logit_err:.3f}%, "
            f"max |dp| {np.abs(p_attack(z_got) - p_want).max():.5f}: {'passes' if ok else 'FAILS'}",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
