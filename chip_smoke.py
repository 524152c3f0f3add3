#!/usr/bin/env python3
"""The quickest proof that fedtpu still starts on the chip.

One process, one pass over the main path at the flagship's full width
(``ModelConfig()``: 6 layers x 768 x 12 heads x 3072, vocab 30522, L=128,
bf16 compute, ~66 M parameters; weights random from a seed, data synthetic
from a seed): ``fedtpu federated`` takes a few lockstep steps over 2 clients
and one FedAvg round and writes a checkpoint; ``fedtpu infer-serve`` restores
it and answers scoring requests over loopback. Around that: the native
libraries rebuilt from source, the timing-fence question, the Pallas kernels
compiled by Mosaic and compared with the XLA path, the persistent compile
cache cold and warm, and — when the host has four chips — the same path over
2x2 and 4x1 meshes plus the sharded scorer.

    python chip_smoke.py                  the whole smoke; needs a TPU
    python chip_smoke.py --rehearse-cpu   the same code at the tiny preset on
                                          4 virtual CPU devices, kernels in
                                          interpret mode: a rehearsal of the
                                          control flow, it prints no timing

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every phase passed. Exit 2, within seconds and with no result line, when JAX
finds no TPU (and no rehearsal was asked for) or the script is not inside a
checkout. Exit 1 when any phase failed. Everything that touches JAX runs in
this process: a chip belongs to one process at a time (the only children are
the C++ compiler's, for the native rebuild).
The full per-phase record lands in ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import faulthandler
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = (
    "detecting_cyber_attacks_with_distilled_large_language_models"
    "_in_distributed_networks_tpu"
)
#: The driver's limit is 1200 s; past this the run dumps every thread's
#: stack and exits non-zero on its own instead of being killed mute.
DEADLINE_S = 1150
V5E_PEAK_BF16 = 197e12  # Google Cloud "TPU v5e" documentation, per chip
#: Served probability against the trainer-side reference, absolute (bf16).
SERVE_TOL = 5e-3

ONE_CHIP_PHASES = (
    "native", "peak", "fence", "kernels", "train", "serve_cold", "serve_warm",
)
FOUR_CHIP_PHASES = ("fed_2x2", "fed_4x1", "serve_fsdp", "fed_seq")
#: Every phase always runs; a phase whose prerequisite failed fails with it
#: instead of crashing on what the prerequisite did not leave behind.
NEEDS = {
    "serve_cold": ("train",),
    "serve_warm": ("serve_cold",),
    "serve_fsdp": ("serve_cold",),
}


def pkg(module: str):
    """A module of the package, imported once main() has put the checkout
    on sys.path (the script must start, and fail cleanly, without it)."""
    return importlib.import_module(f"{PKG}.{module}")


class Failed(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Failed(msg)


class CompileMeter:
    """Totals of JAX's own monitoring events: seconds inside backend
    compilation (a persistent-cache retrieval counts as its, short,
    compile) and persistent-cache requests, hits and misses."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache = {"requests": 0, "hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        key = {
            "/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "hits",
            "/jax/compilation_cache/cache_misses": "misses",
        }.get(event)
        if key:
            self.cache[key] += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": self.compile_s,
            "compiles": self.compiles,
            **{f"cache_{k}": v for k, v in self.cache.items()},
        }


def tree_crc(tree) -> int:
    """The repo's own checksum of a parameter tree (comm/wire.py: crc32
    over the sorted flat leaves, through the native library when live)."""
    wire = pkg("comm.wire")
    return wire.flat_crc32(wire.flatten_params(tree))


class Smoke:
    def __init__(self, *, rehearsal: bool, workdir: str):
        import jax

        self.rehearsal = rehearsal
        self.workdir = workdir
        self.devices = jax.devices()
        d = self.devices[0]
        self.device = {
            "platform": d.platform,
            "kind": d.device_kind,
            "count": len(self.devices),
        }
        self.meter = CompileMeter()
        self.records: dict[str, dict] = {}
        # Filled by phases for the ones that build on them.
        self.cfg_path: str | None = None
        self.ckpt_dir: str | None = None
        self.trained_crc: int | None = None
        self.trained_params = None
        self.texts: list[str] = []
        self.replies: list[float] = []

    # ------------------------------------------------------------ reporting
    def secs(self, x: float) -> str:
        """A rehearsal runs on the CPU: its times are not device numbers
        and are not printed under any name."""
        return "withheld" if self.rehearsal else f"{x:.2f}s"

    def say(self, msg: str) -> None:
        print(f"[smoke] {msg}", flush=True)

    def run_phase(self, name: str, fn) -> None:
        for dep in NEEDS.get(name, ()):
            if not self.records.get(dep, {}).get("ok"):
                self.records[name] = {
                    "ok": False, "error": f"prerequisite phase {dep} did not pass",
                }
                self.say(f"phase {name}: FAIL ({self.records[name]['error']})")
                return
        self.say(f"phase {name}: start")
        before = self.meter.snapshot()
        t0 = time.perf_counter()
        rec: dict = {"ok": False}
        try:
            rec.update(fn() or {})
            rec["ok"] = True
        except Exception as e:  # the boundary that keeps later phases running
            traceback.print_exc()
            rec["error"] = f"{type(e).__name__}: {e}"
        wall = time.perf_counter() - t0
        after = self.meter.snapshot()
        delta = {k: after[k] - before[k] for k in after}
        if not self.rehearsal:
            rec["wall_s"] = round(wall, 3)
            rec["compile_s"] = round(delta["compile_s"], 3)
            rec["run_s"] = round(wall - delta["compile_s"], 3)
        rec.update({k: v for k, v in delta.items() if k != "compile_s"})
        self.records[name] = rec
        self.say(
            f"phase {name}: {'PASS' if rec['ok'] else 'FAIL'} "
            f"wall {self.secs(wall)} of which compile "
            f"{self.secs(delta['compile_s'])} in {delta['compiles']} "
            f"compilation(s); persistent cache {delta['cache_hits']} hit(s) "
            f"{delta['cache_misses']} miss(es)"
            + (f" — {rec['error']}" if not rec["ok"] else "")
        )

    # --------------------------------------------------------------- phases
    def phase_native(self) -> dict:
        """§9: both libraries rebuilt from native/*.cpp in this run."""
        native = pkg("utils.native")
        build = native.build_module()
        toolchain = shutil.which("g++") or shutil.which("clang++")
        self.say(f"native: toolchain {toolchain or 'none found'}")
        for src, soname in build.LIBS:
            built = build.build_lib(src, soname, force=True)
            self.say(f"native: {src} -> {built or 'not built'}")
            check(
                built is not None or toolchain is None,
                f"{toolchain} is present but {soname} did not build",
            )
        pkg("comm.native").have_native()
        pkg("data.native_tokenizer").have_native()
        status = native.native_status()
        for soname, state in status.items():
            self.say(f"native: {soname}: {state}")
        check(
            toolchain is None or all(s == "native" for s in status.values()),
            f"a toolchain is present but a Python twin is live: {status}",
        )
        return {"toolchain": toolchain, "status": status}

    def phase_peak(self) -> dict:
        """§3: an unknown device has no MFU, and must not get a default."""
        peak = pkg("utils.profiling").device_peak_flops()
        self.say(f"peak: device_peak_flops() = {peak}")
        if self.rehearsal:
            check(peak is None, f"a CPU has no peak in the table, got {peak}")
            return {"peak_flops": None}
        check(peak is not None, f"no peak for {self.device['kind']!r}")
        if "v5 lite" in self.device["kind"].lower():
            check(peak == V5E_PEAK_BF16, f"v5e peak is 197e12, table says {peak}")
        return {"peak_flops": peak}

    def phase_fence(self) -> dict:
        """§2: one bf16 matmul chain of known FLOPs, timed to
        ``jax.block_until_ready`` and to a host read-back of one scalar."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        n, chain = (512, 2) if self.rehearsal else (8192, 8)
        flops = chain * 2.0 * n**3

        @jax.jit
        def f(x, w):
            for _ in range(chain):
                x = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
                    jnp.bfloat16
                )
            return x, x[0, 0].astype(jnp.float32)

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)
        # Scaled so the chain neither overflows nor flushes to zero.
        w = jnp.asarray(rng.normal(size=(n, n)) / np.sqrt(n), jnp.bfloat16)
        y, s = f(x, w)
        check(bool(np.isfinite(float(s))), "matmul chain is not finite")
        jax.block_until_ready(y)

        def timed(fence) -> float:
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                out = f(x, w)
                fence(out)
                best = min(best, time.perf_counter() - t0)
            return best

        t_bur = timed(lambda out: jax.block_until_ready(out[0]))
        t_read = timed(lambda out: float(out[1]))
        if self.rehearsal:
            self.say("fence: both fences ran (rehearsal: no timing printed)")
            return {}
        peak = V5E_PEAK_BF16
        rec = {
            "flops": flops,
            "block_until_ready_ms": round(t_bur * 1e3, 3),
            "scalar_readback_ms": round(t_read * 1e3, 3),
            "block_until_ready_tflops": round(flops / t_bur / 1e12, 1),
            "scalar_readback_tflops": round(flops / t_read / 1e12, 1),
            "floor_ms_at_peak": round(flops / peak * 1e3, 3),
        }
        early = t_bur < 0.9 * flops / peak
        rec["block_until_ready_returns_early"] = early
        self.say(
            f"fence: {chain} x bf16 {n}^3 matmul = {flops / 1e12:.2f} TFLOP; "
            f"block_until_ready {rec['block_until_ready_ms']} ms "
            f"({rec['block_until_ready_tflops']} TFLOP/s, "
            f"{flops / t_bur / peak:.0%} of the 197 TFLOP/s peak); scalar "
            f"read-back {rec['scalar_readback_ms']} ms "
            f"({rec['scalar_readback_tflops']} TFLOP/s, "
            f"{flops / t_read / peak:.0%}); floor at peak "
            f"{rec['floor_ms_at_peak']} ms -> block_until_ready "
            f"{'RETURNS EARLY' if early else 'is a sound fence'}"
        )
        check(
            t_read >= 0.9 * flops / peak,
            "the read-back fence beat the chip's peak: the measurement is broken",
        )
        return rec

    def phase_kernels(self) -> dict:
        """§6: flash attention forward, backward and backward with dropout,
        compiled by Mosaic (``interpret=False``, the custom call found in
        the executable's text), against ``dot_product_attention``."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        att = pkg("ops.attention")
        flash = pkg("ops.flash_attention").flash_attention
        b, h, d = 1, 12, 64
        lengths = (128, 256) if self.rehearsal else (128, 2048, 8192)
        interpret = self.rehearsal
        # bf16 inputs, fp32 comparison, error relative to the reference's
        # largest magnitude: 2^-8 per rounding, a few roundings deep.
        tol = {"fwd": 2e-2, "grad": 4e-2}
        out: dict = {"tolerance": tol, "cases": {}}
        for L in lengths:
            rng = np.random.default_rng(L)
            q, k, v = (
                jnp.asarray(rng.normal(size=(b, h, L, d)), jnp.bfloat16)
                for _ in range(3)
            )
            cot = jnp.asarray(rng.normal(size=(b, h, L, d)), jnp.float32)
            mask = np.ones((b, L), np.int32)
            mask[:, -L // 4 :] = 0  # a key mask: the last quarter is padding
            bias = att.make_attention_bias(jnp.asarray(mask))
            key = jax.random.key(0)

            def loss(fn, **kw):
                return lambda q, k, v: (
                    fn(q, k, v, bias, **kw).astype(jnp.float32) * cot
                ).sum()

            drop = dict(dropout_rate=0.1, dropout_rng=key, deterministic=False)
            programs = {
                "fwd": (
                    lambda q, k, v: flash(q, k, v, bias, interpret=interpret),
                    lambda q, k, v: att.dot_product_attention(q, k, v, bias),
                ),
                "grad": (
                    jax.grad(loss(flash, interpret=interpret), argnums=(0, 1, 2)),
                    jax.grad(loss(att.dot_product_attention), argnums=(0, 1, 2)),
                ),
                "grad_dropout": (
                    jax.grad(
                        loss(flash, interpret=interpret, **drop), argnums=(0, 1, 2)
                    ),
                    None,  # different mask bits by design: finiteness only
                ),
            }
            for name, (kernel_fn, ref_fn) in programs.items():
                compiled = jax.jit(kernel_fn).lower(q, k, v).compile()
                n_mosaic = compiled.as_text().count("tpu_custom_call")
                if not interpret:
                    want = 1 if name == "fwd" else 3
                    check(
                        n_mosaic >= want,
                        f"L={L} {name}: {n_mosaic} Mosaic custom call(s) in "
                        f"the executable, want {want}: the kernel did not run",
                    )
                got = jax.tree.leaves(compiled(q, k, v))
                check(
                    all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in got),
                    f"L={L} {name}: non-finite output",
                )
                err = None
                if ref_fn is not None:
                    ref = jax.tree.leaves(jax.jit(ref_fn)(q, k, v))
                    err = max(
                        float(
                            jnp.abs(
                                g.astype(jnp.float32) - r.astype(jnp.float32)
                            ).max()
                            / (jnp.abs(r.astype(jnp.float32)).max() + 1e-6)
                        )
                        for g, r in zip(got, ref)
                    )
                    limit = tol["fwd" if name == "fwd" else "grad"]
                    check(
                        err <= limit,
                        f"L={L} {name}: relative error {err:.4f} > {limit}",
                    )
                out["cases"][f"L{L}_{name}"] = {
                    "mosaic_calls": n_mosaic, "rel_err": err,
                }
                self.say(
                    f"kernels: flash L={L} {name}: "
                    + ("interpreted" if interpret else f"{n_mosaic} Mosaic call(s)")
                    + (f", rel err vs dot {err:.4f}" if err is not None else ", finite")
                )
        return out

    # ------------------------------------------------------- the main path
    def flagship(self):
        """The one model this smoke builds: ``ModelConfig()`` at full width
        (the tiny preset in a rehearsal)."""
        config = pkg("config")
        return config.ModelConfig.tiny() if self.rehearsal else config.ModelConfig()

    def _write_config(self) -> str:
        """The --config file: ``export-config``'s shape with the model the
        flagship (``--preset distilbert`` without --hf-dir would size the
        embedding from the 148-token domain vocabulary)."""
        config = pkg("config")
        model = self.flagship()
        cfg = config.ExperimentConfig(
            model=model,
            data=config.DataConfig(max_len=model.max_len),
            train=config.TrainConfig(log_every=1),
        )
        path = os.path.join(self.workdir, "smoke_config.json")
        with open(path, "w") as f:
            json.dump(cfg.to_dict(), f, indent=1)
        return path

    def _federated(
        self, name: str, extra: list[str], want_mesh: tuple, *, ring: bool = False
    ) -> tuple:
        """One ``fedtpu federated`` launch and the checks every mesh shares.
        ``ring``: the trainer itself switches the model to ring attention
        (``--seq-parallel``); every other field must still be the flagship's."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        cli = pkg("cli")
        fed = pkg("cli.federated")
        if self.cfg_path is None:
            self.cfg_path = self._write_config()
        bs = 8 if self.rehearsal else 64
        argv = [
            "federated", "--config", self.cfg_path,
            # Each client samples the whole set: 0.6 x 8 batches of train
            # rows is 5 lockstep steps an epoch.
            "--synthetic", str(8 * bs), "--data-fraction", "1.0",
            "--rounds", "1", "--epochs", "1", "--batch-size", str(bs),
            "--output-dir", os.path.join(self.workdir, f"{name}_out"),
            *extra,
        ]
        self.say(f"{name}: fedtpu {' '.join(argv)}")
        run = fed.run_federated(cli.build_parser().parse_args(argv))
        trainer, state = run.trainer, run.state
        C = trainer.C

        flagship = self.flagship()
        if ring:
            flagship = flagship.replace(attention_impl="ring")
        check(trainer.cfg.model == flagship, f"{name}: not the flagship model config")
        n_params = sum(int(x.size) for x in jax.tree.leaves(state.params)) // C
        model_mod = pkg("models.distilbert")
        want_params = sum(
            int(np.prod(x.shape))
            for x in jax.tree.leaves(
                jax.eval_shape(
                    lambda: model_mod.init_params(
                        model_mod.DDoSClassifier(flagship), flagship,
                        jax.random.key(0),
                    )
                )
            )
        )
        self.say(
            f"{name}: built {n_params:,} parameters per client "
            f"(vocab {trainer.cfg.model.vocab_size}, "
            f"{trainer.cfg.model.n_layers}x{trainer.cfg.model.dim}x"
            f"{trainer.cfg.model.n_heads}x{trainer.cfg.model.hidden_dim}, "
            f"L={trainer.cfg.model.max_len}, {trainer.cfg.model.compute_dtype})"
        )
        check(n_params == want_params, f"{name}: built {n_params}, flagship has {want_params}")
        if not self.rehearsal:
            check(65e6 < n_params < 68e6, f"{name}: {n_params} is not ~66 M")

        asked = (trainer.cfg.mesh.clients, trainer.cfg.mesh.data) + (
            (trainer.cfg.mesh.seq,) if len(want_mesh) == 3 else ()
        )
        got = tuple(trainer.mesh.devices.shape)
        mesh_ids = sorted(int(d.id) for d in trainer.mesh.devices.flat)
        self.say(
            f"{name}: mesh asked {'x'.join(map(str, asked))}, expected on "
            f"{len(self.devices)} device(s) {'x'.join(map(str, want_mesh))}, "
            f"got {'x'.join(map(str, got))} on device ids {mesh_ids}"
            + (
                ""
                if asked == want_mesh
                else f" — REFIT, not the mesh asked for: the CLI asks one "
                f"mesh row per client and {C} clients on "
                f"{len(self.devices)} device(s) can only stack"
            )
        )
        check(got == want_mesh, f"{name}: mesh {got} is not the expected {want_mesh}")
        # The one refit this smoke accepts is the one the hardware forces.
        check(
            asked == want_mesh or int(np.prod(asked)) > len(self.devices),
            f"{name}: {asked} fits {len(self.devices)} device(s), yet "
            f"{want_mesh} was expected",
        )
        platforms = {d.platform for d in trainer.mesh.devices.flat}
        check(
            platforms == {self.device["platform"]},
            f"{name}: mesh devices are {platforms}",
        )

        steps = int(state.step)
        losses = np.concatenate([np.asarray(x) for x in run.round_losses])
        self.say(
            f"{name}: {steps} lockstep step(s); epoch-mean loss per client "
            f"{np.round(losses, 4).tolist()} (per-step lines above)"
        )
        check(steps >= 3, f"{name}: only {steps} steps were taken")
        check(bool(np.isfinite(losses).all()), f"{name}: non-finite loss {losses}")

        # Where the stacked parameters and a batch actually live.
        param_ids = sorted(
            {
                int(s.device.id)
                for leaf in jax.tree.leaves(state.params)
                for s in leaf.addressable_shards
            }
        )
        rows = want_mesh[0]
        lead = {
            int(s.data.shape[0])
            for leaf in jax.tree.leaves(state.params)
            for s in leaf.addressable_shards
        }
        L = trainer.cfg.model.max_len
        feed = pkg("parallel.multihost").global_batch(
            trainer.sh.batch,
            {"input_ids": np.zeros((C, bs, L), np.int32)},
            C,
        )["input_ids"]
        batch_ids = sorted(int(s.device.id) for s in feed.addressable_shards)
        shard_shapes = {tuple(s.data.shape) for s in feed.addressable_shards}
        self.say(
            f"{name}: stacked params on device ids {param_ids} "
            f"({C // rows} client row(s) per shard); a [C={C}, B={bs}, L={L}] "
            f"batch on device ids {batch_ids} in shards {sorted(shard_shapes)}"
        )
        check(param_ids == mesh_ids, f"{name}: params on {param_ids}, mesh is {mesh_ids}")
        check(lead == {C // rows}, f"{name}: param shards lead with {lead}")
        check(batch_ids == mesh_ids, f"{name}: batch on {batch_ids}, mesh is {mesh_ids}")
        # After FedAvg every client's replica is the same model.
        same = all(
            bool(jnp.all(x == x[:1])) for x in jax.tree.leaves(state.params)
        )
        check(same, f"{name}: replicas differ after aggregation")
        self.say(f"{name}: all {C} replicas identical after aggregation")
        return run, {
            "params_per_client": n_params,
            "mesh_asked": list(asked),
            "mesh_got": list(got),
            "mesh_refit": asked != got,
            "device_ids": mesh_ids,
            "steps": steps,
            "epoch_mean_loss": np.round(losses, 5).tolist(),
        }

    def phase_train(self) -> dict:
        """§1: a few lockstep steps over 2 clients, one FedAvg round, an
        Orbax checkpoint. The mesh asked for is 2x1; one chip cannot give it
        (the CLI has no way to ask 2 clients for fewer than 2 rows), so there
        the trainer's refit to 1x1 is what must come out, said as a refit."""
        import jax
        import numpy as np

        self.ckpt_dir = os.path.join(self.workdir, "ckpt")
        want = (2, 1) if len(self.devices) >= 2 else (1, 1)
        run, rec = self._federated(
            "train", ["--num-clients", "2", "--checkpoint-dir", self.ckpt_dir], want
        )
        latest = pkg("serving.reload").latest_finalized_step
        step = latest(self.ckpt_dir)
        check(step == 1, f"train: latest finalized checkpoint step is {step}, want 1")
        # Replica 0 is the global model; its bytes (on the host, as a
        # registry artifact would hold them) are what serving must load.
        self.trained_params = jax.tree.map(
            lambda x: np.asarray(x[0]), run.state.params
        )
        self.trained_crc = tree_crc(self.trained_params)
        self.say(
            f"train: checkpoint step {step} in {self.ckpt_dir}; "
            f"crc32 of the global model {self.trained_crc:#010x}"
        )
        rec.update(checkpoint_step=step, params_crc32=self.trained_crc)
        return rec

    def _serve(self, name: str, extra: list[str]) -> tuple[dict, list[float]]:
        """One ``fedtpu infer-serve`` lifetime: restore, listen, answer."""
        import jax
        import numpy as np

        cli = pkg("cli")
        serving_cli = pkg("cli.serving")
        serving = pkg("serving")
        argv = [
            "infer-serve", "--config", self.cfg_path,
            "--checkpoint-dir", self.ckpt_dir, "--buckets", "1,8",
            "--host", "127.0.0.1", "--port", "0", *extra,
        ]
        self.say(f"{name}: fedtpu {' '.join(argv)}")
        if not self.texts:
            data = pkg("data")
            spec = pkg("data.datasets").get_dataset("cicids2017")
            self.texts = spec.render_texts(data.make_synthetic("cicids2017", 12, seed=3))
        server, banner = serving_cli.build_infer_server(
            cli.build_parser().parse_args(argv)
        )
        probs: list[float] = []
        rejected = 0
        with server:
            self.say(f"{name}: {banner}")
            served = server.engine.snapshot()[0]
            served_crc = tree_crc(served)
            # Where the engine put them, and what one chip holds at rest.
            placed_ids = sorted(
                {int(d.id) for x in jax.tree.leaves(served) for d in x.devices()}
            )
            bytes_per_chip = pkg("parallel.mesh").device_tree_bytes(served)
            del served
            check(
                served_crc == self.trained_crc,
                f"{name}: restored params crc {served_crc:#010x} is not the "
                f"trainer's {self.trained_crc:#010x}",
            )
            with serving.ScoringClient("127.0.0.1", server.port, timeout=120.0) as c:
                for text in self.texts:
                    try:
                        reply = c.score(text=text)
                    except serving.ScoreRejected as e:
                        rejected += 1
                        self.say(f"{name}: REJECTED {e}")
                        continue
                    check(
                        reply["round"] == 1,
                        f"{name}: reply names round {reply['round']}, "
                        "the checkpoint is round 1",
                    )
                    probs.append(float(reply["prob"]))
                stats = c.stats()
        sent = len(self.texts)
        self.say(
            f"{name}: restored params crc32 {served_crc:#010x} == trainer's, "
            f"on device ids {placed_ids}, {bytes_per_chip:,} bytes at rest per "
            f"chip; requests sent {sent}, answered {len(probs)}, rejected {rejected} "
            f"(server counts {stats['rejects_total']}); server round "
            f"{stats['round']}; probs {np.round(probs, 4).tolist()}"
        )
        check(rejected == 0 and stats["rejects_total"] == 0, f"{name}: rejects")
        check(len(probs) == sent, f"{name}: {len(probs)} of {sent} answered")
        check(stats["round"] == 1, f"{name}: server reports round {stats['round']}")
        check(
            all(np.isfinite(p) and 0.0 <= p <= 1.0 for p in probs),
            f"{name}: a probability is outside [0, 1]",
        )
        # The reference: the trainer's in-memory model on the same rows,
        # one batch, straight through model.apply. bf16 compute and a
        # different batch shape: agreement to 5e-3, not to the bit — and,
        # since random weights answer every flow within a few hundredths
        # of each other, to well inside the spread of the answers, so a
        # scorer that ignored its input could not pass.
        tok = server.tok
        enc = tok.batch_encode(self.texts, max_len=server.engine.seq_len)
        model = pkg("models.distilbert").DDoSClassifier(
            server.engine.model_cfg
        )
        logits = jax.jit(
            lambda p, i, m: model.apply({"params": p}, i, m, True)
        )(self.trained_params, enc["input_ids"], enc["attention_mask"])
        ref = np.asarray(jax.nn.softmax(logits, axis=-1)[:, 1], np.float32)
        err = float(np.abs(ref - np.asarray(probs, np.float32)).max())
        spread = float(ref.max() - ref.min())
        self.say(
            f"{name}: max |served - trainer-side reference| = {err:.5f} "
            f"(limit {SERVE_TOL}); the reference's answers span {spread:.5f}"
        )
        check(
            err <= SERVE_TOL,
            f"{name}: served probs differ from the reference by {err}",
        )
        check(
            err <= spread / 4,
            f"{name}: error {err} is not small against the {spread} the "
            "answers span: the comparison cannot tell the flows apart",
        )
        return {
            "requests_sent": sent, "answered": len(probs), "rejected": rejected,
            "server_round": stats["round"], "restored_crc32": served_crc,
            "param_device_ids": placed_ids, "param_bytes_per_chip": bytes_per_chip,
            "max_abs_err_vs_reference": err, "reference_spread": spread,
        }, probs

    def phase_serve_cold(self) -> dict:
        rec, self.replies = self._serve("serve_cold", [])
        return rec

    def phase_serve_warm(self) -> dict:
        """§5: the same phase again with every in-memory executable
        dropped, so each program comes back through the persistent cache."""
        import jax

        cold = self.records["serve_cold"]
        jax.clear_caches()
        before = self.meter.snapshot()
        rec, probs = self._serve("serve_warm", [])
        after = self.meter.snapshot()
        hits = after["cache_hits"] - before["cache_hits"]
        warm_s = after["compile_s"] - before["compile_s"]
        check(probs == self.replies, "serve_warm: answers differ from serve_cold's")
        check(hits >= 1, "serve_warm: no persistent-cache hit")
        # A machine that keeps JAX_COMPILATION_CACHE_DIR between calls makes
        # the first run warm too; it was cold only if this run hit more.
        first_was_cold = cold["cache_hits"] < hits
        self.say(
            f"serve_warm: cache dir {jax.config.jax_compilation_cache_dir}: "
            f"first run compile {self.secs(cold.get('compile_s', 0.0))} "
            f"({cold['cache_hits']} hit(s), {cold['cache_misses']} miss(es): "
            f"{'cold' if first_was_cold else 'already warm from an earlier call'}), "
            f"second run compile {self.secs(warm_s)} with {hits} hit(s)"
        )
        if first_was_cold and not self.rehearsal:
            check(
                warm_s < cold["compile_s"],
                f"serve_warm: warm compile {warm_s:.2f}s is not below cold "
                f"{cold['compile_s']:.2f}s",
            )
        if not self.rehearsal:
            rec.update(cold_compile_s=cold["compile_s"], warm_compile_s=round(warm_s, 3))
        rec["warm_cache_hits"] = hits
        return rec

    # ------------------------------------------------------- four chips (§7)
    def phase_fed_2x2(self) -> dict:
        return self._federated(
            "fed_2x2", ["--num-clients", "2", "--data-parallel", "2"], (2, 2)
        )[1]

    def phase_fed_4x1(self) -> dict:
        return self._federated("fed_4x1", ["--num-clients", "4"], (4, 1))[1]

    def phase_fed_seq(self) -> dict:
        """One ``--seq-parallel 2`` launch: the ring's ppermute over real
        links (2 clients x 1 x 2)."""
        return self._federated(
            "fed_seq", ["--num-clients", "2", "--seq-parallel", "2"], (2, 1, 2),
            ring=True,
        )[1]

    def phase_serve_fsdp(self) -> dict:
        """``infer-serve --data-parallel 2 --fsdp``: the same answers as the
        replicated engine, to the bit, from half the bytes at rest."""
        rec, probs = self._serve("serve_fsdp", ["--data-parallel", "2", "--fsdp"])
        check(
            probs == self.replies,
            "serve_fsdp: the sharded scorer's answers are not byte-equal to "
            f"the replicated engine's: {probs} vs {self.replies}",
        )
        self.say("serve_fsdp: answers byte-equal to the replicated engine's")
        cold = self.records["serve_cold"]
        shd_b, rep_b = rec["param_bytes_per_chip"], cold["param_bytes_per_chip"]
        in_use = {
            int(d.id): (d.memory_stats() or {}).get("bytes_in_use")
            for d in self.devices
        }
        self.say(
            f"serve_fsdp: at-rest param bytes per chip {shd_b:,} sharded vs "
            f"{rep_b:,} replicated ({shd_b / rep_b:.2f}x); memory_stats "
            f"bytes_in_use per device id {in_use}"
        )
        # The fleet builds each of its N replicas exactly as serve_cold's
        # engine was built: no device named (ROADMAP Queue 2 item 6).
        self.say(
            f"serve_fsdp: the replicated engine, no device named, held its "
            f"params on device ids {cold['param_device_ids']} of "
            f"{[int(d.id) for d in self.devices]}"
        )
        check(shd_b <= 0.6 * rep_b, f"serve_fsdp: {shd_b} is not ~half of {rep_b}")
        rec["memory_stats_bytes_in_use"] = in_use
        return rec


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny preset on 4 virtual CPU devices; no timing is printed",
    )
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, PKG)):
        sys.stderr.write(
            f"chip_smoke: {HERE} holds no {PKG}/ — not inside a checkout\n"
        )
        return 2
    sys.path.insert(0, HERE)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    # The cache directory is settled before jax is imported (§5).
    cache_dir = pkg("utils.compile_cache").place_compile_cache()
    import jax

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 4)
        # The tiny programs compile in under the 1 s a cache entry must
        # have cost by default; without this the warm phase could not hit.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import jaxlib

    dev = jax.devices()[0]
    from importlib import metadata

    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__}
    for dist in ("libtpu", "flax", "optax", "orbax-checkpoint"):
        try:
            versions[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            versions[dist] = "not installed"
    print(
        f"[smoke] device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(jax.devices())}; versions {versions}; compile cache "
        f"{cache_dir}",
        flush=True,
    )
    if args.rehearse_cpu:
        print(
            "[smoke] REHEARSAL on the CPU at the tiny preset: control flow "
            "only, kernels interpreted, no timing printed, nothing below is "
            "a device number",
            flush=True,
        )
    elif dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU — "
            "nothing was run (--rehearse-cpu rehearses the control flow)\n"
        )
        return 2

    names = list(ONE_CHIP_PHASES)
    if len(jax.devices()) >= 4:
        names += FOUR_CHIP_PHASES
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    smoke = Smoke(rehearsal=args.rehearse_cpu, workdir=workdir)
    try:
        for name in names:
            smoke.run_phase(name, getattr(smoke, f"phase_{name}"))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    failed = [n for n, r in smoke.records.items() if not r["ok"]]
    report = {
        "ok": not failed,
        "rehearsal": args.rehearse_cpu,
        "device": smoke.device,
        "versions": versions,
        "compile_cache_dir": cache_dir,
        "phases": smoke.records,
    }
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    for name, rec in smoke.records.items():
        print(f"[smoke] {name:<11} {'PASS' if rec['ok'] else 'FAIL'}", flush=True)
    if failed:
        print(f"[smoke] FAILED: {failed}", flush=True)
        print(json.dumps({"ok": False, "failed": failed, "device": smoke.device}))
        return 1
    verdict = {"ok": True, "device": smoke.device}
    if args.rehearse_cpu:
        verdict["rehearsal"] = True
    print(json.dumps(verdict))
    return 0


if __name__ == "__main__":
    sys.exit(main())
