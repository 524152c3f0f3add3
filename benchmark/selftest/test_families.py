"""The model-family seam, and the yardstick's quick checks, under pytest:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/selftest/test_families.py -q -p no:cacheprovider

On the CPU, each case under its own short timeout; nothing here is a speed.

(a) every configuration's family counts the FLOPs ``utils/profiling.py``
counts and the constants PERF.md quotes, the parameters the file states and
32 B of them a step; (b) a configuration without ``family``, or naming a
module that is not there, is an error with the path looked for; (c) **the
seam holds**: a second family, installed as ``benchmark.families.<name>``, is
what ``Context.model_config``, ``harness.check_model``, ``readers/mfu`` and
``readers/span_roofline`` use for a configuration that names it, with no edit
to ``harness.py`` or a reader, and a family whose ``program`` answers wrongly
comes out not correct; (d) no file under ``benchmark/`` outside ``families/``
and ``reference/`` names a class or module of the program's model. Beside
them, as cases of one parametrised test, the checks of ``selftest/run.py``
that touch no device and end in seconds, and the collectives that
``tools/rehearse_compile.py`` reads out of a compiled program's text.

``tests/`` is outside what a benchmark PR may touch: a later PR hooks this
file into tier 1 (``from benchmark.selftest.test_families import *`` in a
``tests/test_benchmark_families.py``).
"""

from __future__ import annotations

import collections
import glob
import json
import os
import signal
import sys
import tempfile
import time
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, flops, harness  # noqa: E402
from benchmark.families import bert_encoder  # noqa: E402
from benchmark.readers import mfu, span_roofline  # noqa: E402
from benchmark.selftest import run as selftest  # noqa: E402
from benchmark.tools import rehearse_compile  # noqa: E402

CASE_TIMEOUT_S = 120
CONFIGS = sorted(
    os.path.basename(p)[: -len(".json")]
    for p in glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))
)
#: What PERF.md quotes: FLOP a row forward and a row for a step at L=128,
#: parameters (the configuration file's ``parameters``).
QUOTED = {
    "distilbert-base-l128": (11_173_628_928, 33_520_886_784, 66_364_418),
    "bert-large-l128": (78_920_028_160, 236_760_084_480, 334_092_290),
}


@pytest.fixture(autouse=True)
def case_timeout():
    """Each case ends inside CASE_TIMEOUT_S or fails (no plugin needed)."""

    def expired(signum, frame):
        raise TimeoutError(f"the case ran over {CASE_TIMEOUT_S} s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(CASE_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def context(config: dict, *, rehearsal: bool = True):
    """A context built by hand, as ``selftest/run.py`` builds one."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    cell = "distilbert-fed-round-c8"
    ctx = harness.Context(
        workload=cell, seed=5, seconds=0.0, trace=True, rehearsal=rehearsal, chips=1,
        config=config, traffic=harness.load_json("traffic", "fed-round-c8.json"),
        cell=harness.load_json("cells", f"{cell}.json"), t_start=time.perf_counter(),
        workdir=tempfile.gettempdir(), rec=harness.Recorder(), meter=None, devices=jax.devices(),
    )
    ctx.said = []
    ctx.say = ctx.said.append
    return ctx


# ------------------------------------------------- (a) the family's counts
@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_the_quoted(name):
    """That they equal ``utils/profiling.py``'s and what the program builds is
    ``selftest.check_flops``, a case of ``test_selftest_quick``."""
    conf = harness.load_json("configs", f"{name}.json")
    family, model = families.load(conf), conf["model"]
    assert family is bert_encoder
    forward, step, parameters = QUOTED[name]
    assert family.forward_flops(model, 1) == forward
    assert family.train_step_flops(model, 1) == step == 3 * forward
    # Counters a driver put on the spans change nothing for this family.
    assert family.train_step_flops(model, 7, steps=3, routed_tokens=99) == 7 * step
    assert family.param_count(model) == conf["parameters"] == parameters
    assert family.train_step_bytes(model) == 32.0 * parameters
    assert family.train_step_bytes(model, steps=16, routed_tokens=99) == 16 * 32.0 * parameters


def test_quoted_work_of_the_traced_fits():
    """PERF.md section 5: the flagship's traced fit (8 clients x 1,024 rows in
    128 steps) "needs 274.6 TFLOP and at least 271.8 GB", BERT-large's (1,024
    rows in 16 steps) "242.4 TFLOP / 171.1 GB"."""
    small = harness.load_json("configs", "distilbert-base-l128.json")["model"]
    large = harness.load_json("configs", "bert-large-l128.json")["model"]
    assert f"{bert_encoder.train_step_flops(small, 8192) / 1e12:.1f}" == "274.6"
    assert f"{bert_encoder.train_step_bytes(small, steps=128) / 1e9:.1f}" == "271.8"
    assert f"{bert_encoder.train_step_flops(large, 1024) / 1e12:.1f}" == "242.4"
    assert f"{bert_encoder.train_step_bytes(large, steps=16) / 1e9:.1f}" == "171.1"


def test_tiny_keeps_what_is_not_a_size():
    model = harness.load_json("configs", "bert-large-l128.json")["model"]
    tiny = bert_encoder.tiny(model)
    assert set(tiny) >= set(model)
    assert all(tiny[k] == model[k] for k in bert_encoder.REHEARSAL_KEEPS)
    assert tiny["dim"] < model["dim"] and tiny["n_layers"] < model["n_layers"]
    assert context(harness.load_json("configs", "bert-large-l128.json")).model == tiny


# ----------------------------------------------- (b) a family is named, or not
def test_a_configuration_without_family_is_an_error_with_the_path():
    conf = harness.load_json("configs", "distilbert-base-l128.json")
    del conf["family"]
    with pytest.raises(KeyError) as e:
        families.load(conf)
    assert os.path.join("benchmark", "families", "<family>.py") in str(e.value)
    assert "distilbert-base-l128" in str(e.value)
    with pytest.raises(KeyError):
        context(conf).model_config()


def test_a_family_that_is_not_there_is_an_error_with_the_path():
    conf = {**harness.load_json("configs", "distilbert-base-l128.json"), "family": "no_such_family"}
    with pytest.raises(ModuleNotFoundError) as e:
        families.load(conf)
    assert os.path.join(ROOT, "benchmark", "families", "no_such_family.py") in str(e.value)
    with pytest.raises(ModuleNotFoundError):
        context(conf).model


def test_a_family_that_cannot_import_says_so_itself(monkeypatch):
    """A family module that is there and imports something that is not: the
    error names what it could not import, not the family."""
    monkeypatch.setattr(
        families.importlib, "import_module",
        lambda name: (_ for _ in ()).throw(ModuleNotFoundError("No module named 'moe_lib'", name="moe_lib")),
    )
    with pytest.raises(ModuleNotFoundError, match="moe_lib"):
        families.load({"name": "x", "family": "bert_encoder"})


# --------------------------------------------------- (c) the seam holds
def install_family(monkeypatch, name: str, **overrides):
    """A second family under ``benchmark.families.<name>``: ``bert_encoder``
    with its own step FLOPs and bytes, its own limits and a reference and a
    ``model_config`` that count their calls; ``overrides`` replace more."""
    mod = types.ModuleType(f"benchmark.families.{name}")
    mod.__dict__.update({k: v for k, v in vars(bert_encoder).items() if not k.startswith("__")})
    mod.calls = collections.Counter()
    mod.seen = {}

    def model_config(model):
        mod.calls["model_config"] += 1
        return bert_encoder.model_config(model)

    def reference(params, ids, mask, model, **kw):
        mod.calls["reference"] += 1
        return bert_encoder.reference(params, ids, mask, model, **kw)

    def train_step_flops(model, rows=1, seq_len=None, **counters):
        mod.seen["flops"] = dict(counters)
        return 1e9 * rows + 5e8 * counters.get("routed_tokens", 0.0)

    def train_step_bytes(model, steps=1, **counters):
        mod.seen["bytes"] = {"steps": steps, **counters}
        return 1e6 * steps

    mod.model_config, mod.reference = model_config, reference
    mod.train_step_flops, mod.train_step_bytes = train_step_flops, train_step_bytes
    mod.TOLERANCES = {**bert_encoder.TOLERANCES, "hidden_rel": 0.015, "logit_rel": 0.025, "binding": 3.0}
    mod.__dict__.update(overrides)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def named(family: str) -> dict:
    """The flagship's configuration, naming another family."""
    return {**harness.load_json("configs", "distilbert-base-l128.json"), "family": family}


def weights_and_flows(ctx):
    tok = harness.pkg("data").default_tokenizer()
    _, split = harness.tokenised_flows(ctx, 24, ctx.seed, tok)
    params = harness.init_params_on_device(ctx.family, ctx.model_config(), ctx.seed, "threefry2x32")
    return params, split


def test_check_model_uses_the_named_familys_reference_and_limits(monkeypatch):
    mod = install_family(monkeypatch, "standin_a")
    ctx = context(named("standin_a"))
    assert ctx.family is mod
    params, split = weights_and_flows(ctx)
    assert mod.calls["model_config"] >= 1
    harness.check_model(ctx, params, split, what="stand-in", key="k", bind=True)
    assert mod.calls["reference"] == 1
    assert not ctx.problems, ctx.problems
    assert {k: limit for k, (_, limit) in ctx.compared.items()} == {
        "k.hidden_rel": 0.015, "k.binding": 3.0, "k.logit_rel": 0.025,
    }
    line = next(x for x in ctx.said if x.startswith("correct/stand-in"))
    assert "limit 1.5%" in line and "(limit 3)" in line and "(limit 2.5%)" in line
    # The benchmark's own family prints the limits it printed before.
    ctx = context(harness.load_json("configs", "distilbert-base-l128.json"))
    harness.check_model(ctx, params, split, what="own", key="k", bind=True)
    line = next(x for x in ctx.said if x.startswith("correct/own"))
    assert "limit 3.5%" in line and "(limit 2)" in line and "(limit 6%)" in line
    assert mod.calls["reference"] == 1


FAULTS = {
    # An answer altered where it is produced: hidden states a tenth off.
    "hidden states scaled": (lambda h, z: (h * 1.1, z), "hidden states differ"),
    # Every sequence answered with its neighbour's states.
    "sequences mixed up": (lambda h, z: (jnp.roll(h, 1, 0), z), "cannot bind"),
    "logits shifted": (lambda h, z: (h, z + 0.5), "logits differ"),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_family_whose_program_answers_wrongly_is_not_correct(monkeypatch, fault):
    alter, says = FAULTS[fault]

    def program(model_cfg):
        forward = bert_encoder.program(model_cfg)
        return lambda p, i, a: alter(*forward(p, i, a))

    install_family(monkeypatch, "standin_b", program=program, TOLERANCES=bert_encoder.TOLERANCES)
    ctx = context(named("standin_b"))
    params, split = weights_and_flows(ctx)
    harness.check_model(ctx, params, split, what="faulty", key="k", bind=True)
    assert any(says in p for p in ctx.problems), (ctx.problems, ctx.compared)


def fit_spans(ctx, phase: str):
    for t0 in (0.0, 2.0):
        ctx.rec.spans.append({
            "name": "fit", "phase": phase, "rows": 64, "steps": 4, "routed_tokens": 10,
            "losses_finite": True, "t0": t0, "t1": t0 + 0.5,
        })


def test_mfu_reads_the_named_familys_counts(monkeypatch):
    mod = install_family(monkeypatch, "standin_c")
    ctx = context(named("standin_c"))
    assert mfu.read(ctx) is None  # no span: nothing to read
    fit_spans(ctx, "window")
    peak = ctx.peaks()["bf16_flops_per_s"]
    assert mfu.read(ctx) == pytest.approx(100.0 * (1e9 * 128 + 5e8 * 20) / 1.0 / peak, rel=1e-12)
    assert mod.seen["flops"] == {"steps": 8.0, "routed_tokens": 20.0}
    # The benchmark's own family, on the same spans: FLOPs a row x rows a second.
    own = context(harness.load_json("configs", "distilbert-base-l128.json"))
    fit_spans(own, "window")
    want = 100.0 * bert_encoder.train_step_flops(own.model, 1) * 128 / 1.0 / peak
    assert mfu.read(own) == pytest.approx(want, rel=1e-12)


def test_span_roofline_reads_the_named_familys_counts(monkeypatch):
    mod = install_family(monkeypatch, "standin_d")
    ctx = context(named("standin_d"))
    assert span_roofline.read(ctx) is None  # no trace: nothing to read
    fit_spans(ctx, "traced")
    # A made-up reduced trace: one chip busy 0.4 s inside each fit span.
    ops = (["op"] * 2, np.array([0.05e9, 2.05e9]), np.array([0.4e9, 0.4e9]))
    ctx.rec.data["xplane"] = {
        "chips": [0], "window": (0.0, 3e9),
        "trace": {"chips": {0: {"ops": ops}}, "spans": [("fit", 0.0, 0.5e9), ("fit", 2e9, 2.5e9)]},
    }
    peaks = ctx.peaks()
    need_f, need_b = 1e9 * 128 + 5e8 * 20, 1e6 * 8
    floor, roof = flops.roofline_floor_s(need_f, need_b, peaks, 1)
    assert span_roofline.read(ctx) == pytest.approx(100.0 * floor / 0.8, rel=1e-9)
    assert mod.seen["flops"] == mod.seen["bytes"] == {"steps": 8.0, "routed_tokens": 20.0}
    line = next(x for x in ctx.said if x.startswith("roofline/fit"))
    assert "128 rows in 8 step(s) need 0.138 TFLOP and at least 0.008 GB" in line and roof in line


def test_recorder_counters_sum_what_a_driver_counted():
    rec = harness.Recorder()
    rec.phase = "window"
    with rec.span("fit", rows=8, steps=2, note="x", flag=True):
        pass
    with rec.span("fit", rows=8, steps=2, routed_tokens=5):
        pass
    with rec.span("eval", rows=3):
        pass
    assert rec.counters("fit") == {"rows": 16.0, "steps": 4.0, "routed_tokens": 5.0}
    assert rec.counters("fit", phase="traced") == {}


def test_compare_keeps_each_number_beside_its_limit():
    ctx = context(harness.load_json("configs", "distilbert-base-l128.json"))
    assert ctx.compare("a", 0.01, 0.02) and not ctx.compare("b", 0.03, 0.02)
    assert ctx.compare("c", 3.0, 2.0, least=True) and not ctx.compare("d", 1.0, 2.0, least=True)
    assert not ctx.compare("e", float("nan"), 1.0)
    assert ctx.compared["b"] == [0.03, 0.02] and list(ctx.compared) == list("abcde")


# ------------------------------------------------------ (d) the guard
MODEL_NAMES = ("models" + ".distilbert", "DDoS" + "Classifier", "DistilBert" + "Encoder",
               "Model" + "Config(", "encoder" + "_fp32")


def test_nothing_outside_the_family_names_the_programs_model():
    bench = os.path.join(ROOT, "benchmark")
    offenders = []
    for folder, dirs, files in os.walk(bench):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        if os.path.relpath(folder, bench).split(os.sep)[0] in ("families", "reference"):
            continue
        for name in files:
            if not name.endswith((".py", ".json", ".txt", ".toml", ".csv", ".jsonl")):
                continue
            with open(os.path.join(folder, name), encoding="utf-8") as f:
                text = f.read()
            offenders += [
                f"{os.path.relpath(os.path.join(folder, name), ROOT)}: {x}" for x in MODEL_NAMES if x in text
            ]
    assert not offenders, offenders
    assert [n for n, x in vars(flops).items() if callable(x) and not n.startswith("_")] == [
        "load_peaks", "roofline_floor_s",
    ]
    assert not hasattr(harness, "HIDDEN_TOL_REL") and not hasattr(harness, "logit_scale")


def test_every_configuration_names_a_family_that_is_there():
    for name in CONFIGS:
        with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
            conf = json.load(f)
        assert conf["family"] == "bert_encoder" and os.path.isfile(
            os.path.join(ROOT, "benchmark", "families", f"{conf['family']}.py")
        )


# -------------------------- selftest/run.py's quick checks, one case each
@pytest.mark.parametrize("check", selftest.QUICK, ids=lambda f: f.__name__)
def test_selftest_quick(check):
    assert check()


# ------------------------------------- tools/rehearse_compile.py's reading
HLO = """
  %all-reduce.5 = (bf16[4,768,768]{2,1,0:T(8,128)(2,1)}, bf16[4,768]{1,0}) all-reduce(bf16[4,768,768] %a, bf16[4,768] %b), channel_id=1, to_apply=%add
  %all-reduce.6 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}
  %ars = f32[16]{0:T(1024)S(1)} all-reduce-start(f32[16] %y)
  %ard = f32[16]{0} all-reduce-done(f32[16] %ars)
  %fusion.1 = f32[4]{0} fusion(f32[4] %all-reduce.6), kind=kLoop
  %ag = (bf16[2,4]{1,0}, bf16[4,4]{1,0}) all-gather-start(bf16[2,4] %z), dimensions={0}
"""


def test_rehearse_compile_counts_tuple_typed_collectives_and_their_bytes():
    found = rehearse_compile.collectives(HLO)
    assert found["all-reduce"]["count"] == 3  # the tuple-typed, the plain, the -start; not the -done
    assert dict(found["all-reduce"]["bytes"]) == {"bf16": 2 * (4 * 768 * 768 + 4 * 768), "f32": 4 * (8 + 16)}
    assert found["all-gather"]["count"] == 1 and dict(found["all-gather"]["bytes"]) == {"bf16": 2 * (8 + 16)}
    assert rehearse_compile.collectives("%x = f32[4]{0} add(f32[4] %a, f32[4] %b)") == {}
