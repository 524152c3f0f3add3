"""The profiler-clock plane: ``obs/trace.py::annotate`` and the program
names the compile ledger gives.

Under a ``jax.profiler`` session the trainers put ``fedtpu:<name>``
annotations on the ``/host:CPU`` plane of the ``.xplane.pb`` (tiny preset,
CPU: names, counts and nesting, never a time); without a session nothing is
written. Every jitted program on the fed, engine and FedAvg paths lowers to
a module named after what it is, the ledger's sites to ``jit_<site>``. The
events-JSONL plane stays what it was. The benchmark's readers of all this
are checked by ``benchmark/selftest/program_spans.py``, run from here.
"""

import collections
import dataclasses
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import (
    DataConfig,
    ExperimentConfig,
    FedConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
    default_tokenizer,
    make_all_client_splits,
    make_synthetic_flows,
    stack_clients,
    tokenize_client,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
    default_ledger,
    trace as obs_trace,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.trace import (
    ANNOTATIONS,
    annotate,
    annotate_iter,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.parallel.mesh import (
    make_mesh,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train import (
    FederatedTrainer,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import (
    Trainer,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN = 32
C = 4
BATCH = 8
#: A learning rate no other test file uses: the step programs are memoised
#: per configuration, process-wide, and the trace counts below are of
#: programs this file compiled itself.
LR = 1.2345e-3


def host_annotations(trace_dir) -> list[tuple[str, int, int, str]]:
    """``(name, t0, t1, line)`` of every ``fedtpu:`` event on ``/host:CPU``
    of the newest trace under ``trace_dir``, by start."""
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fedtpu:"):
                    out.append((e.name[len("fedtpu:"):], e.start_ns, e.start_ns + e.duration_ns, line.name))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def start_trace(out) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the annotations are TraceMe events, not Python frames
    jax.profiler.start_trace(str(out), profiler_options=opts)


def named(spans, name):
    return [s for s in spans if s[0] == name]


def within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def tok():
    return default_tokenizer()


@pytest.fixture(scope="module")
def clients(tok):
    df = make_synthetic_flows(400, seed=3)
    splits = make_all_client_splits(df, C, DataConfig(data_fraction=1.0, max_len=MAX_LEN))
    return [tokenize_client(s, tok, max_len=MAX_LEN) for s in splits]


def fed_cfg(tok, rows: int, data: int, **fed_kw) -> ExperimentConfig:
    return ExperimentConfig(
        model=ModelConfig.tiny(
            vocab_size=len(tok), max_len=MAX_LEN, max_position_embeddings=MAX_LEN,
            dim=32, n_layers=1, n_heads=2, hidden_dim=64,
        ),
        data=DataConfig(max_len=MAX_LEN, batch_size=BATCH, eval_batch_size=BATCH),
        train=TrainConfig(learning_rate=LR, epochs_per_round=1, seed=0, log_every=0),
        fed=FedConfig(num_clients=C, weighted=True, **fed_kw),
        mesh=MeshConfig(clients=rows, data=data),
    )


class Fed:
    """One tiny federation on one path: two untraced rounds (every program
    compiled, every retrace behind it), then a traced one."""

    def __init__(self, path, tok, clients, devices, trace_dir):
        self.path = path
        rows, data = (1, 1) if path == "packed" else (2, 2)
        self.site = "fed.packed_step" if path == "packed" else "fed.train_step"
        self.trainer = FederatedTrainer(
            fed_cfg(tok, rows, data), pad_id=tok.pad_id,
            mesh=make_mesh(rows, data, devices=devices[: rows * data]),
        )
        assert self.trainer._packed_eligible() == (path == "packed")
        self.stacked = stack_clients([c.train for c in clients])
        self.prepared = self.trainer.prepare_eval([c.val for c in clients])
        self.weights = np.array([len(c.train) for c in clients], np.float64)
        self.steps = self.stacked.labels.shape[1] // BATCH
        self.eval_steps = self.prepared.stacked.labels.shape[1] // BATCH
        self.state = self.trainer.init_state()
        before = sum(default_ledger().compile_counts(self.site).values())
        for r in range(2):
            self.round(r)
        self.traces_two_rounds = sum(default_ledger().compile_counts(self.site).values()) - before
        start_trace(trace_dir)
        try:
            self.round(2)
            jax.block_until_ready(self.state.params)
        finally:
            jax.profiler.stop_trace()
        self.traces_third_round = (
            sum(default_ledger().compile_counts(self.site).values()) - before - self.traces_two_rounds
        )
        self.spans = host_annotations(trace_dir)

    def round(self, r: int) -> None:
        t = self.trainer
        state, _ = t.fit_local(self.state, self.stacked, epoch_offset=r)
        t.evaluate_clients(state.params, prepared=self.prepared)
        state = t.round_aggregate(state, round_index=r, weights=self.weights)
        t.evaluate_clients(state.params, prepared=self.prepared)
        self.state = t.reset_optimizer(state)


@pytest.fixture(scope="module", params=["packed", "stacked"])
def fed(request, tok, clients, eight_devices, tmp_path_factory):
    return Fed(request.param, tok, clients, eight_devices, tmp_path_factory.mktemp(f"trace_{request.param}"))


# ------------------------------------------------------- the fed round's plane
def test_fed_round_emits_exactly_the_tables_annotations(fed):
    """One traced round: the names, and how often each."""
    counts = collections.Counter(s[0] for s in fed.spans)
    want = {
        "fit": 1,
        # one per lockstep step, and the last ``next`` that ends the epoch
        "fit/next_batch": fed.steps + 1,
        f"dispatch/{fed.site}": fed.steps * (C if fed.path == "packed" else 1),
        "fit/loss_read": 1,  # one epoch
        "eval": 2,
        "dispatch/fed.eval_step": 2 * fed.eval_steps,
        "eval/read": 2,
        "agg": 1,
        "reset": 1,
    }
    if fed.path == "packed":
        want.update({"fit/unstack": 1, "fit/restack": 1})
    assert dict(counts) == want
    assert len({s[3] for s in fed.spans}) == 1  # one thread drives the round


def test_fed_round_annotations_nest_as_the_table_says(fed):
    (fit,) = named(fed.spans, "fit")
    launches = named(fed.spans, f"dispatch/{fed.site}")
    inside_fit = [s for s in fed.spans if s[0].startswith("fit/")] + launches
    assert all(within(s, fit) for s in inside_fit)
    (read,) = named(fed.spans, "fit/loss_read")
    assert read[1] >= max(s[2] for s in launches)  # after the last launch
    if fed.path == "packed":
        (unstack,) = named(fed.spans, "fit/unstack")
        (restack,) = named(fed.spans, "fit/restack")
        assert unstack[2] <= min(s[1] for s in launches)
        assert restack[1] >= read[2] and restack[2] <= fit[2]
    # Every launch follows its lockstep step's batch: C (packed) or 1 a step.
    batches = named(fed.spans, "fit/next_batch")
    per_step = len(launches) // fed.steps
    for i, launch in enumerate(launches):
        assert batches[i // per_step][2] <= launch[1]
    evals = named(fed.spans, "eval")
    for name in ("dispatch/fed.eval_step", "eval/read"):
        assert all(any(within(s, e) for e in evals) for s in named(fed.spans, name))
    # The round's phases follow one another and do not overlap.
    phases = [s for s in fed.spans if "/" not in s[0]]
    assert [s[0] for s in phases] == ["fit", "eval", "agg", "eval", "reset"]
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))


def test_every_emitted_name_is_in_the_vocabulary(fed):
    sites = set(default_ledger().report()["sites"])
    for name in {s[0] for s in fed.spans}:
        if name.startswith("dispatch/"):
            assert "dispatch/" in ANNOTATIONS and name[len("dispatch/"):] in sites, name
        else:
            assert name in ANNOTATIONS, name


def test_step_site_traces_are_what_step_compiles_reads(fed):
    """The benchmark's ``step_compiles`` (reader ``ledger_count``) is the
    ledger's trace count of the train-step site; a third round adds none."""
    sys.path.insert(0, REPO)
    from benchmark.readers import ledger_count

    with open(os.path.join(REPO, "benchmark", "layer_metrics", "step_compiles.json")) as f:
        sites = json.load(f)["args"]["sites"]
    assert fed.site in sites
    said = []
    ctx = type("Ctx", (), {"say": staticmethod(said.append)})
    ledger = default_ledger()
    assert ledger_count.read(ctx, sites=sites) == sum(
        sum(ledger.compile_counts(s).values()) for s in sites
    )
    assert ledger_count.read(ctx, sites=["no.such_site"]) is None
    assert fed.site in said[0]
    assert fed.traces_two_rounds >= 1 and fed.traces_third_round == 0
    assert ledger.report()["sites"][fed.site]["compiles"] == sum(ledger.compile_counts(fed.site).values())


def test_step_site_seconds_are_what_step_trace_s_reads(fed):
    """The benchmark's ``step_trace_s`` (reader ``ledger_seconds``) is the
    ledger's ``trace_s`` summed over the sites ``step_compiles`` counts at:
    the wall seconds of the calls in which the train-step site traced."""
    sys.path.insert(0, REPO)
    from benchmark.readers import ledger_seconds

    specs = {}
    for name in ("step_trace_s", "step_compiles"):
        with open(os.path.join(REPO, "benchmark", "layer_metrics", f"{name}.json")) as f:
            specs[name] = json.load(f)
    sites = specs["step_trace_s"]["args"]["sites"]
    assert sites == specs["step_compiles"]["args"]["sites"] and fed.site in sites
    said = []
    ctx = type("Ctx", (), {"say": staticmethod(said.append)})
    report = default_ledger().report()["sites"]
    value = ledger_seconds.read(ctx, sites=sites)
    assert value == sum(report[s]["trace_s"] for s in sites if s in report)
    assert value >= report[fed.site]["trace_s"] > 0.0
    assert ledger_seconds.read(ctx, sites=["no.such_site"]) is None
    assert fed.site in said[0] and len(said) == 1


def test_round_anchor_is_annotated_when_it_copies(tok, eight_devices, tmp_path):
    trainer = FederatedTrainer(
        fed_cfg(tok, 1, 1, server_opt="momentum"), pad_id=tok.pad_id,
        mesh=make_mesh(1, 1, devices=eight_devices[:1]),
    )
    state = trainer.init_state()
    start_trace(tmp_path)
    try:
        assert trainer.round_anchor(state) is not None
    finally:
        jax.profiler.stop_trace()
    assert [s[0] for s in host_annotations(tmp_path)] == ["round_anchor"]


# ------------------------------------------------------------- the engine's
def test_engine_fit_and_evaluate_annotations(tok, clients, tmp_path):
    cfg = fed_cfg(tok, 1, 1)
    trainer = Trainer(cfg.model, cfg.train, pad_id=tok.pad_id)
    split = clients[0].train
    steps = len(split) // BATCH
    state = trainer.init_state()
    state, _ = trainer.fit(state, split, batch_size=BATCH, epochs=1)  # compiles
    start_trace(tmp_path)
    try:
        state, _ = trainer.fit(state, split, batch_size=BATCH, epochs=1, epoch_offset=1)
        trainer.evaluate_state(state, clients[0].val, batch_size=BATCH, collect_probs=False)
    finally:
        jax.profiler.stop_trace()
    spans = host_annotations(tmp_path)
    eval_steps = -(-len(clients[0].val) // BATCH)
    assert dict(collections.Counter(s[0] for s in spans)) == {
        "fit": 1, "fit/next_batch": steps + 1, "dispatch/engine.train_step": steps,
        "fit/loss_read": 1, "eval": 1, "dispatch/engine.eval_step": eval_steps, "eval/read": 1,
    }
    (fit,) = named(spans, "fit")
    (ev,) = named(spans, "eval")
    assert all(within(s, fit) for s in spans if s[0].startswith("fit/") or s[0] == "dispatch/engine.train_step")
    assert all(within(s, ev) for s in spans if s[0] in ("dispatch/engine.eval_step", "eval/read"))


# ----------------------------------------------------------- off: nothing
def test_no_session_nothing_written_and_no_clock(monkeypatch, tmp_path):
    """With no profiler session ``annotate`` is one ``with``: it reads no
    clock, writes no record, and ``annotate_iter`` only passes items on."""

    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"annotate touched time.{name}")

    writes = []
    monkeypatch.setattr(obs_trace, "time", NoClock())
    monkeypatch.setattr(obs_trace, "append_jsonl_line", lambda *a: writes.append(a))
    monkeypatch.chdir(tmp_path)
    with annotate("fit"):
        with annotate("fit/unstack"):
            pass
    assert list(annotate_iter("fit/next_batch", iter(range(3)))) == [0, 1, 2]
    assert writes == [] and os.listdir(tmp_path) == []
    assert type(annotate("fit")) is jax.profiler.TraceAnnotation


# ------------------------------------------------------------ program names
def lowered_name(jitted, *args) -> str:
    if not hasattr(jitted, "lower"):  # the ledger's timed wrapper
        jitted = jitted.__wrapped__
    text = jitted.lower(*args).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def test_fed_programs_are_named_by_site_or_by_what_they_are(fed):
    t, state = fed.trainer, fed.state
    batch = next(iter(t._epoch_iterator(fed.stacked, BATCH, 0)))
    sl = slice(0, BATCH)
    ev = fed.prepared.stacked
    eval_batch = {
        "input_ids": ev.input_ids[:, sl], "attention_mask": ev.attention_mask[:, sl],
        "labels": ev.labels[:, sl],
    }
    names = {
        "fed.eval_step": lowered_name(t.eval_step, state.params, eval_batch, fed.prepared.valid[:, sl]),
        "fedavg": lowered_name(t.fedavg_step, state.params, jnp.asarray(fed.weights), None),
        "opt_init": lowered_name(t._opt_init, state.params),
        "replicate": lowered_name(t._replicate, state.step),
        "slice": lowered_name(t._slice_client, state.params, 0),
    }
    want = {
        "fed.eval_step": "jit_fed_eval_step", "fedavg": "jit_fedavg_step", "opt_init": "jit_opt_init",
        "replicate": "jit_replicate", "slice": "jit_slice_client",
    }
    if fed.path == "packed":
        cb = {k: v[0] for k, v in batch.items()}
        cstate = (
            jax.tree.map(lambda x: x[0], state.params), jax.tree.map(lambda x: x[0], state.opt_state),
            state.step, state.rngs[0],
        )
        names["fed.packed_step"] = lowered_name(t._packed_step, cstate, cb)
        names["unstack"] = lowered_name(t._unstack_fn, state.params, state.opt_state)
        names["restack"] = lowered_name(t._restack_fn, cstate[0], cstate[0])
        want.update({
            "fed.packed_step": "jit_fed_packed_step", "unstack": "jit_unstack_clients",
            "restack": "jit_restack_clients",
        })
    else:
        names["fed.train_step"] = lowered_name(t.train_step, state, t._feed(batch))
        want["fed.train_step"] = "jit_fed_train_step"
    assert names == want
    assert not any("lambda" in n for n in names.values())


@pytest.mark.parametrize("mu", [0.0, 0.1], ids=["fedavg", "fedprox"])
def test_engine_and_variant_programs_keep_the_sites_name(tok, clients, eight_devices, mu):
    """The FedProx variants of a site are other bodies under the same
    program name; the engine's two sites likewise."""
    cfg = fed_cfg(tok, 2, 1, prox_mu=mu)
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(cfg.train, prox_mu=mu))
    eng = Trainer(cfg.model, cfg.train, pad_id=tok.pad_id)
    state = eng.init_state()
    split = clients[0].train
    batch = {
        "input_ids": split.input_ids[:BATCH], "attention_mask": split.attention_mask[:BATCH],
        "labels": split.labels[:BATCH],
    }
    extra = (state.params,) if mu > 0.0 else ()
    assert lowered_name(eng.train_step, state, batch, *extra) == "jit_engine_train_step"
    assert lowered_name(eng.eval_step, state.params, batch, np.ones(BATCH, np.int32)) == "jit_engine_eval_step"
    fed_t = FederatedTrainer(cfg, pad_id=tok.pad_id, mesh=make_mesh(2, 1, devices=eight_devices[:2]))
    fstate = fed_t.init_state()
    stacked = stack_clients([c.train for c in clients])
    fbatch = fed_t._feed(next(iter(fed_t._epoch_iterator(stacked, BATCH, 0))))
    fextra = (fstate.params,) if mu > 0.0 else ()
    assert lowered_name(fed_t.train_step, fstate, fbatch, *fextra) == "jit_fed_train_step"
    cstate = (
        jax.tree.map(lambda x: x[0], fstate.params), jax.tree.map(lambda x: x[0], fstate.opt_state),
        fstate.step, fstate.rngs[0],
    )
    cb = {k: v[0] for k, v in fbatch.items()}
    cextra = (cstate[0],) if mu > 0.0 else ()
    assert lowered_name(fed_t._build_packed_step(), cstate, cb, *cextra) == "jit_fed_packed_step"


# ------------------------------------------------- the JSONL plane is as it was
def test_cli_federated_jsonl_unchanged_and_profile_dir_holds_annotations(tmp_path, eight_devices):
    """``fedtpu federated --trace-jsonl ... --profile-dir ...``: the events
    JSONL holds the spans and attributes it held before this plane existed,
    and the profile an operator opens holds the annotations."""
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli import (
        main,
    )
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import (
        SPAN_NAMES,
        load_spans,
    )

    spans_jsonl, prof = tmp_path / "spans.jsonl", tmp_path / "prof"
    rc = main([
        "federated", "--synthetic", "400", "--num-clients", "2", "--rounds", "1", "--epochs", "1",
        "--output-dir", str(tmp_path / "out"), "--trace-jsonl", str(spans_jsonl),
        "--profile-dir", str(prof),
    ])
    assert rc == 0
    spans = load_spans([str(spans_jsonl)])
    # xla-compile spans only where this process had not compiled the
    # programs already (the step memo is process-wide).
    assert {"client-local", "agg"} <= {s["span"] for s in spans} <= {"client-local", "agg", "xla-compile"}
    assert {s["span"] for s in spans} <= set(SPAN_NAMES)
    base = {"schema", "run_id", "proc", "span", "ts", "dur_s"}
    for s in spans:
        assert s["proc"] == "fed"
        if s["span"] == "xla-compile":
            assert set(s) - {"recompile"} == base | {"site", "signature"}
        else:
            assert set(s) == base | {"round", "path", "clients"}
            assert s["path"] == "fed2" and s["clients"] == 2 and s["round"] == 0
    # The CLI stacks ragged, so its launches are the ragged site's.
    names = {s[0] for s in host_annotations(prof)}
    assert {"fit", "fit/next_batch", "dispatch/fed.ragged_step", "fit/loss_read", "eval", "eval/read", "agg"} <= names


# -------------------------------------------------- the yardstick's new part
def test_benchmark_selftest_program_spans():
    """``benchmark/selftest/program_spans.py`` checks the three readers of
    this plane (a CPU process of its own: it records a trace)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="0")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "selftest", "program_spans.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "checks passed" in out.stdout
