"""The device plane's vocabulary (``obs/trace.py::SCOPES``) bound to the
program and to the benchmark's data files, on the CPU at the tiny presets.

Every per-layer metric that reads device time by a ``jax.named_scope`` is a
data file holding a path fragment; nothing else ties the fragment to the
line of the program that opens the scope. Here each window cell's
``engine.train_step`` is compiled at its family's tiny preset (with the
cell's own ``remat``) and (a) every such data file's fragment is looked for
in the compiled text of a cell that lists the metric, by
``scope_ops.time_under``'s own expression; (b) every scope this PR's issue
added is found in a forward, a recomputed and a backward instruction; (c)
the scopes the program's own files opened while the step was traced, and
every literal a ``jax.named_scope(`` of ``models/``, ``ops/`` and ``train/``
holds, are in the vocabulary; (d) the readers that read paths and not scopes
give the hand-computed answer on a made-up table; (e) the buffer rows the
expert layers count are the rungs their calls took (``ops/moe.py::expert_rungs``)
and reset with the slots; (f) a pass of an expert layer over its buffer is one
``conditional`` of a branch a rung, and no instruction outside one moves the
whole buffer.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

from benchmark import families, harness
from benchmark.readers import registry_ratio, xplane_path_found, xplane_path_share
from benchmark.reduce import scope_ops, xplane
from benchmark.selftest import test_families as _cases
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.config import TrainConfig
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data.pipeline import TokenizedSplit
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs import metrics as obs_metrics
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.trace import SCOPES
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.causal_attention import KERNEL_SCOPE
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.ops.moe import expert_rungs
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.train.engine import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, harness.PKG)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
WINDOW_CELLS = [w["name"] for w in MANIFEST["workloads"] if w["traffic"].startswith("window-fit")]
CELLS_OF = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"]}
SCOPE_READERS = ("xplane_scope_share", "scope_roofline")


def _specs():
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "layer_metrics", "*.json"))):
        with open(path) as f:
            yield json.load(f)


SCOPE_METRICS = {s["name"]: s["args"]["scope"] for s in _specs() if s["reader"] in SCOPE_READERS}
#: Scopes that only the TPU path opens: ``ops/causal_attention.py`` runs its
#: Pallas kernels where a row's length tiles, and a tiny preset's 64 tokens
#: take the XLA blocks. ``tests/test_tpu_compile.py`` holds the kernels.
TPU_ONLY = {KERNEL_SCOPE}
#: What the issue of PR 36 added, by the cell whose program has it.
NEW_SCOPES = {
    "kimilinear-window-fit-l4k": ("kda/proj", "kda/conv", "kda/prep", "kda/norm_gate"),
    "qwen3next-window-fit-l16k": ("gdn/proj", "gdn/conv", "gdn/prep", "gdn/norm_gate"),
    "laguna-window-fit-l8k": ("qkv", "rope", "out"),  # the scopes were there; their metrics are new
}
EVERYWHERE = ("moe/experts/dispatch", "moe/experts/grouped", "moe/experts/combine")
#: Scopes with no instruction in a block's recomputation. The buffer's pass is
#: differentiated by hand (``ops/moe.py::_buffer_pass``): its backward rule
#: computes the chosen rung's forward again itself, at the rung's length, so the
#: recomputation's own switch has no consumer and is gone from the program;
#: what a recomputation keeps under ``dispatch`` is the slots' index arithmetic,
#: which the rule's switch is chosen by.
NEVER_RECOMPUTED = {"moe/experts/grouped", "moe/experts/combine"}
B, L = 2, 64


def opens(name: str) -> bool:
    """Whether ``name``, as handed to ``jax.named_scope``, is of the
    vocabulary: an entry, or an entry's part after the module's own name
    (``window`` of ``attn/window``)."""
    return any(entry == name or entry.endswith("/" + name) for entry in SCOPES)


def under(scope: str):
    return re.compile(r"/" + re.escape(scope) + r"(?=/)")  # scope_ops.time_under's


@pytest.fixture(scope="module")
def programs():
    """Per window cell: the trainer at the family's tiny preset, the paths
    of its compiled ``engine.train_step`` and the names the program's own
    files handed to ``jax.named_scope`` while it was traced."""
    out = {}
    real = jax.named_scope
    for cell in WINDOW_CELLS:
        entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
        config = harness.load_json("configs", f"{entry['config']}.json")
        family = families.load(config)
        model_cfg = family.model_config(family.tiny(config["model"])).replace(max_len=L)
        assert model_cfg.remat
        trainer = Trainer(model_cfg, TrainConfig(log_every=0), pad_id=0)
        state = trainer.init_state(seed=0)
        batch = {
            "input_ids": np.ones((B, L), np.int32), "attention_mask": np.ones((B, L), np.int32),
            "labels": np.zeros((B,), np.int32),
        }
        opened = set()

        def recording(name, _opened=opened):
            if sys._getframe(1).f_code.co_filename.startswith(PKG):
                _opened.add(name)
            return real(name)

        jax.clear_caches()  # a cell traces every body itself: ``ops/moe.py``'s jitted rungs would come from the cell before
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "named_scope", recording)
            text = trainer.train_step.__wrapped__.lower(state, batch).compile().as_text()
        (paths,) = scope_ops.paths_by_program([text]).values()
        out[cell] = {"trainer": trainer, "state": state, "paths": sorted(set(paths.values())), "opened": opened, "text": text}
    return out


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_a_scope_metrics_fragment_is_a_path_of_a_cell_that_lists_it(programs, metric):
    scope = SCOPE_METRICS[metric]
    cells = CELLS_OF[metric]
    assert cells and set(cells) <= set(WINDOW_CELLS), cells
    found = [cell for cell in cells if any(under(scope).search(p) for p in programs[cell]["paths"])]
    if scope in TPU_ONLY:
        assert opens(scope) and not found  # the tiny presets' rows take the XLA blocks
    else:
        assert found == list(cells), f"{metric}: no op_name path of {set(cells) - set(found)} holds /{scope}/"


@pytest.mark.parametrize(
    "cell, scope",
    [(cell, scope) for cell in WINDOW_CELLS for scope in NEW_SCOPES[cell] + EVERYWHERE],
)
def test_a_new_scope_names_the_forward_the_recomputation_and_the_backward(programs, cell, scope):
    # (a jitted rung of ``ops/moe.py`` is lowered once for its call sites, and the combiner of a scatter-add
    # inside it carries the rung's own scopes alone: a path that does not start at the step is no pass's)
    paths = [p for p in programs[cell]["paths"] if under(scope).search(p) and p.startswith("jit(engine_train_step)/")]
    passes = {
        "forward": [p for p in paths if "transpose(jvp(" not in p and "/jvp(" in p],
        "recomputed": [p for p in paths if "transpose(jvp(" in p and "/rematted_computation/" in p],
        "backward": [p for p in paths if "transpose(jvp(" in p and "/rematted_computation/" not in p],
    }
    if scope in NEVER_RECOMPUTED:
        assert not passes.pop("recomputed")
    assert all(passes.values()), {k: len(v) for k, v in passes.items()}
    assert sum(len(v) for v in passes.values()) == len(paths)


@pytest.mark.parametrize("cell", WINDOW_CELLS)
def test_the_step_opens_no_scope_outside_the_vocabulary(programs, cell):
    opened = programs[cell]["opened"]
    assert "optimizer" in opened and "moe/experts" in opened and {"dispatch", "grouped", "combine"} <= opened
    assert not [name for name in opened if not opens(name)]
    assert any(p.startswith("jit(engine_train_step)/optimizer/") for p in programs[cell]["paths"])


def test_every_named_scope_of_the_program_is_of_the_vocabulary():
    """The source's own literals, so that a site no tiny step reaches
    (``train/fedsteps.py``, a TPU-only branch) is held too."""
    assert len(set(SCOPES)) == len(SCOPES) and opens(KERNEL_SCOPE)
    sites = 0
    for sub in ("models", "ops", "train"):
        for path in glob.glob(os.path.join(PKG, sub, "*.py")):
            with open(path) as f:
                source = f.read()
            for call in re.findall(r"jax\.named_scope\(([^\n]*)\):", source):
                names = re.findall(r'"([^"]+)"', re.sub(r'==\s*"[^"]*"', "", call))  # not a condition's literal
                assert names or call == "KERNEL_SCOPE", (path, call)
                assert all(opens(n) for n in names), (path, call)
                sites += 1
    assert sites >= 25
    with open(os.path.join(PKG, "train", "fedsteps.py")) as f:
        assert f.read().count('jax.named_scope("optimizer")') == 2


def _table_context(paths_and_times):
    """A context whose traced window holds one event per ``(path, start,
    duration)``, every one an instruction of ``jit_engine_train_step``; a
    path of None is an event the text does not name."""
    text = ["HloModule jit_engine_train_step, entry_computation_layout={()->()}"]
    names, start, dur = [], [], []
    for i, (path, t0, d) in enumerate(paths_and_times):
        meta = f', metadata={{op_name="{path}"}}' if path else ""
        text.append(f"  %op.{i} = f32[4] add(%x, %y){meta}")
        names.append(f"%op.{i} = f32[4] add(...)")
        start.append(t0)
        dur.append(d)
    ctx = _cases.context(harness.load_json("configs", "kimi-linear-48b-a3b-ep32.json"), rehearsal=False)
    ctx.trace_path = "unused"
    order = np.argsort(start)
    ops = ([names[i] for i in order], np.asarray(start, float)[order], np.asarray(dur, float)[order])
    modules = (["jit_engine_train_step(1)"], np.array([0.0]), np.array([10_000.0]))
    busy = xplane.union_ns(ops[1], ops[2], 0.0, 10_000.0)
    ctx.rec.data.update(
        hlo_texts=["\n".join(text)],
        xplane={"window": (0.0, 10_000.0), "chips": [0], "busy_s": busy * 1e-9, "trace": {"chips": {0: {"ops": ops, "modules": modules}}}},
    )
    return ctx, busy


def test_the_path_readers_give_the_hand_computed_shares():
    J, M = "jit(engine_train_step)", "KimiLinearClassifier"
    events = [
        (f"{J}/jvp({M})/encoder/layer_1/kda/kda/proj/q_proj/dot_general", 0.0, 100.0),  # forward, named
        (f"{J}/jvp({M})/encoder/layer_1/mixer_norm/mul", 100.0, 40.0),  # forward, no name
        (f"{J}/transpose(jvp({M}))/encoder/jvp({M})/encoder/checkpoint/rematted_computation/layer_1/kda/kda/proj/q_proj/dot_general", 200.0, 100.0),
        (f"{J}/transpose(jvp({M}))/encoder/jvp({M})/encoder/checkpoint/rematted_computation/layer_1/mixer_norm/mul", 300.0, 40.0),
        (f"{J}/transpose(jvp({M}))/encoder/jvp({M})/encoder/checkpoint/layer_1/kda/kda/chunks/bwd/while", 400.0, 300.0),
        (f"{J}/transpose(jvp({M}))/encoder/jvp({M})/encoder/checkpoint/layer_1/kda/kda/chunks/bwd/while/body/mul", 450.0, 100.0),  # inside the while
        (f"{J}/transpose(jvp({M}))/encoder/jvp({M})/encoder/checkpoint/layer_1/ffn_norm/mul", 700.0, 60.0),  # backward, no name
        (f"{J}/optimizer/mul", 800.0, 150.0),
        ("jit(engine_eval_step)/encoder/final_norm/mul", 1000.0, 30.0),  # another program's
        (None, 1100.0, 80.0),  # found no path
    ]
    ctx, busy = _table_context(events)
    assert busy == 100 + 40 + 100 + 40 + 300 + 60 + 150 + 30 + 80
    spec = {s["name"]: s for s in _specs()}
    read = lambda name: xplane_path_share.read(ctx, **spec[name]["args"])  # noqa: E731
    assert read("bwd_share") == pytest.approx(100.0 * (300 + 60) / busy)  # the while's body lies inside it
    assert read("step_unnamed_share") == pytest.approx(100.0 * (40 + 40 + 60) / busy)
    assert xplane_path_share.read(ctx, holds="/rematted_computation/") == pytest.approx(100.0 * 140 / busy)
    # every event's time counts once in the found share, nested or not
    total = sum(d for _, _, d in events)
    assert xplane_path_found.read(ctx) == pytest.approx(100.0 * (total - 80) / total)
    # without the programs' texts there is nothing to read, and nothing raises
    ctx.rec.data.pop("scope_ops"), ctx.rec.data.pop("hlo_texts")
    assert read("bwd_share") is None and read("step_unnamed_share") is None and xplane_path_found.read(ctx) is None


def test_the_buffers_fill_is_the_registrys_slots_over_its_rows(monkeypatch):
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(obs_metrics, "_DEFAULT", reg)
    ctx = _cases.context(harness.load_json("configs", "kimi-linear-48b-a3b-ep32.json"), rehearsal=False)
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", "moe_buffer_fill.json")) as f:
        args = json.load(f)["args"]
    assert registry_ratio.read(ctx, **args) is None  # a program that published nothing
    obs_metrics.publish_route(np.array([30, 10]), 0, 160, first_expert=8)
    obs_metrics.publish_route(np.array([5, 15]), 0, 80, first_expert=8)
    assert registry_ratio.read(ctx, **args) == pytest.approx(100.0 * 60 / 240)
    assert 'fedtpu_moe_buffer_rows_total 240' in reg.render()


@pytest.mark.parametrize("cell", WINDOW_CELLS)
def test_the_rows_counted_are_the_rungs_taken_and_reset_with_the_slots(programs, cell):
    trainer, state = programs[cell]["trainer"], programs[cell]["state"]
    cfg = trainer.model_cfg
    moe_layers = sum(1 for i in range(cfg.n_layers) if cfg.is_moe(i))
    rows = 4
    rng = np.random.default_rng(5)
    mask = (np.arange(L)[None, :] < np.array([64, 50, 37, 33])[:, None]).astype(np.int32)
    split = TokenizedSplit(rng.integers(1, cfg.vocab_size, size=(rows, L)).astype(np.int32) * mask, mask, np.array([0, 1, 0, 1], np.int32))
    assert set(state.route) == {"slots", "overflow", "rows"}
    trainer.last_route = None
    state, _ = trainer.fit(state, split, batch_size=B, epochs=2)
    calls = moe_layers * 2 * rows // B
    route = trainer.last_route
    rungs = expert_rungs(B * L, cfg.experts_per_token, cfg.n_experts, cfg.experts_held)
    low, top = rungs
    # every call moved one of the rungs, the shorter if it held the call's slots: the rows are a sum of ``calls`` rung lengths
    assert 0 < int(route["slots"].sum()) <= route["rows"] <= top * calls and route["overflow"] == 0
    assert route["rows"] in {a * low + (calls - a) * top for a in range(calls + 1)}
    assert route["rows"] < top * calls  # a quarter of the slots name a held expert: some call took the lower rung
    assert int(state.route["rows"]) == 0 and int(state.route["slots"].sum()) == 0  # read and started again


def _computations(text: str) -> dict[str, list[str]]:
    """A compiled program's text as ``{computation: its instructions' lines}``."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name and line.startswith("  "):
            out[name].append(line)
    return out


@pytest.mark.parametrize("cell", WINDOW_CELLS)
def test_a_pass_over_the_buffer_is_one_conditional_of_a_branch_a_rung(programs, cell):
    """In the compiled step every expert layer holds two ``conditional``s of
    a branch a rung (the forward pass and the backward rule; the
    recomputation's has no consumer), and the layer's every gather and scatter
    of rows of the width ``D`` is an instruction of a branch: outside the
    switch nothing moves the buffer's rows, at the capacity or at a rung."""
    cfg = programs[cell]["trainer"].model_cfg
    moe_layers = sum(1 for i in range(cfg.n_layers) if cfg.is_moe(i))
    rungs = expert_rungs(B * L, cfg.experts_per_token, cfg.n_experts, cfg.experts_held)
    comps = _computations(programs[cell]["text"])
    callee = r"(?:calls|to_apply|body|condition|branch_computations|true_computation|false_computation)=(\{[^}]*\}|%[\w.\-]+)"
    called = lambda line: re.findall(r"%([\w.\-]+)", " ".join(re.findall(callee, line)))  # noqa: E731
    # the expert layers' own (off the TPU the interpreted kernels hold conditionals too)
    switches = [line for lines in comps.values() for line in lines if re.search(r" conditional\(", line) and "/moe/experts/cond" in line]
    assert len(switches) == 2 * moe_layers
    inside, todo = set(), []
    for line in switches:
        branches = called(line)  # two branches are a true and a false computation
        assert len(branches) == len(rungs) == 2
        todo += branches
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo += [c for line in comps[name] for c in called(line)]
    wide = re.compile(r" = \w+\[(\d+),(?:1,)?%d\]\S* (gather|scatter)\(" % cfg.dim)  # rows of the layer's width
    moved = {rows: 0 for rows in rungs}
    for name, lines in comps.items():
        for line in lines:
            m = wide.search(line)
            if m and "/moe/experts/" in line:
                assert name in inside, line
                moved[int(m.group(1))] = moved.get(int(m.group(1)), 0) + (m.group(2) == "gather")
    # the rungs' gathers (the rows in; the cotangent's rows in the rule), and beside them only the scatter-adds per token
    assert all(moved[rows] >= 2 * moe_layers for rows in rungs) and set(moved) <= {*rungs, B * L + 1}, moved
