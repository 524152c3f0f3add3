"""Tier-1 hook for the benchmark's model-family seam (PR 27 could not add a
file under ``tests/``): the cases of ``benchmark/selftest/test_families.py``
run here as they are, plus cases for the second, the third and the fourth
family the benchmark now has (``kimi_linear``, ``laguna``, ``qwen3_next``)
through the same seam, and their cells' CPU rehearsals.

Two of the selftest's cases quote a table of the BERT configurations only
(``QUOTED``; ``family == "bert_encoder"`` for every configuration): they are
taken over here for the configurations the table knows, and the second is
asked of every configuration's own family.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.selftest import test_families as _cases
from benchmark.selftest.test_families import *  # noqa: F401,F403  (the cases and their fixture)

from benchmark import families, harness
from benchmark.families import kimi_linear, laguna, qwen3_next

ROOT = _cases.ROOT
KIMI = "kimi-linear-48b-a3b-ep32"
CELL = "kimilinear-window-fit-l4k"
LAGUNA = "laguna-xs2-ep8"
LAGUNA_CELL = "laguna-window-fit-l8k"
QWEN = "qwen3-next-80b-a3b-ep16"
QWEN_CELL = "qwen3next-window-fit-l16k"


@pytest.mark.parametrize("name", [n for n in _cases.CONFIGS if n in _cases.QUOTED])
def test_counts_are_the_quoted(name):
    _cases.test_counts_are_the_quoted(name)


def test_every_configuration_names_a_family_that_is_there():
    assert KIMI in _cases.CONFIGS and LAGUNA in _cases.CONFIGS and QWEN in _cases.CONFIGS
    for name in _cases.CONFIGS:
        conf = harness.load_json("configs", f"{name}.json")
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "families", f"{conf['family']}.py"))
        assert families.load(conf).__name__.endswith(conf["family"])


# ------------------------------------------------ the second family's cases
def test_kimi_family_loads_and_counts_what_the_program_builds():
    import jax

    conf = harness.load_json("configs", f"{KIMI}.json")
    family, model = families.load(conf), conf["model"]
    assert family is kimi_linear
    cfg = family.model_config(model)
    built = jax.eval_shape(lambda: family.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(built))
    assert n == family.param_count(model) == conf["parameters"] == 555_253_122
    assert set(built) == {"encoder", "classifier"}
    assert family.train_step_bytes(model, steps=8) == 8 * 32.0 * n
    # every width as published; the cuts are the three the file lists
    src = conf["source_config"]
    assert (cfg.dim, cfg.hidden_dim, cfg.expert_dim) == (src["hidden_size"], src["intermediate_size"], src["moe_intermediate_size"])
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel) == (32, 128, 4)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.routed_scale) == (256, 8, 2.446)
    assert (cfg.n_layers, cfg.experts_held, cfg.vocab_size) == (5, 8, 20480)
    assert all(conf[k] == src[k] for k in src if k not in conf["reduced"])
    # the yardstick counts the chunk the program runs
    assert family.KDA_CHUNK == harness.pkg("ops.kda").CHUNK


def test_kimi_flops_and_bytes_rise_with_the_slots_really_routed():
    model = harness.load_json("configs", f"{KIMI}.json")["model"]
    fam = kimi_linear
    base = fam.train_step_flops(model, 32, steps=8, tokens=32 * 4096, routed_slots_here=0)
    more = fam.train_step_flops(model, 32, steps=8, tokens=32 * 4096, routed_slots_here=262144)
    assert 0 < base < more and more - base == 3 * 262144 * 6 * 2304 * 1024
    mean = fam.train_step_flops(model, 32)
    assert base < mean < more  # without the counter: the mean load (0.25 slots a token a layer)
    assert 1.85e9 < mean / (32 * 4096) < 1.95e9  # about 1.9 GFLOP a token to train
    f0, b0 = fam.scope_work(model, "moe/experts", tokens=131072.0, steps=8, routed_slots_here=1000)
    f1, b1 = fam.scope_work(model, "moe/experts", tokens=131072.0, steps=8, routed_slots_here=2000)
    assert 0 < f0 < f1 and 0 < b0 < b1
    f, b = fam.scope_work(model, "kda/chunks", tokens=131072.0)
    assert f > 0 and b > 0 and fam.scope_work(model, "mla", tokens=1.0) is None


def test_kimi_tiny_is_the_tiny_preset():
    model = harness.load_json("configs", f"{KIMI}.json")["model"]
    tiny = kimi_linear.tiny(model)
    cfg = kimi_linear.model_config(tiny)
    assert cfg.dim == 32 and cfg.remat is True and cfg.routed_scale == 2.446
    assert {cfg.mixer(i) for i in range(cfg.n_layers)} == {"kda", "mla"}


def _family_context(monkeypatch, family, config, name, **overrides):
    """A context whose family is ``family`` with ``overrides`` in place, under
    the name ``name`` beside it, with the weights and 8 tokenised flows."""
    mod = types.ModuleType(f"benchmark.families.{name}")
    mod.__dict__.update({k: v for k, v in vars(family).items() if not k.startswith("__")})
    mod.__dict__.update(overrides)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    conf = {**harness.load_json("configs", f"{config}.json"), "family": name}
    ctx = _cases.context(conf)
    tok = harness.pkg("data").default_tokenizer()
    _, split = harness.tokenised_flows(ctx, 8, ctx.seed, tok)
    params = harness.init_params_on_device(ctx.family, ctx.model_config(), ctx.seed, "threefry2x32")
    return ctx, params, split


def _kimi_context(monkeypatch, name, **overrides):
    return _family_context(monkeypatch, kimi_linear, KIMI, name, **overrides)


def test_kimi_program_agrees_with_its_reference_through_check_model(monkeypatch):
    ctx, params, split = _kimi_context(monkeypatch, "standin_k")
    harness.check_model(ctx, params, split, what="kimi", key="k", bind=True, n=4)
    assert not ctx.problems, ctx.problems
    assert ctx.compared["k.hidden_rel"][0] < 1e-4
    assert ctx.compared["k.hidden_rel"][1] == kimi_linear.TOLERANCES["hidden_rel"]


@pytest.mark.parametrize("fault", _cases.FAULTS)
def test_a_planted_fault_in_the_kimi_program_is_not_correct(monkeypatch, fault):
    alter, says = _cases.FAULTS[fault]

    def program(model_cfg):
        forward = kimi_linear.program(model_cfg)
        return lambda p, i, a: alter(*forward(p, i, a))

    ctx, params, split = _kimi_context(monkeypatch, "standin_f", program=program)
    harness.check_model(ctx, params, split, what="faulty", key="k", bind=True, n=4)
    assert any(says in p for p in ctx.problems), (ctx.problems, ctx.compared)


def test_the_timed_step_is_judged_against_a_reference_step_and_planted_faults_are_not_correct(monkeypatch):
    """The new driver's comparison of the timed step, at the tiny preset: one
    launch of ``engine.train_step`` gives its loss, its gradient (Adam's
    first moment) and the parameters' change, and ``judge_step`` holds each
    to the family's limit against the reference's loss, gradient and first
    Adam step, the reference computing under the step's own choice of experts.
    A state left unchanged, a step on half the batch, a fault in ONE small
    leaf (which moves the whole tree's figure almost nothing) and a router
    that chooses other experts each come out not correct."""
    import copy

    import jax

    from benchmark.drivers import window_fit

    ctx, _, split = _kimi_context(monkeypatch, "standin_s")
    cfg = ctx.model_config()
    config = harness.pkg("config")
    train_cfg = config.TrainConfig(learning_rate=2e-5, seed=ctx.seed, log_every=0)
    trainer = harness.pkg("train.engine").Trainer(cfg, train_cfg, pad_id=0)
    fresh = lambda: harness.init_params_on_device(ctx.family, cfg, ctx.seed, train_cfg.prng_impl)  # noqa: E731
    batch = {
        "input_ids": split.input_ids[:4], "attention_mask": split.attention_mask[:4],
        "labels": np.array([0, 1, 1, 0], np.int32),
    }
    got = window_fit.timed_step(ctx, trainer, fresh(), batch)
    want = window_fit.reference_step(ctx, fresh(), batch, train_cfg, got["routes"])
    assert got["overflow"] == 0 and set(got["grads"]) == set(want["grads"]) == {"encoder", "classifier"}
    mask = batch["attention_mask"]
    out = window_fit.judge_step(ctx, got, want, mask, what="sound")
    assert not ctx.problems, (ctx.problems, ctx.compared)
    assert {"step.loss_abs", "step.grad_rel", "step.grad_leaf_rel", "step.update_rel", "step.flip_share"} <= set(ctx.compared)
    assert out["flipped"] == 0 and out["slots"] == int(mask.sum()) * cfg.experts_per_token * len(got["routes"])
    assert out["grad_rel_whole"] < 1e-3 and out["update_rel"] < 0.2  # fp32 compute: rounding, and a few signs of tiny gradients
    # the parameters really moved by the learning rate, as the reference Adam step says
    moved = np.abs(got["update"]["classifier"]["kernel"])
    assert 0.9 * 2e-5 < float(np.median(moved)) < 1.1 * 2e-5

    def judged(planted, says):
        ctx.problems.clear()
        window_fit.judge_step(ctx, planted, want, mask, what="planted")
        assert any(says in p for p in ctx.problems), (says, ctx.problems, ctx.compared)

    judged({**got, "update": jax.tree.map(np.zeros_like, got["update"])}, "parameters' change")
    assert ctx.compared["step.update_rel"][0] == 1.0  # what a state left unchanged reads
    half = {k: np.concatenate([v[:2], v[:2]]) for k, v in batch.items()}
    judged(window_fit.timed_step(ctx, trainer, fresh(), half), "gradient differs")
    one_leaf = copy.deepcopy(got)
    one_leaf["grads"]["encoder"]["layer_0"]["kda"]["dt_bias"] *= 3.0
    judged(one_leaf, "['dt_bias'] differs")
    assert ctx.compared["step.grad_rel"][0] < ctx.compared["step.grad_rel"][1]  # the whole tree's figure still passes
    # a router that chooses other experts: a tenth of the slots moved to the next expert
    moved = copy.deepcopy(got)
    for idx in moved["routes"]:
        idx[:, ::3, 0] = (idx[:, ::3, 0] + 1) % cfg.n_experts
    judged(moved, "router chose other experts")


def test_a_cache_too_small_for_the_cell_is_left_alone():
    """Under a size cap that cannot hold the cell's programs the driver
    compiles outside the persistent cache (the chip machine caps it at 192
    MiB, and the cell's writes emptied it for the BERT cells); an uncapped or
    a large cache is used as ever, and a rehearsal changes nothing."""
    import jax

    from benchmark.drivers import window_fit

    ctx = _cases.context(harness.load_json("configs", f"{KIMI}.json"), rehearsal=False)
    was = (jax.config.jax_enable_compilation_cache, jax.config.jax_compilation_cache_max_size)
    try:
        for cap, rehearsal, stays in ((-1, False, True), (2**30, False, True), (192 * 2**20, True, True), (192 * 2**20, False, False)):
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_max_size", cap)
            ctx.rehearsal = rehearsal
            window_fit.leave_a_small_cache_alone(ctx)
            assert jax.config.jax_enable_compilation_cache is stays, (cap, rehearsal)
        assert "capped at 192 MiB" in ctx.said[-1]
    finally:
        jax.config.update("jax_enable_compilation_cache", was[0])
        jax.config.update("jax_compilation_cache_max_size", was[1])


@pytest.mark.parametrize("cell", [CELL, LAGUNA_CELL, QWEN_CELL])
def test_the_new_cells_rehearsal_ends_in_a_result_line(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_ENABLE_COMPILATION_CACHE": "0"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", cell, "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True and result["correct"] is True, result
    assert set(result["metrics"]) == {"train_samples_per_s", "round_s", "setup_s"}
    assert {"routed_overflow", "step.loss_abs", "step.grad_rel", "window_compiles"} <= set(result["compared"])


def test_scope_time_is_the_union_of_the_events_under_the_scope():
    """reduce/scope_ops.py: an event finds its path by its program and its
    instruction name in the compiled text; a while covers its body."""
    from benchmark.reduce import scope_ops

    text = "\n".join([
        "HloModule jit_engine_train_step, entry_computation_layout={()->()}",
        '  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(engine_train_step)/encoder/layer_1/kda/kda/chunks/while"}',
        '  %fusion.2 = f32[4] fusion(%x), kind=kLoop, metadata={op_name="jit(engine_train_step)/encoder/layer_1/kda/kda/chunks/while/body/mul"}',
        '  ROOT %dot.3 = f32[4] dot(%x, %y), metadata={op_name="jit(engine_train_step)/encoder/layer_1/moe/moe/experts/dot_general"}',
        "  %copy.4 = f32[4] copy(%x)",
    ])
    assert scope_ops.paths_by_program([text])["jit_engine_train_step"]["dot.3"].endswith("moe/experts/dot_general")
    ctx = _cases.context(harness.load_json("configs", f"{KIMI}.json"), rehearsal=False)
    ctx.trace_path = "unused"
    names = ["%while.1 = (s32[]) while(...)", "%fusion.2 = f32[4] fusion(...)", "%dot.3 = f32[4] dot(...)", "%copy.4 = f32[4] copy(%x)"]
    ops = (names, np.array([10.0, 20.0, 200.0, 300.0]), np.array([100.0, 30.0, 50.0, 10.0]))
    modules = (["jit_engine_train_step(123)"], np.array([0.0]), np.array([1000.0]))
    ctx.rec.data.update(
        hlo_texts=[text],
        xplane={"window": (0.0, 1000.0), "chips": [0], "busy_s": 190e-9, "trace": {"chips": {0: {"ops": ops, "modules": modules}}}},
    )
    table = scope_ops.of(ctx)
    assert scope_ops.time_under(table, "kda") == 100.0  # the body's 30 ns lie inside the while's 100
    assert scope_ops.time_under(table, "kda/chunks") == 100.0
    assert scope_ops.time_under(table, "moe/experts") == 50.0 and scope_ops.time_under(table, "mla") == 0.0
    from benchmark.readers import xplane_scope_share

    assert xplane_scope_share.read(ctx, scope="moe") == pytest.approx(100.0 * 50.0 / 190.0)
    # without the programs' texts (the parent's program, another driver) there is nothing to read
    ctx.rec.data.pop("scope_ops"), ctx.rec.data.pop("hlo_texts")
    assert xplane_scope_share.read(ctx, scope="moe") is None


# ------------------------------------------------- the third family's cases
def test_laguna_family_loads_and_counts_what_the_program_builds():
    import jax

    conf = harness.load_json("configs", f"{LAGUNA}.json")
    family, model = families.load(conf), conf["model"]
    assert family is laguna
    cfg = family.model_config(model)
    built = jax.eval_shape(lambda: family.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(built))
    assert n == family.param_count(model) == conf["parameters"] == 665_937_922
    assert set(built) == {"encoder", "classifier"}
    assert family.train_step_bytes(model, steps=8) == 8 * 32.0 * n
    # every width as published; the cuts are the six the file lists, and the manifest's
    src = conf["source_config"]
    assert (cfg.dim, cfg.hidden_dim, cfg.expert_dim, cfg.shared_dim) == (
        src["hidden_size"], src["intermediate_size"], src["moe_intermediate_size"], src["shared_expert_intermediate_size"],
    )
    assert (cfg.n_kv_heads, cfg.head_dim, cfg.sliding_window) == (src["num_key_value_heads"], src["head_dim"], src["sliding_window"])
    assert (cfg.n_experts, cfg.experts_per_token, cfg.routed_scale) == (256, src["num_experts_per_tok"], src["moe_routed_scaling_factor"])
    full, sliding = src["rope_parameters"]["full_attention"], src["rope_parameters"]["sliding_attention"]
    assert (cfg.full_rope_theta, cfg.full_rotary_share, cfg.full_rope_factor, cfg.full_rope_original_len) == (
        full["rope_theta"], full["partial_rotary_factor"], full["factor"], full["original_max_position_embeddings"],
    )
    assert (cfg.full_rope_beta_fast, cfg.full_rope_beta_slow, cfg.full_rope_attention_factor) == (
        full["beta_fast"], full["beta_slow"], full["attention_factor"],
    )
    assert (cfg.sliding_rope_theta, cfg.sliding_rotary_share) == (sliding["rope_theta"], sliding["partial_rotary_factor"])
    kinds = {"full_attention": "full", "sliding_attention": "sliding"}
    assert cfg.layer_types == tuple(kinds[k] for k in conf["layer_types"]) == tuple(kinds[k] for k in src["layer_types"][:5])
    assert cfg.heads_per_layer == tuple(conf["num_attention_heads_per_layer"]) == tuple(src["num_attention_heads_per_layer"][:5])
    assert cfg.ffn_types == tuple(conf["mlp_layer_types"]) == tuple(src["mlp_layer_types"][:5])
    assert (cfg.n_layers, cfg.experts_held, cfg.vocab_size) == (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (5, 32, 12544)
    assert all(conf[k] == src[k] for k in src if k not in conf["reduced"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == LAGUNA)
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    # the program's own preset is this configuration
    assert harness.pkg("models").model_preset("laguna-xs2-ep8") == cfg


def test_laguna_counts_the_band_and_the_slots_really_routed():
    model = harness.load_json("configs", f"{LAGUNA}.json")["model"]
    fam = laguna
    L = 8192
    assert fam.score_keys(model, "full", L) == L * (L + 1) / 2
    assert fam.score_keys(model, "sliding", L) == sum(min(i + 1, 512) for i in range(L))
    assert fam.score_keys(model, "sliding", 300) == fam.score_keys(model, "full", 300)  # a row inside its window
    mean = fam.train_step_flops(model, 2)
    assert 2.2e9 < mean / (2 * L) < 2.3e9  # about 2.25 GFLOP a token to train
    base = fam.train_step_flops(model, 16, steps=8, tokens=16 * L, routed_slots_here=0)
    more = fam.train_step_flops(model, 16, steps=8, tokens=16 * L, routed_slots_here=262144)
    assert 0 < base < more and more - base == 3 * 262144 * 6 * 2048 * 512
    f0, b0 = fam.scope_work(model, "moe/experts", tokens=16.0 * L, steps=8, routed_slots_here=1000)
    f1, b1 = fam.scope_work(model, "moe/experts", tokens=16.0 * L, steps=8, routed_slots_here=2000)
    assert 0 < f0 < f1 and 0 < b0 < b1
    # a sliding layer's scores are counted over the band: a program that scores every key reads low
    fw, bw = fam.scope_work(model, "attn/window/scores", tokens=16.0 * L, rows=16)
    ff, bf = fam.scope_work(model, "attn/full/scores", tokens=16.0 * L, rows=16)
    assert fw == 3 * 16 * fam.score_keys(model, "sliding", L) * (3 * 64) * 4 * 128
    assert ff == 3 * 16 * fam.score_keys(model, "full", L) * (2 * 48) * 4 * 128
    per_layer = (fw / 3) / (ff / 2)
    assert 0.15 < per_layer < 0.17  # 496 of 4,096 keys at 64 heads against 48
    assert bw > 0 and bf > 0 and fam.scope_work(model, "attn/window", tokens=1.0) is None
    assert fam.scope_work(model, "attn/full/scores", tokens=16.0 * L) == (ff, bf)  # without the rows: max_len


def test_laguna_tiny_is_the_tiny_preset():
    model = harness.load_json("configs", f"{LAGUNA}.json")["model"]
    cfg = laguna.model_config(laguna.tiny(model))
    assert cfg.dim == 32 and cfg.remat is True and cfg.routed_scale == 2.5 and cfg.rms_norm_eps == 1e-6
    assert set(cfg.layer_types) == {"full", "sliding"} and set(cfg.ffn_types) == {"dense", "sparse"}
    assert cfg.sliding_window < cfg.max_len and len(set(cfg.heads_per_layer)) == 2


def _laguna_context(monkeypatch, name, **overrides):
    return _family_context(monkeypatch, laguna, LAGUNA, name, **overrides)


def test_laguna_program_agrees_with_its_reference_through_check_model(monkeypatch):
    ctx, params, split = _laguna_context(monkeypatch, "standin_l")
    harness.check_model(ctx, params, split, what="laguna", key="l", bind=True, n=4)
    assert not ctx.problems, ctx.problems
    assert ctx.compared["l.hidden_rel"][0] < 1e-4
    assert ctx.compared["l.hidden_rel"][1] == laguna.TOLERANCES["hidden_rel"]


@pytest.mark.parametrize("fault", _cases.FAULTS)
def test_a_planted_fault_in_the_laguna_program_is_not_correct(monkeypatch, fault):
    alter, says = _cases.FAULTS[fault]

    def program(model_cfg):
        forward = laguna.program(model_cfg)
        return lambda p, i, a: alter(*forward(p, i, a))

    ctx, params, split = _laguna_context(monkeypatch, "standin_g", program=program)
    harness.check_model(ctx, params, split, what="faulty", key="l", bind=True, n=4)
    assert any(says in p for p in ctx.problems), (ctx.problems, ctx.compared)


@pytest.mark.parametrize("fault", ["none", "mask-only", "no-rotation", "wrong-group"])
def test_a_fault_in_what_laguna_adds_is_not_correct(monkeypatch, fault):
    """Three faults in the mechanisms this family brought, each planted in
    the program's own forward at the tiny preset: a sliding layer that
    attends to its whole past, positions left unrotated, and query heads
    reading the wrong key head. Each leaves shapes and finiteness alone and
    fails the comparison with the reference, which the program as it is
    passes on the same weights."""
    ops = harness.pkg("ops.causal_attention")
    model_mod = harness.pkg("models.laguna")
    if fault == "mask-only":
        real = ops.causal_attention
        monkeypatch.setattr(model_mod, "causal_attention", lambda q, k, v, m, window=None: real(q, k, v, m, None))
    elif fault == "no-rotation":
        monkeypatch.setattr(model_mod, "apply_rope", lambda x, cos, sin: x)
    elif fault == "wrong-group":
        real = ops.causal_attention
        monkeypatch.setattr(
            model_mod, "causal_attention", lambda q, k, v, m, window=None: real(q, k[:, ::-1], v[:, ::-1], m, window)
        )
    laguna.program.cache_clear()
    try:
        ctx, params, split = _laguna_context(monkeypatch, f"standin_{fault[:2]}")
        # At 32 dimensions and weights of 0.02 every score is near 0 and a row
        # attends evenly wherever it may: the queries and keys are made as
        # large as the published widths make them, so that whom a token
        # attends to matters.
        import jax

        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 30.0 if any(getattr(k, "key", "") in ("q_proj", "k_proj") for k in path) else x, params
        )
        harness.check_model(ctx, params, split, what=fault, key="l", bind=True, n=4)
        if fault == "none":
            assert not ctx.problems and ctx.compared["l.hidden_rel"][0] < 1e-4, (ctx.problems, ctx.compared)
        else:
            assert any("hidden states differ" in p for p in ctx.problems), (ctx.problems, ctx.compared)
    finally:
        laguna.program.cache_clear()


# ------------------------------------------------ the fourth family's cases
def test_qwen3_next_family_loads_and_counts_what_the_program_builds():
    import jax

    conf = harness.load_json("configs", f"{QWEN}.json")
    family, model = families.load(conf), conf["model"]
    assert family is qwen3_next
    cfg = family.model_config(model)
    built = jax.eval_shape(lambda: family.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(built))
    assert n == family.param_count(model) == conf["parameters"] == 586_775_618
    # the issue's sum: three linear layers, the attention layer, the table, the final norm, the head
    assert n == 3 * 138_582_208 + 132_127_232 + 38_895_616 + 2_048 + 4_098
    assert set(built) == {"encoder", "classifier"}
    assert family.train_step_bytes(model, steps=4) == 4 * 32.0 * n
    # every width as published; the cuts are the three the file lists, and the manifest's
    src = conf["source_config"]
    assert (cfg.dim, cfg.expert_dim, cfg.shared_dim) == (src["hidden_size"], src["moe_intermediate_size"], src["shared_expert_intermediate_size"])
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (src["num_attention_heads"], src["num_key_value_heads"], src["head_dim"])
    assert (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.conv_kernel) == (
        src["linear_num_key_heads"], src["linear_num_value_heads"], src["linear_key_head_dim"], src["linear_value_head_dim"],
        src["linear_conv_kernel_dim"],
    )
    assert (cfg.rotary_share, cfg.rope_theta, cfg.rms_norm_eps) == (src["partial_rotary_factor"], src["rope_theta"], src["rms_norm_eps"])
    assert (cfg.n_experts, cfg.experts_per_token, cfg.full_attention_interval) == (512, src["num_experts_per_tok"], src["full_attention_interval"])
    assert (cfg.n_layers, cfg.experts_held, cfg.vocab_size) == (conf["num_hidden_layers"], conf["num_experts"], conf["vocab_size"]) == (4, 32, 18992)
    assert [cfg.mixer(i) for i in range(4)] == ["linear", "linear", "linear", "full"]
    assert conf["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"] and set(conf["reduced_how"]) == set(conf["reduced"])
    assert all(conf[k] == src[k] for k in src if k not in conf["reduced"])
    assert conf["published"] == {k: src[k] for k in conf["reduced"]}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == QWEN)
    assert entry["reduced"] == conf["reduced"] and entry["source"] == conf["source"]
    # the program's own preset is this configuration
    assert harness.pkg("models").model_preset("qwen3-next-ep16") == cfg
    assert family.KDA_CHUNK == harness.pkg("ops.kda").CHUNK


def test_qwen3_next_counts_the_published_mathematics_and_the_slots_really_routed():
    model = harness.load_json("configs", f"{QWEN}.json")["model"]
    fam = qwen3_next
    L = 16384
    mean = fam.train_step_flops(model, 1)
    assert 1.35e9 < mean / L < 1.37e9  # about 1.36 GFLOP a token to train (22.3 TFLOP a step)
    base = fam.train_step_flops(model, 4, steps=4, tokens=4 * L, routed_slots_here=0)
    more = fam.train_step_flops(model, 4, steps=4, tokens=4 * L, routed_slots_here=163840)
    assert 0 < base < more and more - base == 3 * 163840 * 6 * 2048 * 512
    assert fam.mean_slots(model, 4 * L) == 163840  # 10,240 rows a layer and step
    f0, b0 = fam.scope_work(model, "moe/experts", tokens=4.0 * L, steps=4, routed_slots_here=1000)
    f1, b1 = fam.scope_work(model, "moe/experts", tokens=4.0 * L, steps=4, routed_slots_here=2000)
    assert 0 < f0 < f1 and 0 < b0 < b1
    # the delta rule is the scalar-gate rule's: the channel-wise rule's products, a head's ONE decay in the bytes
    f, b = fam.scope_work(model, "gdn/chunks", tokens=4.0 * L)
    assert f == 3 * 3 * 4 * L * 32 * (64 * 5 * 128 + 6 * 128 * 128)
    kimi = harness.load_json("configs", f"{KIMI}.json")["model"]
    k_f, k_b = kimi_linear.scope_work(kimi, "kda/chunks", tokens=4.0 * L)  # the same heads and widths, in 4 layers
    assert f / 3 == k_f / 4
    assert b == 3 * 3 * 4 * L * (2 * 16 * 128 * 4 + 32 * 128 * 2 + 2 * 32 * 4 + 32 * 128 * 4)
    assert b / 3 < 0.6 * k_b / 4
    # the attention: query i against i + 1 keys, whatever blocks a program rounds them to
    fs, bs = fam.scope_work(model, "attn/gated/scores", tokens=4.0 * L, rows=4)
    assert fs == 3 * 4 * (L * (L + 1) / 2) * 16 * 4 * 256 and bs == 3 * 4 * L * (2 * 16 + 2 * 2) * 256 * 2
    assert fam.scope_work(model, "attn/gated/scores", tokens=4.0 * L) == (fs, bs)  # without the rows: max_len
    assert fam.scope_work(model, "attn/gated", tokens=1.0) is None and fam.scope_work(model, "kda/chunks", tokens=1.0) is None


def test_qwen3_next_tiny_is_the_tiny_preset():
    model = harness.load_json("configs", f"{QWEN}.json")["model"]
    cfg = qwen3_next.model_config(qwen3_next.tiny(model))
    assert cfg.dim == 32 and cfg.remat is True and cfg.rms_norm_eps == 1e-6 and cfg.routed_scale == 1.0
    assert {cfg.mixer(i) for i in range(cfg.n_layers)} == {"linear", "full"}
    assert cfg.linear_value_heads == 2 * cfg.linear_key_heads and cfg.n_heads > cfg.n_kv_heads


def _qwen_context(monkeypatch, name, **overrides):
    return _family_context(monkeypatch, qwen3_next, QWEN, name, **overrides)


def test_qwen3_next_program_agrees_with_its_reference_through_check_model(monkeypatch):
    ctx, params, split = _qwen_context(monkeypatch, "standin_q")
    harness.check_model(ctx, params, split, what="qwen3_next", key="q", bind=True, n=4)
    assert not ctx.problems, ctx.problems
    assert ctx.compared["q.hidden_rel"][0] < 1e-4
    assert ctx.compared["q.hidden_rel"][1] == qwen3_next.TOLERANCES["hidden_rel"]


@pytest.mark.parametrize("fault", _cases.FAULTS)
def test_a_planted_fault_in_the_qwen3_next_program_is_not_correct(monkeypatch, fault):
    alter, says = _cases.FAULTS[fault]

    def program(model_cfg):
        forward = qwen3_next.program(model_cfg)
        return lambda p, i, a: alter(*forward(p, i, a))

    ctx, params, split = _qwen_context(monkeypatch, "standin_r", program=program)
    harness.check_model(ctx, params, split, what="faulty", key="q", bind=True, n=4)
    assert any(says in p for p in ctx.problems), (ctx.problems, ctx.compared)


@pytest.mark.parametrize("fault", ["none", "no-decay", "wrong-key-head", "no-rotation", "plain-norm", "sigmoid-router", "ungated-shared"])
def test_a_fault_in_what_qwen3_next_adds_is_not_correct(monkeypatch, fault):
    """Six faults in the mechanisms this family brought, each planted in the
    program's own forward at the tiny preset: a delta rule that never forgets,
    value heads reading the wrong key head, positions left unrotated, the
    other classes' norm (a weight that multiplies, read as 1 + w = 1), a
    sigmoid for the router's softmax, a shared expert without its gate. Each
    leaves shapes and finiteness alone and fails the comparison with the
    reference, which the program as it is passes on the same weights."""
    import jax
    import jax.numpy as jnp

    # The weights first, from the program as it is (a fault that drops a leaf leaves the tree whole).
    ctx, params, split = _qwen_context(monkeypatch, f"standin_{fault[:4]}")
    model_mod = harness.pkg("models.qwen3_next")
    blocks = harness.pkg("models.blocks")
    if fault == "no-decay":
        real = model_mod.kda_chunked
        monkeypatch.setattr(model_mod, "kda_chunked", lambda q, k, v, g, beta, **kw: real(q, k, v, jnp.zeros_like(g), beta, **kw))
    elif fault == "wrong-key-head":
        real = model_mod.kda_chunked
        monkeypatch.setattr(model_mod, "kda_chunked", lambda q, k, v, g, beta, **kw: real(q, k[:, ::-1], v, g, beta, **kw))
    elif fault == "no-rotation":
        monkeypatch.setattr(model_mod, "apply_rope", lambda x, cos, sin: x)
    elif fault == "plain-norm":
        real = blocks.rms
        monkeypatch.setattr(model_mod, "rms", lambda cfg, name, zero_centred=False: real(cfg, name, False))
    elif fault in ("sigmoid-router", "ungated-shared"):
        real = model_mod.SparseMoE
        change = {"score": "sigmoid"} if fault == "sigmoid-router" else {"shared_gate": False}
        monkeypatch.setattr(model_mod, "SparseMoE", lambda cfg, **kw: real(cfg, **{**kw, **change}))
    for cached in (qwen3_next.program, qwen3_next.routing):
        cached.cache_clear()
    try:
        # At 32 dimensions and weights of 0.02 every score is near 0, a row
        # attends evenly and every gate is a half: the queries, keys and gates
        # are made as large as the published widths make them, and the norms'
        # weights moved off 0, so that each mechanism matters.
        def widen(path, x):
            names = [getattr(k, "key", "") for k in path]
            if any(n in ("q_proj", "k_proj", "shared_gate", "router") for n in names):
                return x * 30.0
            if "experts_down" in names:  # the routed part as large beside the residual stream as 10 experts of 512 make it
                return x * 8.0
            if "ba_proj" in names:  # a decay as the published width gives it: the kernels' blocks hold a mean of 5 a token
                return x * 8.0
            if names[-1] == "scale" and "final_norm" not in names:
                return x + 0.5
            return x

        params = jax.tree_util.tree_map_with_path(widen, params)
        harness.check_model(ctx, params, split, what=fault, key="q", bind=True, n=4)
        if fault == "none":
            assert not ctx.problems and ctx.compared["q.hidden_rel"][0] < 1e-4, (ctx.problems, ctx.compared)
        else:
            assert any("hidden states differ" in p for p in ctx.problems), (ctx.problems, ctx.compared)
    finally:
        for cached in (qwen3_next.program, qwen3_next.routing):
            cached.cache_clear()
