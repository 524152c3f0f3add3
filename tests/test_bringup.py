"""The bring-up contracts (PR 21): where the compile cache lives, which
tiers stay jax-free, and that chip_smoke.py runs nothing without a TPU.

Almost everything here runs in child processes: the properties are about
a fresh interpreter (what is in ``sys.modules``, what the environment
holds). The one in-process ``main()`` call is the test of what such calls
leave behind for the children of this session."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def _run(code_or_argv, *, env=None, cwd=None, timeout=120):
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str)
        else [sys.executable, *code_or_argv]
    )
    base = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    base.update(env or {})
    return subprocess.run(
        argv, capture_output=True, text=True, env=base, cwd=cwd, timeout=timeout
    )


_PLACE = f"""
import json, os, sys
from {PKG}.utils.compile_cache import place_compile_cache
path = place_compile_cache()
print(json.dumps({{"path": path, "env": os.environ.get("{ENV_VAR}"),
                  "jax": "jax" in sys.modules}}))
"""


def test_compile_cache_env_var_wins_and_nothing_else_is_set(tmp_path):
    given = str(tmp_path / "given")
    out = _run(_PLACE, env={ENV_VAR: given})
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout)
    assert got == {"path": given, "env": given, "jax": False}
    assert not os.path.exists(given)  # placing is not creating


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    a, b = (json.loads(_run(_PLACE).stdout) for _ in range(2))
    want = os.path.join(REPO, ".jax_cache")
    # Identical across processes: no pid, temp dir or clock in it.
    assert a == b == {"path": want, "env": want, "jax": False}


@pytest.mark.parametrize("jax_first", [False, True])
def test_compile_cache_reaches_jax_in_either_import_order(jax_first):
    imports = ["import jax", f"from {PKG}.utils.compile_cache import place_compile_cache; p = place_compile_cache()"]
    if not jax_first:
        imports.reverse()
    code = "\n".join(imports + ["import jax", "print(jax.config.jax_compilation_cache_dir == p, p)"])
    out = _run(code)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["True", os.path.join(REPO, ".jax_cache")]


_COMPILE = """
import jax, jax.numpy as jnp
# Whatever this compiles would be kept if the cache were in use.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(jax.config.jax_compilation_cache_dir)
"""


def _files(root):
    return sorted(
        os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
    )


def test_test_lane_children_leave_the_checkout_cache_alone(tmp_path, monkeypatch, capsys):
    """An in-process main() publishes <checkout>/.jax_cache through the
    environment; a child started afterwards (a multihost worker, a CLI
    child) inherits it. conftest switched the cache off through the
    environment too, so that child compiles and writes nothing there."""
    import jax

    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli import main

    assert jax.config.jax_enable_compilation_cache is False  # this session
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert main(["export-config"]) == 0
    capsys.readouterr()
    checkout_cache = os.path.join(REPO, ".jax_cache")
    assert os.environ[ENV_VAR] == checkout_cache
    before = _files(checkout_cache)
    # The child as any test would start it: the session's environment.
    inherited = subprocess.Popen(
        [sys.executable, "-c", _COMPILE], env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # The same child with the switch back on writes an entry: the probe
    # can tell a cache in use from one that is off.
    control = _run(
        _COMPILE,
        env={"JAX_ENABLE_COMPILATION_CACHE": "1", ENV_VAR: str(tmp_path / "on")},
    )
    out, err = inherited.communicate(timeout=120)
    assert inherited.returncode == 0, err
    assert out.strip() == checkout_cache  # it was pointed there...
    assert _files(checkout_cache) == before  # ...and wrote nothing
    assert control.returncode == 0, control.stderr
    assert _files(tmp_path / "on"), "the control child cached nothing"


def test_aggregation_tiers_stay_jax_free_through_the_cli():
    """`serve` and `relay` go through the same main() that places the
    cache; neither may drag jax in. (`route` imports jax through
    router -> serving -> models at the parent commit already; it builds
    no array and takes no chip — ROADMAP records it.)"""
    code = f"""
import sys
from {PKG}.cli import build_parser
from {PKG}.utils.compile_cache import place_compile_cache
build_parser()
place_compile_cache()
from {PKG}.comm import AggregationServer
from {PKG}.comm.relay import RelayAggregator
print("jax" in sys.modules)
"""
    out = _run(code)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_chip_smoke_without_a_tpu_runs_nothing():
    t0 = time.monotonic()
    out = _run([os.path.join(REPO, "chip_smoke.py")], cwd=REPO)
    assert out.returncode == 2, (out.returncode, out.stderr[-400:])
    assert "not a TPU" in out.stderr
    assert '"ok"' not in out.stdout  # no result line
    assert "phase" not in out.stdout  # and no phase was started
    assert time.monotonic() - t0 < 60


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path), env={"PYTHONPATH": ""})
    assert out.returncode == 2
    assert "not inside a checkout" in out.stderr
    assert out.stdout == ""


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_every_phase(tmp_path):
    """The whole smoke at the tiny preset on 4 virtual CPU devices — the
    rehearsal to run before spending chip time on a change to it."""
    out = _run(
        [os.path.join(REPO, "chip_smoke.py"), "--rehearse-cpu"],
        cwd=REPO,
        # The warm phase must hit: the cache the test lane switches off
        # is on here, in a directory of the test's own.
        env={"JAX_ENABLE_COMPILATION_CACHE": "1", ENV_VAR: str(tmp_path / "cache")},
        timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True and verdict["rehearsal"] is True
    assert verdict["device"]["platform"] == "cpu"
    assert "withheld" in out.stdout  # a rehearsal prints no timing
