"""Test harness: fake an 8-device CPU mesh before JAX backend init.

The TPU analogue of a fake backend (SURVEY.md §4): multi-client federation is
validated on virtual CPU devices; the chip is reached only by chip_smoke.py
and benchmark/run.py. The tests force the CPU whatever the host offers.
"""

import os
import sys

# Tests that call the CLI's main() in-process publish <checkout>/.jax_cache
# through the environment, for this session and for every child started
# after them. The lane compiles from nothing, every run, and never reads
# an executable some earlier run left behind: the persistent cache is
# switched off in the environment, which jax reads at import and the
# children (multihost workers, CLI children) inherit.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture(scope="session")
def synthetic_csv(tmp_path_factory):
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.data import (
        write_synthetic_csv,
    )

    path = tmp_path_factory.mktemp("data") / "flows.csv"
    write_synthetic_csv(str(path), n_rows=1200, seed=7)
    return str(path)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


# ----------------------------------------------------- lock-order detector
# The runtime half of `fedtpu check`'s concurrency pass (analysis/
# lockorder.py): every threading.Lock/RLock the package creates during
# the session is wrapped, acquisition-order edges are collected per
# creation site, and a cycle (two code paths taking the same two lock
# sites in opposite orders — the ABBA deadlock class) FAILS the session.
# FEDTPU_LOCKORDER=0 disarms. Same-site nesting (e.g. per-client locks
# acquired in a pinned order) is reported, not failed.
_LOCKORDER = {"armed": False}


def pytest_configure(config):
    if os.environ.get("FEDTPU_LOCKORDER", "1").lower() in ("", "0", "false"):
        return
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.analysis import (
        lockorder,
    )

    lockorder.arm()
    _LOCKORDER["armed"] = True


def pytest_sessionfinish(session, exitstatus):
    if not _LOCKORDER["armed"]:
        return
    _LOCKORDER["armed"] = False
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.analysis import (
        lockorder,
    )

    report = lockorder.disarm()
    if report is None:
        return
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    out = tr.write_line if tr is not None else print
    out(report.render())
    if report.cycles:
        out(
            "lock-order cycles detected — failing the session "
            "(see analysis/lockorder.py; FEDTPU_LOCKORDER=0 disarms)"
        )
        session.exitstatus = 1
