"""The `fedtpu check` static-analysis subsystem (analysis/): per-rule
fixture snippets (positive + pragma-suppressed), baseline semantics,
the seeded-mutation self-test (a temp copy of the real tree with one
invariant broken per mutation must exit nonzero), the repo
self-scan-clean contract, and the runtime lock-order detector."""

import argparse
import json
import os
import shutil
import textwrap
import threading

import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.analysis import (
    all_rules,
    run_check,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.analysis import (
    lockorder,
)
from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli.check import (
    cmd_check,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_NAME = (
    "detecting_cyber_attacks_with_distilled_large_language_models_in_"
    "distributed_networks_tpu"
)


# ------------------------------------------------------------ fixture trees
def _mini_tree(tmp_path, files: dict) -> str:
    """Write a throwaway package tree: {relpath: source} under
    tmp/pkgx/ with an __init__.py per directory."""
    root = tmp_path / "mini"
    for rel, src in files.items():
        path = root / "pkgx" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
        d = path.parent
        while d != root:
            init = d / "__init__.py"
            if not init.exists():
                init.write_text("")
            d = d.parent
    return str(root)


def _findings(root, rules):
    return run_check(root, rules=rules, baseline_path=None).new


# ------------------------------------------------------------- wire rules
WIRE_OK = """
    A_MAGIC = b"AAAA"
    B_MAGIC = b"BBBB"
    _X_DOMAIN = b"fedtpu-x-v1"
    _Y_DOMAIN = b"fedtpu-y-v1"

    def encode_a(x):
        return A_MAGIC + encode_b(x)

    def decode_a(x):
        return x[len(A_MAGIC):]

    def encode_b(x):
        return B_MAGIC

    def decode_b(x):
        return x[len(B_MAGIC):]
"""


def test_wire_domain_unique_flags_duplicate_and_unversioned(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": """
                A_MAGIC = b"AAAA"
                B_MAGIC = b"AAAA"
                _X_DOMAIN = b"fedtpu-x-v1"
                _Y_DOMAIN = b"fedtpu-y"
                LONG_MAGIC = b"TOOLONG"
            """
        },
    )
    found = _findings(root, ["wire-domain-unique"])
    messages = "\n".join(f.message for f in found)
    assert "B_MAGIC duplicates the byte value of A_MAGIC" in messages
    assert "-v<N>' version suffix" in messages and "_Y_DOMAIN" in messages
    assert "LONG_MAGIC is 7 bytes" in messages


def test_wire_domain_unique_spans_stream_domains_table(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": """
                _HDR_DOMAIN = b"fedtpu-hdr-v1"
                _STREAM_DOMAINS = {
                    "up": (_HDR_DOMAIN,),
                    "down": (b"fedtpu-hdr-v1",),
                }
                A_MAGIC = b"AAAA"
            """
        },
    )
    found = _findings(root, ["wire-domain-unique"])
    assert any(
        "duplicates the byte value of _HDR_DOMAIN" in f.message for f in found
    )


def test_wire_domain_clean_tree_passes(tmp_path):
    root = _mini_tree(tmp_path, {"comm/wire.py": WIRE_OK})
    assert _findings(root, ["wire-domain-unique"]) == []


def test_wire_meta_key_unique_flags_duplicate_empty_and_stray(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": """
                STREAM_META_KEY = "stream"
                REHOME_META_KEY = "stream"
                EMPTY_META_KEY = ""
            """,
            "comm/client.py": """
                LOCAL_META_KEY = "local"
            """,
        },
    )
    found = _findings(root, ["wire-meta-key-unique"])
    messages = "\n".join(f.message for f in found)
    assert (
        "REHOME_META_KEY duplicates the meta-key string of "
        "STREAM_META_KEY" in messages
    )
    assert "EMPTY_META_KEY must be a non-empty string" in messages
    assert "LOCAL_META_KEY declared outside the wire layer" in messages


def test_wire_meta_key_clean_tree_and_lost_anchor(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": """
                A_META_KEY = "a"
            """,
            "obs/trace.py": """
                TRACE_META_KEY = "trace"
            """,
        },
    )
    assert _findings(root, ["wire-meta-key-unique"]) == []
    bare = _mini_tree(
        tmp_path / "bare", {"comm/wire.py": "A_MAGIC = b'AAAA'\n"}
    )
    found = _findings(bare, ["wire-meta-key-unique"])
    assert any("lost its anchor" in f.message for f in found)


def test_wire_magic_coverage_flags_one_sided_and_adhoc(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": """
                A_MAGIC = b"AAAA"
                ORPHAN_MAGIC = b"ORPH"

                def encode_a(x):
                    return A_MAGIC

                def decode_a(x):
                    return x[len(A_MAGIC):]

                def encode_orphan():
                    return ORPHAN_MAGIC
            """,
            "comm/server.py": """
                from . import wire

                def dispatch(data):
                    if data[:4] == wire.A_MAGIC:
                        return wire.decode_a(data)
                    if data[:4] == b"ADHC":
                        return None
            """,
        },
    )
    found = _findings(root, ["wire-magic-coverage"])
    messages = "\n".join(f.message for f in found)
    assert "ORPHAN_MAGIC is referenced from 1 function scope" in messages
    assert "b'ADHC' outside the wire layer" in messages
    assert "A_MAGIC" not in messages


def test_wire_magic_dead_frame_type_flagged(tmp_path):
    # Encode+decode exist in wire.py but nothing outside ever dispatches.
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": """
                DEAD_MAGIC = b"DEAD"

                def encode_dead():
                    return DEAD_MAGIC

                def decode_dead(x):
                    return x[len(DEAD_MAGIC):]
            """,
            "comm/other.py": "VALUE = 1\n",
        },
    )
    found = _findings(root, ["wire-magic-coverage"])
    assert any("never dispatched" in f.message for f in found)


def test_wire_stream_direction_required_outside_wire(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/wire.py": "def encode_stream_chunk(s, d, direction='up'):\n    return d\n",
            "comm/client.py": """
                from .wire import encode_stream_chunk

                def good(d):
                    return encode_stream_chunk(0, d, direction="up")

                def bad(d):
                    return encode_stream_chunk(0, d)

                def allowed(d):
                    return encode_stream_chunk(0, d)  # fedtpu: allow(wire-stream-direction): test
            """,
        },
    )
    result = run_check(
        root, rules=["wire-stream-direction"], baseline_path=None
    )
    assert len(result.new) == 1
    assert "encode_stream_chunk() called without" in result.new[0].message
    assert result.allowed == 1


# ---------------------------------------------------------- determinism
def test_determinism_flags_entropy_in_contract_modules(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "data/partition.py": """
                import os
                import random
                import time

                import numpy as np

                def bad_partition(items):
                    random.shuffle(items)
                    t = time.time()
                    k = np.random.rand()
                    n = os.urandom(4)
                    for x in set(items):
                        yield x, t, k, n

                def fine(items, seed):
                    rng = np.random.default_rng(seed)
                    rng2 = random.Random(seed)
                    t0 = time.monotonic()
                    for x in sorted(set(items)):
                        yield x, rng.integers(3), t0, rng2.random()
            """,
            "train/engine.py": """
                import time

                def outside_scope():
                    return time.time()  # not a crc-contract module
            """,
        },
    )
    found = _findings(root, ["determinism"])
    assert len(found) == 5
    assert all(f.path.endswith("data/partition.py") for f in found)
    kinds = "\n".join(f.message for f in found)
    assert "random.shuffle" in kinds and "wall clock" in kinds
    assert "np.random.rand" in kinds and "os.urandom" in kinds
    assert "iteration directly over a set" in kinds


def test_determinism_pragma_suppresses_with_reason(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "faults/proxy.py": """
                import time

                def span_stamp():
                    # fedtpu: allow(determinism): span timestamp only
                    return time.time()
            """
        },
    )
    result = run_check(root, rules=["determinism"], baseline_path=None)
    assert result.new == [] and result.allowed == 1


# ------------------------------------------------------------- unguarded
THREADED_BAD = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def start(self):
            threading.Thread(target=self._run).start()

        def _run(self):
            self.count += 1

        def bump(self):
            self.count += 1
"""


def test_unguarded_cross_thread_write_flagged(tmp_path):
    root = _mini_tree(tmp_path, {"comm/w.py": THREADED_BAD})
    found = _findings(root, ["unguarded"])
    assert len(found) == 2  # both the thread-side and main-side writes
    assert all("Worker.count" in f.message for f in found)


def test_unguarded_lock_guard_and_pragma_pass(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/w.py": """
                import threading

                class Worker:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.count = 0
                        self.noted = 0

                    def start(self):
                        threading.Thread(target=self._run).start()

                    def _run(self):
                        with self._lock:
                            self.count += 1
                        self.noted += 1  # fedtpu: allow(unguarded): test-only

                    def bump(self):
                        with self._lock:
                            self.count += 1

                    def note(self):
                        with self._lock:
                            self.noted += 1
            """
        },
    )
    result = run_check(root, rules=["unguarded"], baseline_path=None)
    assert result.new == [] and result.allowed == 1


def test_unguarded_pool_selfrace_rmw_flagged(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "serving/w.py": """
                from concurrent.futures import ThreadPoolExecutor

                class Stats:
                    def __init__(self):
                        self.pool = ThreadPoolExecutor(4)
                        self.hits = 0

                    def handle(self, conn):
                        self.pool.submit(self._work, conn)

                    def _work(self, conn):
                        self.hits += 1
            """
        },
    )
    found = _findings(root, ["unguarded"])
    assert len(found) == 1
    assert "concurrently with itself" in found[0].message


def test_unguarded_mutator_calls_count_as_writes(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "comm/w.py": """
                import threading

                class Acc:
                    def __init__(self):
                        self.items = []

                    def start(self):
                        threading.Thread(target=self._run).start()

                    def _run(self):
                        self.items.append(1)

                    def push(self, x):
                        self.items.append(x)
            """
        },
    )
    found = _findings(root, ["unguarded"])
    assert len(found) == 2 and all("Acc.items" in f.message for f in found)


# ------------------------------------------------------------- obs rules
def test_obs_span_vocab_flags_off_vocabulary_names(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "obs/trace.py": """
                SPAN_NAMES = (
                    "round",
                    "agg",
                )
            """,
            "comm/server.py": """
                def emit(tracer):
                    tracer.record("round", t_start=0, dur_s=0)
                    tracer.record("bogus-span", t_start=0, dur_s=0)
                    with tracer.span("agg"):
                        pass

                def emit2(tracer):
                    from ..obs.trace import maybe_span
                    with maybe_span(tracer, "unknown-span"):
                        pass
            """,
        },
    )
    found = _findings(root, ["obs-span-vocab"])
    assert sorted(f.message.split("'")[1] for f in found) == [
        "bogus-span",
        "unknown-span",
    ]


def test_obs_metric_once_kind_suffix_and_module_checks(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "serving/a.py": """
                def setup(m):
                    m.counter("x_total")
                    m.counter("bad_name")
                    m.gauge("depth")
            """,
            "control/b.py": """
                def setup(m):
                    m.gauge("x_total")
                    m.gauge("depth")
            """,
        },
    )
    found = _findings(root, ["obs-metric-once"])
    messages = "\n".join(f.message for f in found)
    assert "'x_total' registered as counter here but as gauge" in messages
    assert "counter 'bad_name' does not end in '_total'" in messages
    assert "'depth' registered from multiple modules" in messages


# ----------------------------------------------------- baseline semantics
def test_baseline_suppresses_and_reports_stale(tmp_path):
    root = _mini_tree(
        tmp_path,
        {
            "faults/proxy.py": """
                import time

                def stamp():
                    return time.time()
            """
        },
    )
    finding = _findings(root, ["determinism"])[0]
    baseline = tmp_path / "BASELINE.json"
    baseline.write_text(
        json.dumps(
            {
                "findings": [
                    {
                        "rule": finding.rule,
                        "path": finding.path,
                        "message": finding.message,
                        "reason": "fixture",
                    },
                    {
                        "rule": "determinism",
                        "path": "faults/gone.py",
                        "message": "no longer fires",
                        "reason": "stale entry",
                    },
                ]
            }
        )
    )
    result = run_check(
        root, rules=["determinism"], baseline_path=str(baseline)
    )
    assert result.new == [] and len(result.baselined) == 1
    assert result.exit_code == 0
    assert len(result.stale_baseline) == 1


def test_prune_baseline_rewrites_minus_stale_only(tmp_path, capsys):
    """`fedtpu check --prune-baseline`: stale entries are REMOVED from
    the baseline file, live entries and the review comment survive, and
    a re-run against the pruned baseline is clean with zero stale."""
    root = _mini_tree(
        tmp_path,
        {
            "faults/proxy.py": """
                import time

                def stamp():
                    return time.time()
            """
        },
    )
    finding = _findings(root, ["determinism"])[0]
    baseline = tmp_path / "BASELINE.json"
    live_entry = {
        "rule": finding.rule,
        "path": finding.path,
        "message": finding.message,
        "reason": "fixture",
    }
    baseline.write_text(
        json.dumps(
            {
                "comment": "review note must survive the prune",
                "findings": [
                    live_entry,
                    {
                        "rule": "determinism",
                        "path": "faults/gone.py",
                        "message": "no longer fires",
                        "reason": "stale entry",
                    },
                    {
                        "rule": "determinism",
                        "path": "faults/also_gone.py",
                        "message": "also gone",
                        "reason": "second stale entry",
                    },
                ],
            }
        )
    )
    args = argparse.Namespace(
        root=root,
        rules="determinism",
        baseline=str(baseline),
        prune_baseline=True,
        json=False,
        list_rules=False,
    )
    assert cmd_check(args) == 0
    out = capsys.readouterr().out
    assert "pruned 2 stale baseline entries" in out
    data = json.loads(baseline.read_text())
    assert data["comment"] == "review note must survive the prune"
    assert data["findings"] == [live_entry]
    # The pruned baseline stays clean: still suppresses the live
    # finding, reports ZERO stale.
    result = run_check(
        root, rules=["determinism"], baseline_path=str(baseline)
    )
    assert result.exit_code == 0
    assert len(result.baselined) == 1
    assert result.stale_baseline == []
    # A second prune is a no-op (removes 0).
    assert cmd_check(args) == 0
    assert "pruned 0 stale baseline entries" in capsys.readouterr().out
    assert json.loads(baseline.read_text())["findings"] == [live_entry]


def test_prune_baseline_without_file_errors(tmp_path, capsys):
    root = _mini_tree(tmp_path, {"comm/a.py": "X = 1\n"})
    args = argparse.Namespace(
        root=root,
        rules="determinism",
        baseline=None,
        prune_baseline=True,
        json=False,
        list_rules=False,
    )
    assert cmd_check(args) == 2
    assert "no baseline file" in capsys.readouterr().err


def test_baseline_entry_without_reason_rejected(tmp_path):
    baseline = tmp_path / "BASELINE.json"
    baseline.write_text(
        json.dumps(
            {
                "findings": [
                    {"rule": "determinism", "path": "x.py", "message": "m"}
                ]
            }
        )
    )
    root = _mini_tree(tmp_path, {"comm/a.py": "X = 1\n"})
    with pytest.raises(ValueError, match="no reason"):
        run_check(root, rules=["determinism"], baseline_path=str(baseline))


# ------------------------------------------------- seeded-mutation self-test
@pytest.fixture()
def repo_copy(tmp_path):
    """The real package + baseline copied to a temp root — the mutation
    tests break ONE invariant each and expect `fedtpu check` to exit
    nonzero on the copy."""
    dst = tmp_path / "copy"
    dst.mkdir()
    shutil.copytree(
        os.path.join(REPO_ROOT, PKG_NAME),
        dst / PKG_NAME,
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(
        os.path.join(REPO_ROOT, "ANALYSIS_BASELINE.json"),
        dst / "ANALYSIS_BASELINE.json",
    )
    return dst


def _mutate(root, rel, old, new=None, append=None):
    path = os.path.join(root, PKG_NAME, rel)
    src = open(path).read()
    if old is not None:
        assert old in src, f"mutation anchor {old!r} missing from {rel}"
        src = src.replace(old, new)
    if append:
        src += "\n" + textwrap.dedent(append)
    open(path, "w").write(src)


def test_repo_copy_scans_clean(repo_copy):
    result = run_check(str(repo_copy))
    assert result.new == [], [f.render() for f in result.new]
    assert result.exit_code == 0


def test_mutation_duplicate_hmac_domain_fails(repo_copy):
    # The PR-7 reflection hole, re-introduced: the reply-direction chunk
    # domain collapsed onto the upload-direction one.
    _mutate(
        repo_copy,
        "comm/wire.py",
        'b"fedtpu-stream-rchk-v1"',
        'b"fedtpu-stream-chk-v1"',
    )
    result = run_check(str(repo_copy))
    assert result.exit_code == 1
    assert any(
        f.rule == "wire-domain-unique" and "duplicates" in f.message
        for f in result.new
    )


def test_mutation_duplicate_meta_key_fails(repo_copy):
    # Two capabilities collapsing onto one upload-meta field: the PR-14
    # subtree contributor record silently shadowing the streamed-reply
    # advert.
    _mutate(
        repo_copy,
        "comm/wire.py",
        'SUBTREE_IDS_META_KEY = "subtree_ids"',
        'SUBTREE_IDS_META_KEY = "stream_reply"',
    )
    result = run_check(str(repo_copy))
    assert result.exit_code == 1
    assert any(
        f.rule == "wire-meta-key-unique" and "duplicates" in f.message
        for f in result.new
    )


def test_mutation_wall_clock_in_fold_path_fails(repo_copy):
    _mutate(
        repo_copy,
        "comm/stream_agg.py",
        "t0 = time.monotonic()",
        "t0 = time.time()",
    )
    # Exercised through the real CLI entry (argparse namespace) so the
    # exit-code contract is what's pinned, not just the library result.
    rc = cmd_check(
        argparse.Namespace(
            root=str(repo_copy),
            json=False,
            baseline=None,
            rules="determinism",
            list_rules=False,
        )
    )
    assert rc == 1


def test_mutation_unguarded_cross_thread_write_fails(repo_copy):
    _mutate(
        repo_copy,
        "comm/server.py",
        None,
        append="""
        class _MutationProbe:
            def __init__(self):
                self.n = 0

            def start(self):
                threading.Thread(target=self._work).start()

            def _work(self):
                self.n += 1

            def bump(self):
                self.n += 1
        """,
    )
    result = run_check(str(repo_copy))
    assert result.exit_code == 1
    assert any(
        f.rule == "unguarded" and "_MutationProbe.n" in f.message
        for f in result.new
    )


def test_mutation_off_vocabulary_span_fails(repo_copy):
    _mutate(
        repo_copy,
        "comm/relay.py",
        None,
        append="""
        def _mutation_probe(tracer):
            tracer.record("not-a-span", t_start=0.0, dur_s=0.0)
        """,
    )
    result = run_check(str(repo_copy))
    assert result.exit_code == 1
    assert any(
        f.rule == "obs-span-vocab" and "not-a-span" in f.message
        for f in result.new
    )


def test_mutation_missing_stream_direction_fails(repo_copy):
    _mutate(
        repo_copy,
        "comm/client.py",
        'direction="up",\n        )',
        ")",
    )
    result = run_check(str(repo_copy))
    assert result.exit_code == 1
    assert any(f.rule == "wire-stream-direction" for f in result.new)


# -------------------------------------------------------- repo self-scan
def test_repo_self_scan_clean():
    """The shipping tree passes its own checker with the reviewed
    baseline — the contract the tier-1 verify recipe runs."""
    result = run_check(REPO_ROOT)
    assert result.new == [], "\n".join(f.render() for f in result.new)
    assert result.exit_code == 0
    # The reviewed baseline must not rot: every entry still matches a
    # live finding.
    assert result.stale_baseline == [], result.stale_baseline


def test_cli_parser_wires_check_subcommand():
    from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli import (
        build_parser,
    )

    args = build_parser().parse_args(["check", "--json", "--rules", "determinism"])
    assert args.fn is cmd_check and args.rules == "determinism"


def test_cmd_check_list_rules(capsys):
    rc = cmd_check(
        argparse.Namespace(
            list_rules=True, root=None, json=False, baseline=None, rules=None
        )
    )
    out = capsys.readouterr().out
    assert rc == 0
    for rule in all_rules():
        assert rule in out


# -------------------------------------------------- lock-order detector
def test_lockorder_detects_abba_cycle():
    det = lockorder.LockOrderDetector()
    a = det.lock("siteA")
    b = det.lock("siteB")
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    report = det.report()
    assert report.cycles == [["siteA", "siteB"]]
    assert "ABBA" in report.render()


def test_lockorder_consistent_order_is_clean():
    det = lockorder.LockOrderDetector()
    a = det.lock("siteA")
    b = det.lock("siteB")
    for _ in range(3):
        with a:
            with b:
                pass
    report = det.report()
    assert report.cycles == []
    assert report.edges == {("siteA", "siteB"): 3}


def test_lockorder_cross_thread_cycle_detected():
    det = lockorder.LockOrderDetector()
    a = det.lock("siteA")
    b = det.lock("siteB")
    order = threading.Barrier(2, timeout=5)

    def ab():
        with a:
            with b:
                pass
        order.wait()

    def ba():
        order.wait()  # strictly after ab() released both: no deadlock
        with b:
            with a:
                pass

    t1 = threading.Thread(target=ab)
    t2 = threading.Thread(target=ba)
    t1.start(), t2.start()
    t1.join(timeout=5), t2.join(timeout=5)
    assert det.report().cycles == [["siteA", "siteB"]]


def test_lockorder_same_site_nesting_reported_not_failed():
    det = lockorder.LockOrderDetector()
    first = det.lock("shard")
    second = det.lock("shard")
    with first:
        with second:
            pass
    report = det.report()
    assert report.cycles == []
    assert report.same_site_edges == {"shard": 1}


def test_lockorder_reentrant_rlock_records_no_edge():
    det = lockorder.LockOrderDetector()
    r = det.rlock("outer")
    with r:
        with r:
            pass
    report = det.report()
    assert report.edges == {} and report.cycles == []


def test_lockorder_cross_thread_release_clears_holder_stack():
    """A Lock may legally be released by a thread other than its
    acquirer (handoff). The acquirer's held-stack must be cleared, or
    every later acquire in that thread records phantom edges — and one
    reverse edge fabricates an ABBA cycle that fails the session."""
    det = lockorder.LockOrderDetector()
    handoff = det.lock("handoff")
    other = det.lock("other")
    acquired = threading.Event()
    release_done = threading.Event()
    edges_after = {}

    def acquirer():
        handoff.acquire()
        acquired.set()
        assert release_done.wait(timeout=5)
        # If the stale entry survived, this records handoff -> other.
        with other:
            pass
        edges_after.update(det.report().edges)

    t = threading.Thread(target=acquirer)
    t.start()
    assert acquired.wait(timeout=5)
    handoff.release()  # cross-thread release (main thread)
    release_done.set()
    t.join(timeout=5)
    assert edges_after == {}, edges_after


def test_lockorder_condition_interplay():
    det = lockorder.LockOrderDetector()
    cond = threading.Condition(det.lock("cond"))
    hits = []

    def waiter():
        with cond:
            hits.append(cond.wait(timeout=5))

    t = threading.Thread(target=waiter)
    t.start()
    import time as _time

    deadline = _time.monotonic() + 5
    while not hits and _time.monotonic() < deadline:
        with cond:
            cond.notify_all()
        _time.sleep(0.01)
    t.join(timeout=5)
    assert hits == [True]
    assert det.report().cycles == []


def test_lockorder_session_arming_state():
    """Under the conftest arming (the fast lane's default) the factories
    are patched; with FEDTPU_LOCKORDER=0 they must be pristine."""
    armed = lockorder.armed_detector()
    if os.environ.get("FEDTPU_LOCKORDER", "1").lower() in ("", "0", "false"):
        assert armed is None
    else:
        assert armed is not None
        # Repo-created locks are tracked: the obs metrics registry is
        # package code constructing threading.Lock() at class init.
        from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.obs.metrics import (
            MetricsRegistry,
        )

        reg = MetricsRegistry()
        assert isinstance(reg._lock, lockorder._TrackedLock)
        assert "obs/metrics.py" in reg._lock.site
        # Test-file-created locks are NOT tracked (outside the package).
        assert not isinstance(threading.Lock(), lockorder._TrackedLock)
