"""A document names only what the tree holds.

`README.md`, `TESTING.md` and `PARITY.md` send an operator to commands,
records and subcommands by name. Each name a document puts in backticks
has to exist: a script it says to run with ``python``, a root record
(`PERF.md`, `BASELINE.json` ...), a ``fedtpu`` subcommand. A document
outlived a 4,765-line script and nine record files once (PR 30)."""

import argparse
import functools
import os
import re

import pytest

from detecting_cyber_attacks_with_distilled_large_language_models_in_distributed_networks_tpu.cli import (
    build_parser,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE_SPAN = re.compile(r"```.*?```|`[^`\n]+`", re.S)
PYTHON_SCRIPT = re.compile(r"\bpython3?\s+(?:-\S+\s+)*([\w./-]+\.py)\b")
ROOT_RECORD = re.compile(r"(?<![\w./*-])([A-Z][A-Z_0-9a-z]*\.(?:md|json))\b")
FEDTPU_COMMAND = re.compile(r"\bfedtpu\s+([a-z][a-z-]*)")


@functools.cache
def _subcommands() -> frozenset[str]:
    for action in build_parser()._actions:
        if isinstance(action, argparse._SubParsersAction):
            return frozenset(action.choices)
    raise AssertionError("the fedtpu parser has no subcommands")


@pytest.mark.parametrize("document", ["README.md", "TESTING.md", "PARITY.md"])
def test_a_document_names_only_what_the_tree_holds(document):
    with open(os.path.join(REPO_ROOT, document), encoding="utf-8") as f:
        spans = CODE_SPAN.findall(f.read())
    assert spans, f"{document} holds no code span: the patterns have rotted"
    subcommands = _subcommands()
    missing = []
    for span in spans:
        for script in PYTHON_SCRIPT.findall(span):
            if not os.path.isfile(os.path.join(REPO_ROOT, script)):
                missing.append(f"python {script}")
        for record in ROOT_RECORD.findall(span):
            if not os.path.isfile(os.path.join(REPO_ROOT, record)):
                missing.append(record)
        for command in FEDTPU_COMMAND.findall(span):
            if command not in subcommands:
                missing.append(f"fedtpu {command}")
    assert not missing, f"{document} names what is not there: {sorted(set(missing))}"
