"""The user's documents name what exists: ``README.md`` and
``MIGRATION.md`` are held to the tree, the CLI's parser and
``BENCHMARK.json``.
"""

import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "dss_ml_at_scale_tpu")
DOCS = ["README.md", "MIGRATION.md"]


def _code(doc: str) -> list[str]:
    """Every back-ticked span and every line of a fenced block."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    parts = re.split(r"^```[^\n]*\n(.*?)^```", text, flags=re.S | re.M)
    return re.findall(r"`([^`\n]+)`", "".join(parts[0::2])) + [
        line for block in parts[1::2] for line in block.splitlines()
    ]


def _repo_dirs() -> set[str]:
    """Directories of the root and of the package, less what runs leave
    behind (``.gitignore``)."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f}
    return {
        name for base in (REPO, PACKAGE) for name in os.listdir(base)
        if os.path.isdir(os.path.join(base, name))
        and not name.startswith(".") and name not in ignored
    }


def _exists(path: str, bases) -> bool:
    return any(glob.glob(os.path.join(base, path)) for base in bases)


@pytest.mark.parametrize("doc", DOCS)
def test_paths_the_document_names_exist(doc):
    """A path under one of the repo's directories, a bare script name or
    a capitalised ``.json``/``.md`` name has to be there. Not the repo's,
    and so not held: files a run writes (``journal.jsonl``) and paths
    into the reference repo (``deep_learning/...``)."""
    dirs = _repo_dirs()
    missing = set()
    for span in _code(doc):
        for word in span.split():
            word = re.sub(r":[\w.,-]*$", "", word.strip("(),;'\""))
            if not re.fullmatch(r"[\w.*/-]+", word):
                continue
            if "/" in word:
                if word.split("/")[0] in dirs and not _exists(
                        word, (REPO, PACKAGE)):
                    missing.add(word)
            elif re.fullmatch(r"\w+\.py|[A-Z]\w*\.(json|jsonl|md)", word):
                if not _exists(word, (REPO, PACKAGE,
                                      os.path.join(REPO, "scripts"))):
                    missing.add(word)
    assert not missing, f"{doc} names what is not in the tree"


def test_every_dsst_subcommand_shown_is_registered():
    import argparse

    from dss_ml_at_scale_tpu.config.cli import build_parser

    registered = set()
    for action in build_parser()._actions:
        if isinstance(action, argparse._SubParsersAction):
            registered |= set(action.choices)
    assert "train" in registered
    shown = set()
    for doc in DOCS:
        for span in _code(doc):
            for m in re.finditer(r"\bdsst ([a-z][\w/-]*)", span):
                shown |= set(m.group(1).split("/"))
    assert shown and not shown - registered


def test_readme_shows_the_benchmark_command():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    section = readme[readme.index("## Tests and benchmarks"):]
    assert " ".join(benchmark["command"]) in section
    for cell in benchmark["workloads"]:
        assert f"`{cell['name']}`" in section
