"""What the repository says of itself: the documents that tell a reader what
to run name only files that are in the tree, and the root holds one
yardstick (``benchmarks/``, under ``BENCHMARK.json``), not the scripts and
CPU-backend records it replaced."""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ("README.md", "docs/tuning.md", ".claude/skills/verify/SKILL.md",
             ".github/workflows/ci.yml", "benchmarks/README.md")

#: a relative ``*.py`` / ``*.json`` path. An absolute path (``/tmp/...``), a
#: placeholder (``<cell>.json``), a glob or a shell variable is none: what a
#: command writes or a reader fills in is not a file of the tree
_PATH = re.compile(
    r"(?<![\w./<>$*{}\[\]-])((?:[\w.-]+/)*[\w-][\w.-]*\.(?:py|json))(?!\w)")
#: names a document gives to a file its reader (or its command) makes
_MADE_BY_THE_READER = {
    "my_drive.py",   # SKILL.md: "drive from outside the repo dir"
    "trace.json",    # tuning.md: sess.export_trace("trace.json")
    "file.py",       # tuning.md: the ledger's `file.py:lineno` call sites
}


@pytest.fixture(scope="module")
def tree():
    skip = {".git", "__pycache__", ".jax_compile_cache", "chiprun_out",
            "_archive_check", ".pytest_cache", ".cache", ".scratch"}
    found = set()
    for directory, names, files in os.walk(ROOT):
        names[:] = [n for n in names if n not in skip]
        rel = os.path.relpath(directory, ROOT)
        for f in files:
            found.add(os.path.normpath(os.path.join(rel, f)))
    return found


def _in_tree(path, tree):
    path = os.path.normpath(path)
    return path in tree or any(t.endswith(os.sep + path) for t in tree)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_that_exist(doc, tree):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    named = sorted(set(_PATH.findall(text)) - _MADE_BY_THE_READER)
    assert named, f"{doc} names no file at all: the pattern has rotted"
    missing = [p for p in named if not _in_tree(p, tree)]
    assert missing == [], (
        f"{doc} names files that are not in the tree: {missing}")


def test_the_path_pattern_reads_commands(tree):
    text = ("run `python3 benchmarks/run.py --workload x` then "
            "`tools/tpu_profile.py /tmp/evlog/tpu-events-1-2.jsonl`, write "
            "/tmp/out.json, see workloads/<cell>.json, BENCH_*.json, "
            "$PREV.json and exec/aggregate.py:71; smoke.py is gone.")
    assert _PATH.findall(text) == [
        "benchmarks/run.py", "tools/tpu_profile.py", "exec/aggregate.py",
        "smoke.py"]
    assert _in_tree("exec/aggregate.py", tree)
    assert _in_tree("benchmarks/run.py", tree)
    assert not _in_tree("smoke.py", tree)  # whole components only


def test_the_root_holds_one_yardstick():
    def at_root(pattern):
        return sorted(os.path.basename(p)
                      for p in glob.glob(os.path.join(ROOT, pattern)))

    assert at_root("BENCH_*.json") == []
    assert at_root("MULTICHIP_*.json") == []
    assert at_root("*.py") == ["__graft_entry__.py"]
    assert at_root("BENCHMARK.json") == ["BENCHMARK.json"]
