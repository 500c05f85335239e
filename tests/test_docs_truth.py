"""The documents say what the tree holds.

Every builder session starts from ``CLAUDE.md``, ``README.md`` and the
verify skill; a path they name that is gone, a knob nobody reads or a
benchmark that is not the one the driver runs sends the session the
wrong way. These tests read the documents against the tree:

- a backticked token that reads as a path of this repo exists;
- the ``HVD_*`` names the package reads are the ones ``docs/running.md``
  and ``docs/observability.md`` document, both ways;
- ``PERF.md`` names every cell and every metric of ``BENCHMARK.json``;
- the entry documents name the benchmark the driver runs and its record.

``CHANGES.md``, ``ROADMAP.md``, ``PERF.md`` and ``ISSUE.md`` are history:
they may name what is gone, and are not held to the first rule.
"""

import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "horovod_tpu")

ENTRY_DOCS = ("README.md", "CLAUDE.md",
              os.path.join(".claude", "skills", "verify", "SKILL.md"))
# Listed, not globbed: pytest-xdist needs every worker to collect the
# same cases, and a document that is added should be added here.
DOCS = ENTRY_DOCS + tuple(os.path.join("docs", name) for name in (
    "benchmarks.md", "concepts.md", "deploy.md", "inference.md",
    "observability.md", "parallelism.md", "running.md",
    "static-analysis.md", "tensor-fusion.md", "timeline.md", "tpus.md",
    "troubleshooting.md"))

_SOURCE_SUFFIXES = (".py", ".md", ".cc", ".sh")
# What a run leaves behind and git ignores: named in documents, absent
# from a fresh checkout.
_RUNTIME_ROOTS = ("chiprun_out", ".cache", ".chipwork")


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _basenames_in_tree():
    names = set()
    for top in ("horovod_tpu", "examples", "tests", "docs", "benchmark"):
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    names.update(os.listdir(REPO))
    return names


def _repo_path_tokens(text):
    """Backticked tokens that read as a path of this repo: relative, no
    placeholder or glob, and either rooted in a directory of the repo or
    of the package, or a bare source-file name."""
    for token in re.findall(r"`([^`\n]+)`", text):
        token = token.strip().rstrip(".,;:")
        # `path.py:123`, `tests/test_x.py::test_name`
        token = re.sub(r"(::[\w\[\]-]+|:\d+(-\d+)?)+$", "", token)
        if not token or re.search(r"[\s<>*{}$|=()\[\]~\\]|\.\.\.", token):
            continue
        if token.startswith(("/", "-", "http")):
            continue
        first = token.split("/", 1)[0]
        if first in _RUNTIME_ROOTS:
            continue
        if "/" in token:
            if any(os.path.isdir(os.path.join(root, first))
                   for root in (REPO, PACKAGE)):
                yield token
        elif token.endswith(_SOURCE_SUFFIXES):
            yield token


@pytest.mark.parametrize("doc", DOCS, ids=lambda d: d.replace(os.sep, "/"))
def test_backticked_repo_paths_exist(doc):
    basenames = _basenames_in_tree()
    missing = []
    for token in sorted(set(_repo_path_tokens(_read(doc)))):
        if "/" in token:
            # `utils/stats` and `utils/profiler.capture` name a module
            # and a function of it.
            head, _, base = token.rpartition("/")
            module = f"{head}/{base.split('.', 1)[0]}.py"
            found = any(os.path.exists(os.path.join(root, candidate))
                        for root in (REPO, PACKAGE)
                        for candidate in (token, module))
        else:
            found = token in basenames
        if not found:
            missing.append(token)
    assert not missing, f"{doc} names paths that do not exist: {missing}"


# --- HVD_* knobs ----------------------------------------------------------

KNOB_DOCS = (os.path.join("docs", "running.md"),
             os.path.join("docs", "observability.md"))
#: Quoted ``HVD_*`` literals of the package that are not environment
#: variables: the timeline's clock-alignment metadata event.
NOT_KNOBS = {"HVD_CLOCK"}
#: Read as ``<name>`` and as ``<name>_<CLASS>`` for a priority class.
PER_CLASS = ("HVD_ADMISSION_MAX_INFLIGHT", "HVD_ADMISSION_MAX_BYTES")


def _knobs_read():
    """Every quoted ``HVD_*`` literal of the package's sources, and of
    the suite's ``conftest.py`` (the preflight's override)."""
    sources = [p for p in glob.glob(os.path.join(PACKAGE, "**", "*"),
                                    recursive=True)
               if p.endswith((".py", ".cc"))]
    sources.append(os.path.join(REPO, "tests", "conftest.py"))
    names = set()
    for path in sources:
        with open(path) as f:
            names.update(re.findall(r"[\"'](HVD_[A-Z0-9_]*[A-Z0-9])[\"']",
                                    f.read()))
    return names - NOT_KNOBS


def _knobs_documented():
    names = set()
    for doc in KNOB_DOCS:
        # `HVD_FLEET_*` names a family: the trailing underscore drops it.
        names.update(re.findall(r"\bHVD_[A-Z0-9_]*[A-Z0-9]\b(?!_)",
                                _read(doc)))
    for base in PER_CLASS:
        names = {base if n.startswith(base + "_") else n for n in names}
    return names


def test_every_knob_read_is_documented():
    undocumented = sorted(_knobs_read() - _knobs_documented())
    assert not undocumented, (
        f"read by the package, in neither of {KNOB_DOCS}: {undocumented}")


def test_every_documented_knob_is_read():
    unread = sorted(_knobs_documented() - _knobs_read())
    assert not unread, (
        f"documented in {KNOB_DOCS}, read by nothing: {unread}")


# --- the benchmark and its account ----------------------------------------

def test_perf_md_names_every_cell_and_metric():
    bench = json.loads(_read("BENCHMARK.json"))
    perf = _read("PERF.md")
    names = [entry["name"] for key in ("workloads", "end_to_end", "per_layer")
             for entry in bench[key]]
    missing = [n for n in names if f"`{n}`" not in perf]
    assert not missing, f"PERF.md does not name {missing}"


def test_entry_documents_name_the_benchmark():
    for doc in ENTRY_DOCS:
        text = _read(doc)
        for needed in ("benchmark/run.py", "PERF_LEDGER.jsonl"):
            assert needed in text, f"{doc} does not name {needed}"
