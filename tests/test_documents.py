"""The operator's documents name only files that exist.

README.md, BASELINE.md and PARITY.md are what a new owner reads first; a
path they name that is gone (a deleted script, a renamed test) sends them to
run something the tree no longer holds. PERF.md and CHANGES.md are history
and may name what was deleted, so they are not checked."""

import glob
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
# Directories whose paths the documents write from the root of the tree.
PREFIXES = (
    "benchmark/", "tools/", "tests/", "configs/", "distributed_tf_serving_tpu/",
)
BARE = re.compile(r"[\w.-]+\.(?:py|json|md)")
FENCE = re.compile(r"^```.*?^```", re.M | re.S)
INLINE = re.compile(r"`([^`\n]+)`")


def _named_paths(text: str) -> set[str]:
    """Every token, in backticks or in a fenced block, that reads as a path
    into the tree: under one of PREFIXES, or a bare *.py / *.json / *.md."""
    chunks = FENCE.findall(text) + INLINE.findall(FENCE.sub("", text))
    found = set()
    for chunk in chunks:
        for token in chunk.split():
            token = token.strip("`'\"()[],;").rstrip(".:")
            token = re.sub(r"::.*$|:\d[\d,:-]*$", "", token)  # ::test, :line
            if "<" in token or ">" in token or "{" in token or "=" in token:
                continue  # a placeholder, a brace set or an assignment
            if token.startswith(PREFIXES) or BARE.fullmatch(token):
                found.add(token)
    return found


def _exists(token: str) -> bool:
    if "*" in token or "?" in token:
        return bool(glob.glob(str(ROOT / token)))
    if (ROOT / token).exists():
        return True
    # A bare module name in the layout tree (`codec.py`, `faults.py`) is
    # the package's own top level.
    return "/" not in token and (ROOT / "distributed_tf_serving_tpu" / token).exists()


@pytest.mark.parametrize("document", ["README.md", "BASELINE.md", "PARITY.md"])
def test_document_names_only_paths_that_exist(document):
    named = _named_paths((ROOT / document).read_text())
    assert named, f"{document} names no path at all: the extraction is broken"
    missing = sorted(t for t in named if not _exists(t))
    assert not missing, f"{document} names paths the tree does not hold: {missing}"


def _benchmark_lines(section: str) -> list[tuple[str, str]]:
    """The fields of one section of BENCHMARK.json that the driver holds to
    one line of 1 to 200 printable characters, as (where, text)."""
    declared = json.loads((ROOT / "BENCHMARK.json").read_text())
    if section == "command":
        return [(f"command[{i}]", word) for i, word in enumerate(declared["command"])]
    keys = {"configs": ("source", "why"), "workloads": ("why",), "per_layer": ("layer",)}[section]
    return [(f"{section}.{entry['name']}.{key}", entry[key])
            for entry in declared[section] for key in keys]


@pytest.mark.parametrize("section", ["command", "configs", "workloads", "per_layer"])
def test_benchmark_declaration_lines_fit_the_form(section):
    # A `why` of 205 characters had the driver refuse PR 32 before any run.
    lines = _benchmark_lines(section)
    assert lines, section
    bad = [(where, len(text)) for where, text in lines
           if not (1 <= len(text) <= 200 and text.isprintable() and "\t" not in text)]
    assert not bad, f"not one line of 1 to 200 printable characters: {bad}"
