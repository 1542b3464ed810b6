"""Documents cite what exists: every path and every `ATX_*` name that
`README.md`, `PERF.md` or a page of `docs/` puts in back quotes is in the
tree. Historical records (`CHANGES.md`, `ROADMAP.md`, `SURVEY.md`) are not
held to it."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_LEVEL = ("accelerate_tpu/", "benchmarks/", "tests/", "docs/", "perf/", "examples/")
# File names of the reference project, cited as the reference's.
REFERENCE_FILES = {"hooks.py", "inference.py"}
DOCUMENTS = ["README.md", "PERF.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(REPO, "docs")) if name.endswith(".md")
)


@pytest.fixture(scope="module")
def tree():
    """The file names of the tree, and the package's source as one text."""
    names, source = set(os.listdir(REPO)), []
    for top in TOP_LEVEL:
        for base, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            names.update(files)
            if top == "accelerate_tpu/":
                for name in files:
                    if name.endswith((".py", ".cpp")):
                        with open(os.path.join(base, name), encoding="utf-8") as f:
                            source.append(f.read())
    return names, "\n".join(source)


def _cited(text):
    """The words inside back quotes, each cut back to the path it names:
    `tests/test_x.py::TestY`, `models/llama.py:420`, `docs/a.md#part`."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = re.split(r"::|#", word.strip("()[],;'\""))[0]
            yield re.sub(r":[\d,:-]+$", "", word).rstrip(".,:")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_cites_what_exists(document, tree):
    names, source = tree
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    missing = set()
    for word in _cited(text):
        if re.fullmatch(r"\w+\.py", word):
            if word not in names and word not in REFERENCE_FILES:
                missing.add(word)
        elif word.startswith(TOP_LEVEL) and not re.search(r"[<>*{}$]|\.\.\.", word):
            if not os.path.exists(os.path.join(REPO, word)):
                missing.add(word)
    # `ATX_BLOCK_<OP>` cites a family of names: its prefix must be read somewhere.
    for name in set(re.findall(r"ATX_[A-Z0-9_]+", text)):
        if name not in source:
            missing.add(name)
    assert not missing, f"{document} cites what is not in the tree: {sorted(missing)}"
