"""The documents name only files that exist.

README.md, the guides under docs/ and PERF.md point readers at programs,
records and other documents by path.  A path that names nothing sends a
reader looking for a number or a tool that is not there, which is how a
retired benchmark stayed the README's source of speed for a whole round.
"""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    "README.md",
    "docs/architecture.md",
    "docs/api.md",
    "docs/getting_started.md",
    "docs/observability.md",
    "docs/testing.md",
    "PERF.md",
)

# A repo-relative path as the documents write one: segments of word
# characters, dots and dashes, ending in .py, .json or .md.  A `<` or `*`
# beside it marks a pattern (`benchmark/configs/<name>.json`,
# `BENCH_r0*.json`), a `/` before it a URL's path (`GET /incident.json`).
_PATH = re.compile(r"(?<![\w./<*:-])((?:[\w.-]+/)*[\w.-]+\.(?:py|json|md))(?![\w*<])")

# Names that are not this repo's files, each for its reason.
NOT_OURS = {
    "config.json",  # a published model's configuration on huggingface.co
    "train.py",  # the reader's own training script in a launch example
    "incident.json",  # the manifest inside a captured incident bundle
    "t.json",  # part of `t.json.gz`, a trace the reader captured
}

# The documents shorten a path inside the tree they are describing.
_TREES = ("", "torchft_tpu", "benchmark")

_SKIP_DIRS = {".git", "chiprun_out", "committed_tree", "__pycache__", "build", "build-g++", "out"}


def _basenames() -> set:
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        names.update(files)
    return names


def test_documents_name_only_files_that_exist() -> None:
    basenames = _basenames()
    missing = {}
    for doc in DOCUMENTS:
        with open(os.path.join(REPO, doc), encoding="utf-8") as f:
            named = set(_PATH.findall(f.read())) - NOT_OURS
        assert named, f"{doc}: the pattern found no path at all"
        for path in sorted(named):
            if "/" in path:
                found = any(
                    os.path.exists(os.path.join(REPO, tree, path))
                    for tree in _TREES + (os.path.dirname(doc),)
                )
            else:
                found = path in basenames
            if not found:
                missing.setdefault(doc, []).append(path)
    assert not missing, f"documents name files that do not exist: {missing}"
