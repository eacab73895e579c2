"""The documents a new owner reads name files that exist.

Every backticked word of ``README.md``, ``DESIGN.md`` and ``docs/API.md`` that
ends in ``.py``, ``.md``, ``.json``, ``.cpp`` or ``.yml`` (a ``:line`` after it
allowed) and holds no ``*``, ``<`` or ``{`` has to be a file of this tree:
its path from the root, from ``distkeras_tpu/`` or from any directory, down to
a bare file name. A document that cites a deleted benchmark, record or module
fails here. A word under ``distkeras/`` cites the reference implementation
(upstream dist-keras, ``SURVEY.md``), whose modules are listed below and not
held here. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are history and cite
deleted files on purpose; they are not read.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = {f"distkeras/{m}.py" for m in (
    "trainers", "workers", "parameter_servers", "networking", "utils", "transformers",
    "predictors", "evaluators", "job_deployment")}
WORD = re.compile(r"^([\w./\-]+\.(?:py|md|json|cpp|yml))(?::[\d,\-]+)?[.,;:)]*$")


def _ignored_dirs():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        return {line.strip().strip("/").split("/")[-1] for line in f
                if line.strip().endswith("/")} | {".git"}


def _files():
    """Every file git would commit, as ``/``-joined paths from the root."""
    skip, out = _ignored_dirs(), []
    for directory, names, files in os.walk(ROOT):
        names[:] = [n for n in names if n not in skip]
        rel = os.path.relpath(directory, ROOT)
        out += ["/" + (f if rel == "." else f"{rel}/{f}").replace(os.sep, "/") for f in files]
    return out


def _cited(text):
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            hit = WORD.match(word)
            if hit and not set(word) & set("*<{"):
                yield hit.group(1)


@pytest.mark.parametrize("document", ["README.md", "DESIGN.md", "docs/API.md"])
def test_every_file_a_document_names_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        cited = sorted(set(_cited(f.read())))
    assert cited, f"{document} names no file at all: the pattern no longer reads it"
    files = _files()
    missing = [c for c in cited if c not in REFERENCE
               and not any(path.endswith("/" + c.lstrip("./")) for path in files)]
    assert not missing, f"{document} names files this tree does not hold: {missing}"
