"""The documents name files that exist: every back-quoted repo path in
``README.md``, ``PERF.md`` sections 1-4 and ``docs/*.md`` is a file or a
directory of this checkout.  A path is a back-quoted token that ends in a
known suffix or in ``/``, or a root document by its capitals; it may be
written from the root, from ``paddle_tpu/`` or from ``chipbench/``, with
``{a,b}`` for several, and ``::name`` or ``:line`` after it.  Tokens with
a placeholder (``<cell>``, ``*``), URL paths and dot-directories (what a
run leaves behind) are not held to anything."""

import itertools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SUFFIXES = (".py", ".md", ".json", ".jsonl", ".cc", ".toml", ".yaml")
DOCUMENTS = ["README.md", "PERF.md"] + sorted(
    str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md"))


def _text(document):
    text = (ROOT / document).read_text()
    if document == "PERF.md":      # sections 5-7 tell of trees that were
        text = text[:text.index("\n## 5.")]
    return text


def _expand(token):
    """``a/{b,c}/d`` -> ``a/b/d``, ``a/c/d``."""
    parts = re.split(r"\{([^{}]*)\}", token)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def paths_named(text):
    """The repo paths a document's back-quoted tokens name."""
    out = []
    text = re.sub(r"```.*?```", " ", text, flags=re.S)     # fenced code
    for token in re.findall(r"`([^`\n]+)`", text.replace("\n", " ")):
        token = re.sub(r"\s+", "", token) if "{" in token else token
        token = re.split(r"::|:\d|#", token)[0].strip()
        if not token or re.search(r"[<>*\s=()]", token) or \
                token.startswith(("/", ".", "-")) or "//" in token:
            continue
        for path in _expand(token):
            name = path.rstrip("/").rsplit("/", 1)[-1]
            root_document = "/" not in path and path.endswith(SUFFIXES) \
                and name.split(".")[0].isupper()
            if path.endswith("/") or root_document or (
                    path.endswith(SUFFIXES)
                    and ("/" in path or path.endswith(".py"))):
                out.append(path)
    return out


def exists(path):
    return any((base / path).exists()
               for base in (ROOT, ROOT / "paddle_tpu", ROOT / "chipbench"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_repo_path_a_document_names_exists(document):
    named = paths_named(_text(document))
    assert named, f"{document} names no path: the extraction is broken"
    missing = sorted({p for p in named if not exists(p)})
    assert not missing, f"{document} names paths that do not exist: {missing}"


def test_the_extraction_sees_what_it_should():
    text = ("`no_such.py` and `no_such/run.py`, `models/llama.py::f`, "
            "`paddle_tpu/{kernels,nope}/`, `BENCHMARK.json`, `config.json`, "
            "`/v1/completions`, `.chipbench_out/<cell>/trace`, `dp/mp/pp`, "
            "`chipbench/kernels/<kernel>.py::match`, `tests/conftest.py:3`")
    named = paths_named(text)
    assert named == ["no_such.py", "no_such/run.py", "models/llama.py",
                     "paddle_tpu/kernels/", "paddle_tpu/nope/",
                     "BENCHMARK.json", "tests/conftest.py"]
    assert [p for p in named if not exists(p)] == [
        "no_such.py", "no_such/run.py", "paddle_tpu/nope/"]
