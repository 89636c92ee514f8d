"""Every repo path a document, docstring or comment cites is in the tree.

The documents that describe the tree as it is (README.md, the verify
skill, ``docs/*.md``) and the source directories whose docstrings and
comments point at other files are read for file citations; each one
that looks like a path of this repo must be a file ``git ls-files``
lists.  CHANGES.md, PERF.md and ROADMAP.md are history and are not
read; SURVEY.md describes the reference's tree, not this one.

What counts as a cited repo path: a ``dir/.../name.ext`` token, or a bare
``name.py`` / ``name.md``.  A path may be written from the repo's root,
from the package's or the benchmark's root (``core/store.py``,
``references/mf.py``), from the citing file's own directory or, for a
bare name, as any tracked file's name.  Not repo
paths, and not matched: absolute paths, placeholders and globs
(``<cell>``, ``*``, ``{a,b}``), anything under a git-ignored directory
(a run's outputs: ``chiprun_out/``, ``_chip/``, ``results/``).
"""
import io
import os
import re
import subprocess
import tokenize

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "flink_parameter_server_tpu"

TRACKED = frozenset(
    subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True,
        check=True,
    ).stdout.split()
)
BASENAMES = frozenset(os.path.basename(p) for p in TRACKED)

with open(os.path.join(REPO, ".gitignore")) as _f:
    IGNORED_DIRS = tuple(
        line.strip() for line in _f if line.strip().endswith("/")
    )

# a file a run of the program writes where the user points it; cited by
# bare name, never tracked
RUN_OUTPUTS = frozenset({"run_report.md"})

_PATH = re.compile(
    r"(?<![\w/.<>{}*~$-])"
    r"((?:[A-Za-z_.][\w-]*/)*[A-Za-z_][\w.-]*"
    r"\.(?:py|md|json|jsonl|sh|toml|cc|cpp|h))"
    r"(?![\w/*{<-])"
)


def _documents():
    yield "README.md", ["README.md"]
    skill = ".claude/skills/verify/SKILL.md"
    yield skill, [skill]
    for p in sorted(TRACKED):
        if p.startswith("docs/") and p.endswith(".md"):
            yield p, [p]


def _source_dirs():
    subs = sorted({
        p.split("/")[1] for p in TRACKED
        if p.startswith(PACKAGE + "/") and p.count("/") >= 2
    })
    for sub in subs:
        root = f"{PACKAGE}/{sub}/"
        yield root, [p for p in sorted(TRACKED)
                     if p.startswith(root) and p.endswith(".py")]
    for top in ("tools/", "examples/"):
        yield top, [p for p in sorted(TRACKED)
                    if p.startswith(top) and p.endswith(".py")]
    yield "root scripts", [
        p for p in sorted(TRACKED)
        if "/" not in p and p.endswith(".py")
    ] + [f"{PACKAGE}/__init__.py"]


CASES = list(_documents()) + list(_source_dirs())


def _prose(path, text):
    """What of a file may cite: a document whole; of a Python source its
    comments and string literals that read as prose (docstrings), not the
    paths its code builds or matches."""
    if not path.endswith(".py"):
        return text
    out = []
    for tok in tokenize.generate_tokens(io.StringIO(text).readline):
        if tok.type == tokenize.COMMENT:
            out.append(tok.string)
        elif tok.type == tokenize.STRING and tok.string.lstrip(
            "rRbBuU"
        ).startswith(('"""', "'''")):
            out.append(tok.string)
    return "\n".join(out)


def _resolves(cited, citing):
    if "/" not in cited:
        return cited in BASENAMES or cited in RUN_OUTPUTS
    here = os.path.dirname(citing)
    candidates = (
        cited,
        f"{PACKAGE}/{cited}",
        os.path.normpath(os.path.join(here, cited)),
        f"chipbench/{cited}",
        f"docs/{cited}",
        f"tests/{cited}",
    )
    return any(c in TRACKED for c in candidates)


def _is_repo_path(cited):
    if "/" not in cited:
        return cited.endswith((".py", ".md"))
    return not any(f"/{d}" in f"/{cited}" for d in IGNORED_DIRS)


def dangling_in(text, citing):
    return [
        f"{citing}: {cited}  ({line.strip()[:80]})"
        for line in text.splitlines()
        for cited in (
            m.group(1).removeprefix("./") for m in _PATH.finditer(line)
        )
        if _is_repo_path(cited) and not _resolves(cited, citing)
    ]


def dangling(paths):
    bad = []
    for path in paths:
        with open(os.path.join(REPO, path), encoding="utf-8") as f:
            bad += dangling_in(_prose(path, f.read()), path)
    return bad


@pytest.mark.parametrize(
    "files", [c[1] for c in CASES], ids=[c[0] for c in CASES]
)
def test_every_cited_path_exists(files):
    assert files, "the case lists no file: the tree moved under the test"
    assert dangling(files) == []


def test_the_reader_sees_what_it_should_and_no_more():
    text = (
        "gone: `no_such_dir/gone.py`, no_such_script.py, docs/no_such.md; "
        "here: core/store.py, `chipbench/run.py`, store.py, docs/api.md, "
        "references/mf.py; not ours: /opt/x/y.py, chiprun_out/a.json, "
        "results/tpu/run_report.md, chipbench/out/trace/<cell>/x.json, "
        "budget.json, snapshot.py/engine.py"
    )
    assert [b.split()[1] for b in dangling_in(text, "README.md")] == [
        "no_such_dir/gone.py", "no_such_script.py", "docs/no_such.md",
    ]
