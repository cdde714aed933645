"""The documents describe the tree that stands.

One case per document a newcomer reads first (``README.md``,
``Makefile``, ``BASELINE.md``, ``docs/*.md``):

- every repo path it names (``.py``, ``.md``, ``.json``, ``.sh``; from
  the root, from ``aigw_tpu/`` or by a unique tail) exists;
- every ``--flag`` on a ``tpuserve`` / ``aigw run`` command line is in
  that subcommand's parser in ``cli.py``, and every other ``--flag`` is
  defined by ``cli.py`` or by a script the same document names.

A deleted file, script or option that lingers in a document fails here.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import subprocess

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ["README.md", "Makefile", "BASELINE.md"] + sorted(
    os.path.relpath(p, _REPO)
    for p in glob.glob(os.path.join(_REPO, "docs", "*.md")))

_PATH = re.compile(r"(?<![\w./<>*{}$~-])[\w./-]+\.(?:py|md|json|sh)\b(?![\w/*])")
_FLAG = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*(?![\w-])")
#: a line that runs one of these speaks of that program's flags
_FOREIGN = ("pytest", "pip ", "chiprun", "curl ", "kubectl", "helm ",
            "docker ")
#: files a documented command writes: named, never in the tree
_NOT_IN_TREE = {"core.json"}


@functools.cache
def _tree() -> list[str]:
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=_REPO, capture_output=True, text=True)
    if out.returncode == 0 and out.stdout.strip():
        return [p for p in out.stdout.split("\n")
                if p and os.path.exists(os.path.join(_REPO, p))]
    return [os.path.relpath(os.path.join(d, f), _REPO)
            for d, _dirs, fs in os.walk(_REPO) for f in fs]


def _resolve(token: str, tree: list[str]) -> str | None:
    token = token.lstrip("./")
    for p in tree:
        if p == token or p.endswith("/" + token):
            return p
    return None


@functools.cache
def _cli_flags() -> dict[str, set[str]]:
    """``--flags`` per subcommand parser variable of ``cli.py``."""
    with open(os.path.join(_REPO, "aigw_tpu", "cli.py")) as f:
        src = f.read()
    flags: dict[str, set[str]] = {}
    for m in re.finditer(
            r'(p_\w+|parser)\.add_argument\(((?:\s*"[^"]+",?)+)', src):
        flags.setdefault(m.group(1), set()).update(
            re.findall(r'"(--[\w-]+)"', m.group(2)))
    return flags


def _spoken(text: str, makefile: bool) -> list[str]:
    """The pieces of a document that speak of the program: backticked
    spans and the lines of fenced blocks (a Makefile: every line)."""
    text = text.replace("\\\n", " ")  # a continued command is one line
    if makefile:
        return text.split("\n")
    fenced = re.findall(r"^```[^\n]*\n(.*?)^```", text, re.S | re.M)
    rest = re.sub(r"^```[^\n]*\n.*?^```", "", text, flags=re.S | re.M)
    return ([ln for block in fenced for ln in block.split("\n")]
            + re.findall(r"`([^`\n]+)`", rest))


@pytest.mark.parametrize("doc", _DOCS)
def test_document_names_only_what_exists(doc):
    tree = _tree()
    cli = _cli_flags()
    any_cli = set().union(*cli.values())
    with open(os.path.join(_REPO, doc)) as f:
        spoken = _spoken(f.read(), makefile=doc == "Makefile")

    named: set[str] = set()
    missing: list[str] = []
    for piece in spoken:
        for token in _PATH.findall(piece):
            if os.path.basename(token) in _NOT_IN_TREE:
                continue
            found = _resolve(token, tree)
            if found is None:
                missing.append(token)
            elif found.endswith(".py"):
                named.add(found)
    assert not missing, f"{doc} names files that do not exist: {missing}"

    scripted: set[str] = set()
    for path in named:
        with open(os.path.join(_REPO, path)) as f:
            scripted.update(re.findall(r'"(--[a-z][\w-]*)"', f.read()))
    unknown: list[str] = []
    for piece in spoken:
        if any(prog in piece for prog in _FOREIGN):
            continue
        if _PATH.search(piece):  # a script's own command line
            own = any_cli | scripted
        elif "tpuserve" in piece:
            own = cli["p_serve"]
        elif re.search(r"aigw(_tpu)? run\b", piece):
            own = cli["p_run"] | cli.get("parser", set())
        else:
            own = any_cli | scripted
        unknown += [f for f in _FLAG.findall(piece) if f not in own]
    assert not unknown, (
        f"{doc} gives flags no parser it names defines: {unknown}")
