"""The documents name files that exist.

One case a document: every path it links (`](path)`) or back-ticks with a
`.py` / `.json` / `.md` suffix, and every script a `python <script>` line
of a code fence runs, has to be a file of the tree. A name
resolves when some file's repo-relative path equals it or ends with
`/<name>` (documents write `obs/slo.py` for `paddle_tpu/obs/slo.py` and
`engine.py` inside a section about one module).

Not held to it:

* a history section: from a marker line (`> **History:** ...`) to the
  next heading at or above the level of the heading it stands under; a
  marker before the first section heading covers the whole document.
  History keeps the record of code that is gone, by its old names;
* names that are patterns, not files (`<`, `*`, `{`), URLs, paths into
  the reference framework's tree (`paddle/...`, `python/paddle/...`),
  and the files the program writes at run time (`RUNTIME_FILES`).

`benchmarks/` documents itself and is not listed.
"""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md"]
             + sorted(os.path.relpath(p, ROOT) for p in
                      glob.glob(os.path.join(ROOT, "docs", "*.md")))
             + [".claude/skills/verify/SKILL.md"])

#: written by a run (into a checkpoint directory), never checked in
RUNTIME_FILES = {"extra.json"}
UPSTREAM_PREFIXES = ("paddle/", "python/paddle/")

_TICKED = re.compile(
    r"`([^`\s]+?\.(?:py|json|md))(?:::[^`\s]*|:[0-9][0-9,\-]*)?`")
_LINKED = re.compile(r"\]\(([^)#\s]+?\.(?:py|json|md))(?:#[^)]*)?\)")
_RUN = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+)*([\w./\-]+\.py)\b")
_HEADING = re.compile(r"^(#{1,6})\s")
_HISTORY = re.compile(r"^[>\s*_]*history:", re.IGNORECASE)


def _tree_files():
    """Repo-relative paths of the tree's files: no hidden directory but
    `.claude`, and not what a chip call brings back."""
    found = []
    for base, dirs, files in os.walk(ROOT):
        rel = os.path.relpath(base, ROOT)
        if rel == ".":
            dirs[:] = [d for d in dirs if d == ".claude"
                       or not (d.startswith(".") or d == "chiprun_out")]
        found.extend(os.path.normpath(os.path.join(rel, f)) for f in files)
    return found


def live_lines(text):
    """The document's lines outside history sections, as
    `(line_number, line, inside_a_code_fence)`."""
    out, level, exempt, fenced = [], 0, None, False
    for no, line in enumerate(text.splitlines(), 1):
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        m = None if fenced else _HEADING.match(line)
        if m:
            level = len(m.group(1))
            if exempt is not None and exempt >= 2 and level <= exempt:
                exempt = None
        if not fenced and _HISTORY.match(line):
            exempt = level
        if exempt is None:
            out.append((no, line, fenced))
    return out


def named_paths(text):
    """`(line_number, name)` of every path a live line links, ticks or
    (in a code fence) runs."""
    names = []
    for no, line, fenced in live_lines(text):
        for name in (_RUN.findall(line) if fenced else
                     _TICKED.findall(line) + _LINKED.findall(line)):
            if (re.search(r"[<*{]|^https?:", name)
                    or name.startswith(UPSTREAM_PREFIXES)
                    or name in RUNTIME_FILES):
                continue
            names.append((no, name))
    return names


def missing_paths(doc, text, files):
    """Names of `text` (the document at `doc`) that no file answers to."""
    here = os.path.dirname(doc)
    bad = []
    for no, name in named_paths(text):
        # from the root, from the package, or beside the document
        tails = tuple("/" + os.path.normpath(n)
                      for n in (name, os.path.join(here, name)))
        if not any(("/" + f).endswith(tails) for f in files):
            bad.append(f"{doc}:{no}: {name}")
    return bad


@pytest.fixture(scope="module")
def files():
    return _tree_files()


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_files_that_exist(doc, files):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    assert missing_paths(doc, text, files) == []


def test_a_file_that_is_gone_named_in_a_document_is_caught(files):
    """The check itself: a live line naming a file that is gone fails,
    the same line under a history marker does not, and the marker's
    reach ends at the next heading of its section's level."""
    gone = "the gate was `old_gate.py` and [its record](OLD_RECORD.json)\n"
    assert missing_paths("README.md", gone, files) == [
        "README.md:1: old_gate.py", "README.md:1: OLD_RECORD.json"]
    assert missing_paths(
        "README.md", "# T\n> **History:** before PR 21.\n" + gone,
        files) == []
    text = ("# T\n## Old\n> **History:** before PR 21.\n" + gone
            + "### Older\n" + gone + "## Now\n" + gone
            + "```\nOLD_GATE=1 python old_gate.py  # t.json\n```\n"
            + "see `tests/test_docs_paths.py:12`, `obs/slo.py::evaluate`,"
              " `manifest_<host>.json`, `paddle/phi/api.py`\n")
    assert missing_paths("docs/x.md", text, files) == [
        "docs/x.md:8: old_gate.py", "docs/x.md:8: OLD_RECORD.json",
        "docs/x.md:10: old_gate.py"]
