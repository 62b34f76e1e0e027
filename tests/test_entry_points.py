"""The repo's entry points and the documents that describe them name only
what exists: every Makefile target's recipe, every path or ``make``
target a document puts in backticks, and no rate in README that the
ledger does not hold (a rate comes from ``chipbench/run.py`` on the chip,
recorded in ``PERF_LEDGER.jsonl``; README names the cells and states
none)."""
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TARGETS = ("smoke", "test", "test-fast", "verify-fast", "lint-graph",
           "obs-check", "health-check", "aot-check", "cluster-check",
           "chaos-check", "durability-check", "sp-check")
DOCUMENTS = ("README.md", ".claude/skills/verify/SKILL.md")


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _makefile():
    """{target: (prerequisites, recipe text)} and the .PHONY names."""
    text = _read("Makefile").replace("\\\n", " ")
    rules, phony, current = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^([A-Za-z][\w.-]*|\.PHONY):(?!=)(.*)$", line)
        if head and head.group(1) == ".PHONY":
            phony += head.group(2).split()
            current = None
        elif head:
            current = head.group(1)
            rules[current] = (head.group(2).split(), [])
        elif line.startswith("\t") and current:
            rules[current][1].append(line)
        elif line.strip() and not line.startswith("#"):
            current = None
    return {t: (pre, "\n".join(rec)) for t, (pre, rec) in rules.items()}, phony


def _tree():
    """Every file and directory of the checkout, relative, '/'-separated;
    hidden directories other than .claude and what .gitignore lists as a
    directory (run-time output) are left out."""
    ignored = {ln.strip().rstrip("/") for ln in _read(".gitignore").split()
               if ln.strip().endswith("/")}
    files, dirs = set(), set()
    for root, ds, fs in os.walk(REPO):
        rel = os.path.relpath(root, REPO).replace(os.sep, "/")
        ds[:] = [d for d in ds if d not in ignored
                 and (not d.startswith(".") or d == ".claude")]
        prefix = "" if rel == "." else rel + "/"
        dirs.update(prefix + d for d in ds)
        files.update(prefix + f for f in fs)
    return files, dirs, ignored


def _resolves(token, names):
    return any(n == token or n.endswith("/" + token) for n in names)


@pytest.mark.parametrize("target", TARGETS)
def test_make_target_names_only_what_exists(target):
    rules, phony = _makefile()
    assert target in rules, f"Makefile has no target {target!r}"
    assert target in phony
    prerequisites, recipe = rules[target]
    for needed in prerequisites + re.findall(r"\$\(MAKE\)\s+([\w-]+)", recipe):
        assert needed in rules, f"{target}: {needed!r} is not a target"
    for path in re.findall(r"(?<![\w./-])([\w./-]+(?:\.py|/))(?![\w.-])",
                           recipe):
        assert os.path.exists(os.path.join(REPO, path)), \
            f"{target}: {path} does not exist"


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_paths_that_exist(document):
    files, dirs, ignored = _tree()
    targets, _ = _makefile()
    missing = []
    for quoted in re.findall(r"`([^`\n]+)`", _read(document)):
        for target in re.findall(r"\bmake ([a-z][\w-]*)", quoted):
            if target not in targets:
                missing.append(f"make {target}")
        for word in quoted.split():
            word = word.strip("(),;:\"'")
            if not re.fullmatch(r"[\w./-]+", word):
                continue                  # a glob, a placeholder, a URL's ~
            if word.split("/")[0] in ignored:
                continue                  # run-time output, not committed
            if word.endswith("/"):
                if not _resolves(word.rstrip("/"), dirs):
                    missing.append(word)
            elif re.search(r"\.(py|json|md)$", word):
                if not _resolves(word, files):
                    missing.append(word)
    assert not missing, f"{document} names what does not exist: {missing}"


RATE = re.compile(r"tok/s|imgs/s|seq/s|samples/s|MFU 0\.|\b\d+\.\d+x\b")


def test_readme_states_no_rate_outside_the_ledger():
    cells = [w["name"] for w in json.loads(_read("BENCHMARK.json"))["workloads"]]
    assert len(cells) >= 3
    readme = _read("README.md")
    for cell in cells:
        assert cell in readme, f"README does not name the cell {cell}"
    stray = []
    for paragraph in re.split(r"\n\s*\n", readme):
        if any(cell in paragraph for cell in cells):
            continue
        stray += [ln for ln in paragraph.splitlines() if RATE.search(ln)]
    assert not stray, "README states a rate no cell of the ledger holds:\n" \
        + "\n".join(stray)
