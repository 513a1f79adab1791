"""What a deletion leaves dangling: every script still parses its arguments,
every module of the package still imports, and the two documents a newcomer
follows (README.md, the verify skill) name only files that exist."""

import fnmatch
import importlib
import os
import pkgutil
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = sorted(f for f in os.listdir(os.path.join(REPO, "scripts")) if f.endswith(".py"))


@pytest.mark.parametrize("target", SCRIPTS + ["pretraining_llm_tpu"])
def test_entry_point_imports_and_parses(target):
    if target == "pretraining_llm_tpu":
        import pretraining_llm_tpu

        names = [
            m.name
            for m in pkgutil.walk_packages(pretraining_llm_tpu.__path__, "pretraining_llm_tpu.")
        ]
        assert len(names) > 50
        for name in names:
            importlib.import_module(name)
        return
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", target), "--help"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if target == "supervisor.py":  # wants its child command after "--"
        assert r.returncode == 2 and "missing '-- <command ...>'" in r.stderr, r.stderr[-2000:]
        return
    assert r.returncode == 0, r.stderr[-2000:]
    assert "usage:" in r.stdout


def _ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        lines = [ln.strip() for ln in f]
    return [ln.rstrip("/") for ln in lines if ln and not ln.startswith("#")]


def _is_ignored(path, patterns):
    """``path`` or a directory above it is something .gitignore names: made at
    run time, so not expected in a checkout."""
    parts = path.rstrip("/").split("/")
    prefixes = ["/".join(parts[: i + 1]) for i in range(len(parts))]
    return any(fnmatch.fnmatch(p, pat) for p in prefixes + parts for pat in patterns)


_PATH = re.compile(r"^[\w.*-]+(/[\w.*-]+)*/?$")
_SOURCE_END = re.compile(r"\.(py|md|sh|cpp|h)$")


@pytest.mark.parametrize("doc", ["README.md", ".claude/skills/verify/SKILL.md"])
def test_documented_paths_exist(doc):
    """Every backticked repository path (a name under one of the checkout's
    directories, or ending like a source file or a document; an optional
    ``::symbol`` or ``:line`` after it; in a command line, the script) is in
    the checkout: at the root, inside the package, or, for a bare file name,
    anywhere. What .gitignore lists is made at run time and not held to it."""
    patterns = _ignored()
    files, basenames = set(), set()
    for root, dirs, names in os.walk(REPO):
        rel = os.path.relpath(root, REPO)
        rel = "" if rel == "." else rel + "/"
        dirs[:] = [d for d in dirs if d != ".git" and not _is_ignored(rel + d, patterns)]
        files.update(rel + d for d in dirs)
        files.update(rel + n for n in names)
        basenames.update(names)
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    fenced = re.findall(r"^```.*?^```", text, flags=re.S | re.M)
    spans = [ln for block in fenced for ln in block.splitlines()[1:-1]]
    spans += re.findall(r"`([^`\n]+)`", re.sub(r"^```.*?^```", "", text, flags=re.S | re.M))
    top = {f.split("/")[0] for f in files} | {
        f.split("/")[1] for f in files if f.startswith("pretraining_llm_tpu/")
    }
    missing, checked = [], 0
    for span in spans:
        words = span.split()
        if not words:
            continue
        # a command line names its script: `python scripts/train.py --preset tiny`
        launchers = [i for i, w in enumerate(words[:-1]) if w in ("python", "python3", "bash")]
        for word in [words[i + 1] for i in launchers] or words[:1]:
            path = re.sub(r"(::[\w.]+|:\d+([-–]\d+)?)+$", "", word).rstrip("/")
            if not _PATH.match(path) or path.startswith("-"):
                continue
            if not (_SOURCE_END.search(path) or ("/" in path and path.split("/")[0] in top)):
                continue
            if "*" in path or _is_ignored(path, patterns):
                continue
            checked += 1
            if not (
                path in files
                or "pretraining_llm_tpu/" + path in files
                or ("/" not in path and path in basenames)
            ):
                missing.append(span)
    assert checked >= 10, f"{doc}: only {checked} paths recognised"
    assert not missing, f"{doc} names paths that do not exist: {sorted(set(missing))}"
