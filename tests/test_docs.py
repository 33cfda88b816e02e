"""The documents name only files that exist: README.md and docs/*.md
send the reader to paths and file names, and a file that goes takes its
mentions with it."""

import glob
import os
import re

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")

DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: roots under which a back-quoted slash path must exist
ROOTS = ("t2omca_tpu/", "tests/", "benchmark/", "configs/", "scripts/",
         "docs/")

#: the reference implementation's files (SURVEY.md cites them, and
#: docs/MIGRATION.md its ``main.py`` entry point); the documents name
#: them to say what a module was ported from
REFERENCE_FILES = frozenset({
    "main.py", "per_run.py", "parallel_runner.py", "environment_multi_mec.py",
    "transf_agent.py", "n_transf_mixer.py", "transformer.py",
    "normalization.py",
})

#: what git does not commit: a scratch copy of an older tree there must
#: not vouch for a file this tree has lost
_SKIP_DIRS = {".git", "chiprun_out", "archive_check", "__pycache__",
              ".jax_cache", ".native_build"}


@pytest.fixture(scope="module")
def basenames():
    names = set()
    for d, dirs, files in os.walk(REPO):
        dirs[:] = [x for x in dirs if x not in _SKIP_DIRS]
        names.update(f for f in files if f.endswith(".py"))
    return names


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc, basenames):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    tokens = set()
    for quoted in re.findall(r"`([^`\n]+)`", text):
        tokens.update(quoted.split())
    assert tokens, f"{doc}: no back-quoted token found — scan broken?"
    missing = []
    for tok in sorted(tokens):
        if any(c in tok for c in "*<{"):
            continue
        tok = tok.split("::")[0].rstrip(".,;:)")
        tok = re.sub(r":\d+(-\d+)?$", "", tok)
        if tok.startswith(ROOTS):
            if not os.path.exists(os.path.join(REPO, tok)):
                missing.append(tok)
        elif re.fullmatch(r"\w+\.py", tok):
            if tok not in basenames and tok not in REFERENCE_FILES:
                missing.append(tok)
    assert not missing, f"{doc} names files that do not exist: {missing}"
