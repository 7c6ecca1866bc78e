"""The documents a new owner reads first name files and flags that exist.

``README.md``, ``MIGRATING.md`` and ``PARITY.md`` send their reader to
files by path. A path inside backticks that ends in ``.py``, ``.sh`` or
``.json`` has to resolve among the files git tracks (the files under the
code directories, where the checkout has no ``.git``): from the checkout's
root, or from the package (``serving/decode.py`` is
``paddle_tpu/serving/decode.py``, as the layout table writes it); failing
both, a bare file name has to be the name of exactly one file, and where
several share it the document has to write the path. Deleting a file then
fails here until the sentences that cite it are rewritten. A path with a
placeholder in it (``<cell>``, ``*``) names no one file and is not read.
"""

import collections
import dataclasses
import os
import re
import subprocess

import pytest

from paddle_tpu.core.config import Flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "paddle_tpu")
# where the checkout has no .git: the directories that hold code. A checkout
# unpacked beside them (``_checkout/``, ``.archive/``) must not make a
# deleted file resolve
CODE_DIRS = ("paddle_tpu", "benchmarks", "tools", "tests", "examples", "csrc")
PATH = re.compile(r"(?<![\w./<>*{}-])[\w./-]+\.(?:py|sh|json)(?![\w<>*{}/-])")


def _tracked_files():
    """Paths from the root, as ``git ls-files`` writes them: what a run
    left behind (``chiprun_out/``, ``.bench_cache/``) is not in the tree."""
    try:
        listed = subprocess.run(
            ["git", "-C", ROOT, "ls-files"], capture_output=True, text=True, timeout=60)
        if listed.returncode == 0 and listed.stdout.strip():
            # a file deleted and not yet staged is still listed
            return {path for path in listed.stdout.splitlines()
                    if os.path.isfile(os.path.join(ROOT, path))}
    except (OSError, subprocess.SubprocessError):
        pass
    paths = {f for f in os.listdir(ROOT) if os.path.isfile(os.path.join(ROOT, f))}
    for top in CODE_DIRS:
        for at, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            paths.update(os.path.relpath(os.path.join(at, f), ROOT).replace(os.sep, "/")
                         for f in files)
    return paths


def _cited_paths(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    return sorted({path for span in re.findall(r"`([^`\n]+)`", text)
                   for path in PATH.findall(span)})


@pytest.mark.parametrize("document", ["README.md", "MIGRATING.md", "PARITY.md"])
def test_every_path_a_document_cites_resolves(document):
    tracked = _tracked_files()
    named = collections.Counter(path.rsplit("/", 1)[-1] for path in tracked)
    cited = _cited_paths(document)
    assert cited, f"{document} cites no path: the pattern no longer reads it"
    unplaced = [path for path in cited
                if path not in tracked and "paddle_tpu/" + path not in tracked]
    missing = [path for path in unplaced if "/" in path or not named[path]]
    assert not missing, f"{document} cites files that are not in the tree: {missing}"
    ambiguous = [path for path in unplaced if named[path] > 1]
    assert not ambiguous, f"{document} cites names several files share; write the path: {ambiguous}"


def test_every_flag_the_readme_names_is_read_by_the_package():
    """``PADDLE_TPU_<NAME>`` is a field of ``core.config.Flags`` (read by
    ``Flags.from_env``) or a variable some module of the package reads by
    that name."""
    with open(os.path.join(ROOT, "README.md")) as f:
        named = set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", f.read()))
    assert len(named) >= 10
    fields = {"PADDLE_TPU_" + f.name.upper() for f in dataclasses.fields(Flags)}
    source = []
    for at, dirs, files in os.walk(PACKAGE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(at, name)) as f:
                    source.append(f.read())
    source = "\n".join(source)
    unread = sorted(n for n in named - fields if n not in source)
    assert not unread, f"README.md names flags nothing reads: {unread}"
