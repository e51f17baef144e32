"""The README and the API reference name only files that exist.

One case a document.  A back-ticked token that is a repository path must
be a file of the checkout, and a ``python <script>.py`` command must name
one; a document that still quotes a removed script or record fails
here, in the PR that removed it.  Reads files, runs nothing.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "synapseml_tpu"

#: never part of a checkout (.gitignore)
_SKIP_DIRS = {".git", ".jax_cache", "chiprun_out", ".proof", "__pycache__",
              ".pytest_cache", ".hypothesis"}

#: names the program writes while it runs (a gang's post-mortem bundle, its
#: stitched trace, the tuning table): the documents name them, no checkout
#: holds them
WRITTEN_AT_RUN_TIME = {"postmortem.json", "gang_trace.json", "tunetable.json"}

DOCUMENTS = ["README.md"] + sorted(
    "docs/api/" + name
    for name in os.listdir(os.path.join(REPO, "docs", "api"))
    if name.endswith(".md"))

_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"[\w./-]+\.(?:py|json|md|jsonl)")
_COMMAND = re.compile(r"python3?\s+([\w./-]+\.py)\b")
#: what may follow a path inside one span: ``:12``, ``:12-40,55`` or
#: ``::TestClass::test_name[case]``
_SUFFIX = re.compile(r"(?:::[\w\[\]\-.:]+|:\d+(?:-\d+)?(?:,\d+(?:-\d+)?)*)$")


@functools.lru_cache(maxsize=None)
def _checkout():
    """(every file of the checkout, relative to its root; the basenames)."""
    files = set()
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        for name in names:
            files.add(os.path.relpath(os.path.join(root, name), REPO)
                      .replace(os.sep, "/"))
    return files, {os.path.basename(f) for f in files}


def _resolve(token, files, basenames):
    """The one rule that tells a repository path from the rest → the
    path the token must exist as, or None where it claims no file here.

    A repository path is a run of word characters, dots, slashes and
    dashes ending in ``.py``, ``.json``, ``.md`` or ``.jsonl`` whose first
    component is a top-level directory of the checkout or a subpackage of
    ``synapseml_tpu`` (the documents write ``models/llm/slots.py`` for
    short), or that has no directory at all (then any file of that name
    counts, so ``slots.py`` may stand for its module).  Whatever holds
    ``<``, ``*``, ``{`` or ``...`` is a placeholder, and a path under any
    other first component cites the reference's tree
    (``cognitive/.../OpenAI.scala`` is neither)."""
    token = _SUFFIX.sub("", token.strip())
    if not _PATH.fullmatch(token):
        return None
    token = token[2:] if token.startswith("./") else token
    if "/" not in token:
        if token in WRITTEN_AT_RUN_TIME:
            return None
        return token if token in basenames else "<no file named %s>" % token
    top = token.split("/", 1)[0]
    if any(f.startswith(top + "/") for f in files):
        return token
    if any(f.startswith("%s/%s/" % (PACKAGE, top)) for f in files):
        return "%s/%s" % (PACKAGE, token)
    return None


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    files, basenames = _checkout()
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    # spans, and the scripts of commands wherever they stand (a fenced
    # block's lines are commands too)
    named = _SPAN.findall(text) + _COMMAND.findall(text)
    missing = []
    for token in named:
        path = _resolve(token, files, basenames)
        if path is not None and path not in files \
                and path not in basenames:
            missing.append((token, path))
    assert not missing, (
        f"{document} names files that are not in the checkout: {missing}")
