"""The documents name only what exists: every repo path a document puts
in back-ticks is a file or directory of this checkout, and every
``LO_TPU_*`` name it mentions is one ``config.py`` or a file under
``deploy/`` reads. (The other direction — every knob ``config.py`` names
is documented — is lolint's ``env-discipline`` rule.) Paths into the
reference (``microservices/…``) are out of scope."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: Directories whose back-ticked paths must exist.
_ROOTS = ("learningorchestra_tpu/", "tests/", "perfbench/", "tools/",
          "deploy/", "docs/")
#: A bare ``name.py`` / ``name.md`` / ``name.json`` is a top-level file.
_TOP_LEVEL = re.compile(r"[\w.-]+\.(?:py|md|json)")
#: Files the program writes at run time (a dataset's directory, a
#: flight-recorder bundle): documented truthfully, in no checkout.
_RUNTIME_FILES = {"metadata.json", "manifest.json", "spans.json",
                  "history.json", "resources.json", "alerts.json",
                  "metrics.json"}
_PLACEHOLDER = re.compile(r"[<>*{}…]|\.\.\.")
_KNOB = re.compile(r"LO_TPU_[A-Z0-9_]+")


def _named_paths(text):
    """The repo paths ``text`` names in back-ticks, each without its
    ``:line``, ``::test`` or ``:function`` suffix."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.strip("(),;'\"").split(":", 1)[0]
            if _PLACEHOLDER.search(word):
                continue
            if word.startswith(_ROOTS) or (
                    _TOP_LEVEL.fullmatch(word)
                    and word not in _RUNTIME_FILES):
                yield word.rstrip("/.")


@pytest.fixture(scope="module")
def known_knobs():
    sources = [os.path.join(REPO, "learningorchestra_tpu", "config.py")]
    sources += [p for p in glob.glob(os.path.join(REPO, "deploy", "**"),
                                     recursive=True) if os.path.isfile(p)]
    names = set()
    for path in sources:
        with open(path, encoding="utf-8") as fh:
            names.update(_KNOB.findall(fh.read()))
    return names


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc, known_knobs):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    here = os.path.dirname(os.path.join(REPO, doc))
    missing = sorted({
        p for p in _named_paths(text)
        if not os.path.exists(os.path.join(REPO, p))
        and not ("/" not in p and os.path.exists(os.path.join(here, p)))})
    # ``LO_TPU_TUNE_*`` is written as a prefix: some knob must carry it.
    unread = sorted(
        k for k in set(_KNOB.findall(text))
        if not (any(n.startswith(k) for n in known_knobs)
                if k.endswith("_") else k in known_knobs))
    assert not (missing or unread), (
        f"{doc} names paths that do not exist: {missing}; LO_TPU_* names "
        f"that config.py and deploy/ do not read: {unread}")
