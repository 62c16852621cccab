"""Every command a document shows names something that is in the tree:
each ``python[3] <path>.py`` is a file of the repo, each ``python -m
corda_tpu.<module>`` a module (or a package with a ``__main__``)."""
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCUMENTS = ["README.md", "docs/OBSERVABILITY.md", "docs/PERFORMANCE.md",
             "docs/ROBUSTNESS.md", "docs/DEPLOYMENT.md"]
SCRIPT = re.compile(r"\bpython3?\s+([A-Za-z0-9_./-]+\.py)\b")
MODULE = re.compile(r"\bpython3?\s+-m\s+(corda_tpu(?:\.[A-Za-z0-9_]+)+)")


def module_exists(dotted: str) -> bool:
    path = REPO.joinpath(*dotted.split("."))
    return path.with_suffix(".py").is_file() \
        or (path / "__main__.py").is_file()


@pytest.mark.parametrize("document", DOCUMENTS)
def test_commands_shown_resolve_to_files(document):
    text = (REPO / document).read_text()
    missing = sorted(
        {s for s in SCRIPT.findall(text) if not (REPO / s).is_file()}
        | {m for m in MODULE.findall(text) if not module_exists(m)})
    assert not missing, f"{document} shows commands for {missing}"
