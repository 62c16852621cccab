"""Which package of corda_tpu may import which.

The table records the package-to-package imports as they stand (read from the
source with ``ast``, relative imports resolved, imports inside functions
included). It judges only one of them: ``observability`` is a leaf over
``utils``, because ``consensus``, ``flows``, ``node``, ``ops``, ``parallel``,
``utils`` and ``verifier`` all import it. A change that adds an arrow has to
add it here, in view of its reviewer.
"""
import ast
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parents[1] / "corda_tpu"

ALLOWED = {
    "client": {"core", "network", "node"},
    "consensus": {"core", "network", "node", "observability", "storage",
                  "utils"},
    "core": {"ops"},
    "experimental": {"core"},
    "finance": {"core", "flows", "node"},
    "flows": {"core", "node", "observability"},
    "network": {"core", "node", "utils"},
    "node": {"core", "flows", "network", "observability", "ops", "parallel",
             "storage", "utils", "verifier"},
    "observability": {"utils"},
    "ops": {"core", "observability"},
    "parallel": {"core", "observability", "ops"},
    "samples": {"consensus", "core", "finance", "flows", "node", "testing"},
    "storage": {"utils"},
    # finance: testing/trader_ledger.py builds the trader-demo ledger by the
    # Cash and CommercialPaper contracts' own generate_* helpers (PR 49)
    "testing": {"client", "core", "finance", "flows", "network", "node",
                "utils"},
    # ops, utils: tools/fieldsteps.py times the field layer's primitives and
    # kernels on the chip and reads their optimised HLO without one (PR 32)
    "tools": {"client", "core", "finance", "flows", "node", "observability",
              "ops", "testing", "utils"},
    "utils": {"observability"},
    "verifier": {"core", "network", "observability", "ops", "parallel",
                 "utils"},
}
#: what runs the package from outside; nothing inside it may know them
OUTSIDE = {"benchmarks", "chip_smoke", "tests"}


def imported_modules(path: pathlib.Path):
    """Absolute dotted names of everything ``path`` imports."""
    module = list(path.relative_to(PACKAGE.parent).with_suffix("").parts)
    package = module[:-1]       # an __init__'s own package, a module's parent
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and not node.level:
            yield node.module
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1]
            if node.module:
                yield ".".join(base + node.module.split("."))
            else:               # from .. import name
                yield from (".".join(base + [a.name]) for a in node.names)


@pytest.fixture(scope="module")
def edges():
    """{package: {imported package: {files that import it}}}, and the
    imports of OUTSIDE."""
    found = {name: {} for name in ALLOWED}
    outward = []
    for path in sorted(PACKAGE.rglob("*.py")):
        rel = path.relative_to(PACKAGE)
        owner = rel.parts[0] if len(rel.parts) > 1 else None
        for name in imported_modules(path):
            parts = name.split(".")
            if parts[0] in OUTSIDE:
                outward.append(f"{rel}: {name}")
            if parts[0] != "corda_tpu" or len(parts) < 2 or owner is None:
                continue
            if parts[1] != owner and (PACKAGE / parts[1]).is_dir():
                found.setdefault(owner, {}).setdefault(parts[1], set()) \
                    .add(str(rel))
    return found, outward


def test_the_table_names_every_package():
    on_disk = {p.name for p in PACKAGE.iterdir()
               if p.is_dir() and (p / "__init__.py").is_file()}
    assert on_disk == set(ALLOWED)


@pytest.mark.parametrize("package", sorted(ALLOWED))
def test_package_imports_only_what_the_table_allows(package, edges):
    found, _ = edges
    extra = {dep: sorted(files) for dep, files in found[package].items()
             if dep not in ALLOWED[package]}
    assert not extra, f"{package} imports {extra}: not in ALLOWED"
    # an arrow that has gone comes out of the table too
    assert set(found[package]) == ALLOWED[package]


def test_nothing_in_the_package_imports_what_runs_it(edges):
    _, outward = edges
    assert outward == []
