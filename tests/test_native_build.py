"""Native build tooling: the .so and the Python-side ABI gate move together.

Rebuilds libscalarmath.so from source (into a tmpdir — the committed .so
is never touched) when a C++ compiler is present and asserts sm_version()
matches scalarprep.SM_VERSION, so a version bump that forgets one side of
the gate fails in tier-1 instead of silently falling back to the Python
prep on every deployment.  Skips LOUDLY (with the rebuild recipe) when no
compiler is available.
"""
import ctypes
import inspect
import os
import pathlib
import re
import shutil
import subprocess

import pytest

from corda_tpu.ops import scalarprep as sp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "native", "scalarmath.cpp")

RECIPE = ("rebuild with: make -C native libscalarmath.so "
          f"(needs sm_version() == {sp.SM_VERSION}, "
          "the gate in corda_tpu/ops/scalarprep.py)")


def _version_of(path: str) -> int:
    lib = ctypes.CDLL(path)
    lib.sm_version.restype = ctypes.c_int
    return int(lib.sm_version())


def test_rebuilt_so_version_matches_python_gate(tmp_path):
    cxx = (shutil.which(os.environ.get("CXX", "g++"))
           or shutil.which("c++") or shutil.which("clang++"))
    if cxx is None:
        pytest.skip(f"no C++ compiler on PATH — cannot rebuild; {RECIPE}")
    out = tmp_path / "libscalarmath.so"
    # -O0: this is an ABI check, not a perf build — keeps the test seconds
    proc = subprocess.run(
        [cxx, "-O0", "-fPIC", "-shared", "-std=c++17", SRC, "-o", str(out)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert _version_of(str(out)) == sp.SM_VERSION, RECIPE


def test_committed_so_version_matches_python_gate():
    built = [p for p in sp._CANDIDATES if os.path.exists(p)]
    if not built:
        pytest.skip(f"libscalarmath.so not built in this checkout; {RECIPE}")
    for path in built:
        assert _version_of(path) == sp.SM_VERSION, (path, RECIPE)
    # and the loader actually accepted it (no silent Python fallback)
    assert sp.available(), RECIPE


def test_every_export_is_bound_and_used():
    """What scalarmath.cpp exports is what ``_bind`` binds, and what it
    binds something in the package calls: an export whose ladder went, or a
    binding nobody reads, fails here and not in a reviewer's ``grep``."""
    source = pathlib.Path(SRC).read_text()
    block = source[source.index('extern "C" {'):]
    exports = set(re.findall(r"^int (sm_\w+)\(", block, flags=re.M))
    bind = inspect.getsource(sp._bind)
    bound = set(re.findall(r"\.(sm_\w+)", bind))
    assert exports - {"sm_version"} == bound
    package = pathlib.Path(REPO, "corda_tpu")
    rest = "".join(p.read_text() for p in sorted(package.rglob("*.py")))
    assert bind in rest
    called = set(re.findall(r"\.(sm_\w+?)(?:_held)?\b",
                            rest.replace(bind, "")))
    assert bound <= called, sorted(bound - called)
