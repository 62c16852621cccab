"""The benchmark's own tests (``benchmarks/tests/test_*.py``), collected by
tier-1's ``pytest tests/``: the yardstick the driver measures every PR with is
held by every PR. Each file is loaded as ``benchmarks/tests/conftest.py``
would have it found (benchmarks/ and the repo on the path) and its ``test_*``
callables, ``parametrize`` marks and all, are lifted into this module.
"""
import importlib.util
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: fails on the parent: it pins 11 latejoin metric files and PR 28 made them
#: 12. The file is the benchmark's, so a `benchmark` issue repairs it
#: (PERF.md section 7) and then takes this line out.
EXCLUDED = {("test_latejoin", "test_the_cell_has_its_files")}

for _path in sorted((BENCH / "tests").glob("test_*.py")):
    _spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{_path.stem}", _path)
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    for _name, _fn in vars(_module).items():
        if _name.startswith("test_") and callable(_fn) \
                and (_path.stem, _name) not in EXCLUDED:
            globals()[f"{_path.stem}__{_name}"] = _fn
