"""By hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests`` (not
part of the repo's tier-1 tests). Puts benchmarks/ and the repo on the path
the way ``python3 benchmarks/run.py`` does."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
