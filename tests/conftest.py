"""Test configuration: force a virtual 8-device CPU mesh before JAX initialises.

Mirrors the reference's deterministic in-process multi-node testing strategy
(MockNetwork, reference test-utils/.../node/MockNode.kt:41-66): we test multi-chip
sharding without real chips by asking XLA for 8 host-platform devices.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU backend with 8 virtual devices.
import jax

jax.config.update("jax_platforms", "cpu")

import pathlib

from corda_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()

# Build the native engines (kvlog, raftcore) when a compiler is available so
# the suite exercises the C++ paths, not just the Python fallbacks. Import
# happens after this, so the ctypes loaders see fresh .so files.
import subprocess

_native_dir = pathlib.Path(__file__).resolve().parent.parent / "native"
try:
    _mk = subprocess.run(["make", "-C", str(_native_dir)],
                         capture_output=True, timeout=120, check=False)
    if _mk.returncode != 0:
        # a toolchain exists but the build BROKE: surface it loudly instead
        # of letting skipif markers turn native coverage into silent skips
        import sys
        print("NATIVE BUILD FAILED:\n" + _mk.stderr.decode(errors="replace"),
              file=sys.stderr)
except (OSError, subprocess.TimeoutExpired):
    pass  # no toolchain: fallbacks cover the formats

# Chaos reproducibility: when a fault-injection test fails, print the seed
# that drove its injector so the red run reproduces verbatim
# (CORDA_TPU_FAULT_SEED=<seed> pytest <nodeid>). The hookwrapper sees the
# report AFTER the test body ran but while the injector may still be armed
# (inject() disarms in its finally, which runs inside the call phase — so
# the test itself stashes the seed on the item via the chaos_seed fixture
# or we read the param).
import pytest


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if report.when != "call" or not report.failed:
        return
    if item.get_closest_marker("chaos") is None:
        return
    from corda_tpu.utils import faults as _faults
    inj = _faults.active()
    seed = inj.seed if inj is not None else item.funcargs.get("seed")
    if seed is not None:
        report.sections.append((
            "chaos seed",
            f"fault seed {seed} — reproduce with "
            f"CORDA_TPU_FAULT_SEED={seed} pytest {item.nodeid!r}"))
