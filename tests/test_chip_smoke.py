"""chip_smoke.py rehearsed on the CPU backend (on-chip-measurement guide §2,
first and second rehearsal): the script itself must FAIL here, its phase
functions must pass at tiny sizes, and its checks must fail a phase when a
device failure is swallowed by the batcher's host fallback.

Only (scheme, bucket) pairs other tests already compile are used: Ed25519
at the 16 bucket on one device, and at 64 rows over the 8-device mesh.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from corda_tpu.testing.faults import FaultRule, inject  # noqa: E402
from corda_tpu.utils import compile_cache  # noqa: E402
from corda_tpu.utils.metrics import MetricRegistry  # noqa: E402
from corda_tpu.verifier.batcher import SignatureBatcher  # noqa: E402
from corda_tpu.verifier.service import TpuTransactionVerifierService  # noqa: E402


def _device_route_service(**batcher_kwargs):
    """A default service would host-route a 16-row batch (crossover 192)."""
    registry = MetricRegistry()
    return TpuTransactionVerifierService(
        metrics=registry,
        batcher=SignatureBatcher(metrics=registry, host_crossover=0,
                                 **batcher_kwargs))


# -- the script as the driver runs it ------------------------------------------

@pytest.mark.parametrize("extra", [[], ["--chips", "4"]])
def test_script_fails_without_a_tpu(extra):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *extra],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert '"ok": true' not in out.stdout
    last = json.loads(lines[-1])
    assert last["phase"] == "device" and last["ok"] is False
    assert "no TPU" in last["error"]


def test_script_has_no_option_to_pass_on_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--help"],
        capture_output=True, text=True, timeout=60)
    options = {w.rstrip(",") for w in out.stdout.split()
               if w.startswith("--")}
    assert options == {"--help", "--chips", "--seed"}


def test_run_phases_stops_at_first_failure_and_never_says_ok(capsys):
    ran = []

    def boom():
        raise chip_smoke.PhaseFailed("refused")

    device = chip_smoke.run_phases([
        ("device", lambda: {"platform": "tpu", "kind": "k", "count": 1}),
        ("native", boom),
        ("kernels", lambda: ran.append("kernels") or {})])
    assert device is None and ran == []
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["ok"] for x in lines] == [True, False]
    assert lines[1]["phase"] == "native" and lines[1]["error"] == "refused"


def test_deadline_ends_a_hung_run_with_a_failure_line():
    code = ("import time, chip_smoke; chip_smoke.DEADLINE_S = 0.3; "
            "chip_smoke.run_phases = lambda phases: time.sleep(60); "
            "chip_smoke.main([])")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=30)
    assert out.returncode == 1
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"phase": "deadline", "ok": False,
                    "error": "not finished after 0.3 s"}


def test_native_phase_fails_when_the_build_fails(monkeypatch, tmp_path):
    (tmp_path / "native").mkdir()
    (tmp_path / "native" / "Makefile").write_text("all:\n\tfalse\n")
    monkeypatch.setattr(chip_smoke, "ROOT", tmp_path)
    for mod in ("corda_tpu.ops.scalarprep", "corda_tpu.storage.kvstore",
                "corda_tpu.consensus.raftcore"):
        monkeypatch.delitem(sys.modules, mod, raising=False)
    with pytest.raises(chip_smoke.PhaseFailed, match="make -C native"):
        chip_smoke.phase_native()


# -- phases at tiny sizes ------------------------------------------------------------

def test_kernels_phase_tiny():
    out = chip_smoke.phase_kernels(
        seed=0, rows=16, unique=8, schemes=("ed25519",), corrupt_every=5,
        merkle_txs=4, service=_device_route_service())
    assert out["DeviceChecked"] == 16 and out["HostRouted"] == 0
    assert out["corrupted_rows_per_scheme"] == 4
    assert out["secp256r1"] == "not run"


def test_service_phase_tiny_lands_on_the_warm_bucket():
    # warm the 16 bucket the way the script's kernels phase does
    chip_smoke.phase_kernels(
        seed=1, rows=16, unique=8, schemes=("ed25519",), corrupt_every=5,
        merkle_txs=4, service=_device_route_service())
    out = chip_smoke.phase_service(seed=1, n_tx=16, distinct=4, bad=(1,))
    assert out["transactions"] == 16 and out["bad_transactions"] == 4
    assert out["DeviceChecked"] == 16 and out["HostRouted"] == 0
    assert out["compiles_since_warm"] == 0


def test_ledger_phase_prints_the_device_host_split():
    from ledger_cell import TINY
    out = chip_smoke.phase_ledger(seed=0, seconds=2.0, scale=TINY)
    assert out["correct"] and out["compiles_since_warm"] == 0
    assert out["ops_committed"] > 0 and out["exactly_once_ok"]
    # finding 3 of ISSUE 22: at today's thresholds the served path never
    # reaches the device — printed, not hidden
    assert out["DeviceChecked"] == 0 and out["HostRouted"] > 0


def test_mesh_phase_tiny_on_virtual_devices():
    from corda_tpu.parallel import make_mesh
    service = _device_route_service(mesh=make_mesh(8))
    out = chip_smoke.phase_mesh(seed=0, rows=48, unique=6, n_chips=8,
                                corrupt_every=5, leaves=64, service=service)
    assert out["DeviceChecked"] == 48 and out["HostRouted"] == 0
    assert len(out["shard_devices"]) == 8


# -- the checks themselves -------------------------------------------------------------

def test_counter_check_fails_the_phase_when_the_host_fallback_fired():
    """A device dispatch that raises is verified on the host with correct
    verdicts — exactly what a bring-up must not accept."""
    service = _device_route_service()
    with inject(FaultRule("batcher.device_dispatch", "raise",
                          detail="ed25519")):
        with pytest.raises(chip_smoke.PhaseFailed, match="BatchFailure"):
            chip_smoke.phase_kernels(
                seed=0, rows=16, unique=8, schemes=("ed25519",),
                corrupt_every=5, merkle_txs=4, service=service)


def test_counter_check_fails_a_host_routed_phase():
    registry = MetricRegistry()
    service = TpuTransactionVerifierService(metrics=registry)  # crossover 192
    with pytest.raises(chip_smoke.PhaseFailed, match="DeviceChecked"):
        chip_smoke.phase_kernels(
            seed=0, rows=16, unique=8, schemes=("ed25519",),
            corrupt_every=5, merkle_txs=4, service=service)


def test_verdict_comparison_catches_one_flipped_row():
    checks, corrupted = chip_smoke.signed_rows("ed25519", 16, 4, seed=3,
                                               corrupt_every=5)
    want = chip_smoke.host_reference(checks)
    assert [i for i, ok in enumerate(want) if not ok] == corrupted
    chip_smoke.compare_verdicts("ed25519", want, checks, corrupted)
    wrong = list(want)
    wrong[7] = not wrong[7]
    with pytest.raises(chip_smoke.PhaseFailed, match="first rows \\[7\\]"):
        chip_smoke.compare_verdicts("ed25519", wrong, checks, corrupted)


def test_placement_check_on_four_virtual_devices():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from corda_tpu.parallel import make_mesh
    from corda_tpu.parallel.sharded import AXIS
    mesh = make_mesh(4)
    rows = np.zeros((64, 8), np.uint32)
    spread = jax.device_put(rows, NamedSharding(mesh, P(AXIS, None)))
    chip_smoke.check_placement("leaves", spread, 4)
    on_first = jax.device_put(rows, jax.devices()[0])
    with pytest.raises(chip_smoke.PhaseFailed, match="1 device"):
        chip_smoke.check_placement("leaves", on_first, 4)


def test_merkle_reference_matches_the_ledger_tree():
    from corda_tpu.core.crypto.merkle import MerkleTree
    from corda_tpu.core.crypto.secure_hash import SecureHash
    hashes = [SecureHash.sha256(bytes([i])) for i in range(5)]
    assert chip_smoke.merkle_root_hashlib([h.bytes for h in hashes]) == \
        MerkleTree.root_hash(hashes).bytes


# -- the one compile-cache rule ----------------------------------------------------------

_PRINT_CACHE = ("import jax; "
                "from corda_tpu.utils.compile_cache import "
                "enable_compile_cache; "
                "print(enable_compile_cache()); "
                "print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_from(cwd, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PRINT_CACHE], cwd=cwd,
                         capture_output=True, text=True, timeout=60, env=env)
    assert out.returncode == 0, out.stderr
    returned, in_force = out.stdout.split()
    assert returned == in_force
    return in_force


def test_cache_unset_is_the_checkout_cache_from_any_directory(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    assert str(compile_cache.CHECKOUT_CACHE) == want
    assert _cache_dir_from(REPO) == want
    assert _cache_dir_from(str(tmp_path)) == want


def test_cache_variable_set_means_code_sets_no_directory(tmp_path,
                                                         monkeypatch):
    assert _cache_dir_from(REPO, env_dir=str(tmp_path)) == str(tmp_path)
    # and in-process: with the variable set the helper leaves the
    # directory alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == before


def test_only_the_helper_sets_a_cache_directory():
    # spelled in two halves so that this file is not itself a hit; scratch
    # copies of the tree live in git-ignored directories named _*
    needle = 'config.update("jax_' + 'compilation_cache_dir"'
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d[0] not in "._" and d != "chiprun_out"]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    if needle in fh.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["corda_tpu/utils/compile_cache.py"]


# -- one process per chip: the fleet worker's confinement ------------------------------

_TPU_VARS = ("TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES",
             "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")


@pytest.mark.parametrize("chips,shards,index,want,bounds", [
    (4, 4, 0, (0,), "1,1,1"),
    (4, 4, 3, (3,), "1,1,1"),
    (4, 2, 1, (2, 3), "1,2,1"),
    (0, 4, 1, (), None),        # no local TPU: nothing to confine
    (4, 8, 1, (), None),        # more shards than chips: shard_devices says so
    (4, 3, 0, (0, 1), "1,2,1"),  # remainder chips go to the low shards
])
def test_shard_worker_confines_itself_to_its_chips(
        monkeypatch, chips, shards, index, want, bounds):
    import corda_tpu.verifier.__main__ as worker
    for var in _TPU_VARS:
        # empty counts as unset; set through monkeypatch so that what the
        # worker writes into os.environ is undone after the test
        monkeypatch.setenv(var, "")
    monkeypatch.setattr(worker, "_local_chip_count", lambda: chips)
    assert worker.confine_to_shard(index, shards) == want
    assert os.environ["TPU_VISIBLE_CHIPS"] == ",".join(map(str, want))
    assert (os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] or None) == bounds


def test_shard_worker_leaves_an_operators_visibility_alone(monkeypatch):
    import corda_tpu.verifier.__main__ as worker
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2")
    monkeypatch.delenv("TPU_PROCESS_BOUNDS", raising=False)
    monkeypatch.setattr(worker, "_local_chip_count", lambda: 4)
    assert worker.confine_to_shard(0, 4) == ()
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert "TPU_PROCESS_BOUNDS" not in os.environ
