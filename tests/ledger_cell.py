"""The served path under test: ``crosscash-raft.steady`` at a tiny size on
the CPU, through the benchmark's own driver (``benchmarks/drivers/ledger.py``:
``Deployment``, ``Loop``, ``Hostile``, ``check_guarantees``). The guarantees
are judged by that driver against references that import nothing of the
program (``benchmarks/reference/``), not by a report of the program's own.

Where a test needs a hook the driver lacks (a verifier to trip, a fault to arm
inside the window) it hands ``run_ledger`` a ``prepare`` that puts a subclass
in the freshly loaded driver module's place, as ``drivers/latejoin.py``
subclasses the same classes. Nothing under ``benchmarks/`` is edited.
"""
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(REPO / "benchmarks"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench_run  # noqa: E402  (benchmarks/run.py)

CELL = "crosscash-raft.steady"
SEED = 3_000_000_021
#: the size benchmarks/tests rehearse the ledger cells at
TINY = {"parties": 4, "coins_per_party": 3, "warmup_ops": 16,
        "rate_tx_per_s": 10.0, "hostile_ops": 4, "reference_sample": 16,
        "drain_limit_s": 30.0}
#: the spans a committed transaction leaves behind, one trace id over all
COMMIT_PATH_SPANS = ("flow.run", "tx.verify", "notary.uniqueness",
                     "raft.commit", "vault.update")


def run_ledger(seconds: float = 2.0, seed: int = SEED, scale=None,
               prepare=None):
    """One untraced run of the cell. Returns the run's context: ``correct``,
    ``checks`` (each number beside its limit), ``notes`` (the earlier lines)
    and, as ``outcome``, what the driver returned. ``prepare(driver)`` gets
    the driver module before it runs."""
    cell = bench_run.Cell(CELL)
    ctx = bench_run.RunContext(cell, seed, seconds, False,
                               scale={**TINY, **(scale or {})}, quiet=True)
    driver = bench_run.load_module("drivers", cell.driver_name)
    if prepare is not None:
        prepare(driver)
    try:
        ctx.outcome = driver.run(ctx)
    finally:
        ctx.cleanup()
    return ctx


def check(ctx, name: str) -> dict:
    """The row of ``ctx.checks`` called ``name``."""
    (row,) = [c for c in ctx.checks if c["check"] == name]
    return row


def note(ctx, what: str) -> dict:
    (row,) = [n for n in ctx.notes if n["note"] == what]
    return row


def on_deployment(hook):
    """A ``prepare`` that calls ``hook(deployment)`` once it is built."""
    def prepare(driver):
        class Hooked(driver.Deployment):
            def __init__(self, ctx):
                super().__init__(ctx)
                hook(self)
        driver.Deployment = Hooked
    return prepare
