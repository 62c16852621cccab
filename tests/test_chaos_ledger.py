"""Chaos on the ledger commit path: the exactly-once invariant holds.

One fault window per case — a follower partitioned, the leader partitioned
("killed"), AppendEntries dropped at random — armed by this test inside the
measured window of a tiny ``crosscash-raft.steady`` run (tests/ledger_cell.py).
Whatever the window does to latency and availability, every ACCEPTED
transaction must consume its inputs exactly once on every replica, the
replicas must agree at quiescence and every acknowledged commit must be read
back: the benchmark driver's ``check_guarantees``, against its plain
reference.
"""
import time

import pytest

from corda_tpu.testing import faults
from ledger_cell import check, note, run_ledger

#: the window opens this far into the run's 1.5 s of offered load
FAULT_AT_S, FAULT_FOR_S = 0.4, 0.6
APPEND_DROP_P = 0.15


def partition(name):
    return [faults.FaultRule("net.send", "drop", detail=f"{name}->*"),
            faults.FaultRule("net.send", "drop", detail=f"*->{name}")]


def rules_for(kind, raft_nodes):
    from corda_tpu.consensus.raft import LEADER
    if kind == "append_drop":
        return [faults.FaultRule("raft.append", "drop",
                                 probability=APPEND_DROP_P)]
    want_leader = kind == "leader_kill"
    target = next(rn.node_id for rn in raft_nodes
                  if (rn.role == LEADER) == want_leader)
    return partition(target)


def chaos(kind, seed, fired):
    """A ``prepare`` for ``run_ledger``: the driver's ``Loop``, arming one
    fault window while it drives the measured window (the only drive that is
    offered for a fixed time); the drive disarms it however it ends."""
    def prepare(driver):
        class ChaosLoop(driver.Loop):
            injector = None
            started = None

            def drive(self, ops, offer_s, drain_limit_s, hostile=None):
                self.in_window = offer_s is not None
                try:
                    return super().drive(ops, offer_s, drain_limit_s, hostile)
                finally:
                    self.in_window = False
                    self.end_fault()

            def end_fault(self):
                if self.injector is not None:
                    fired.append(len(self.injector.log))
                    faults.disarm()
                    self.injector = None

            def _sweep(self, inflight, t0):
                now = time.monotonic() - t0
                if self.in_window and self.started is None \
                        and now >= FAULT_AT_S:
                    self.started = now
                    self.injector = faults.FaultInjector(seed=seed)
                    for rule in rules_for(kind, self.dep.raft_nodes):
                        self.injector.add(rule)
                    faults.arm(self.injector)
                elif self.injector is not None \
                        and now >= self.started + FAULT_FOR_S:
                    self.end_fault()
                super()._sweep(inflight, t0)
        driver.Loop = ChaosLoop
    return prepare


@pytest.mark.chaos
@pytest.mark.ledger
@pytest.mark.parametrize("seed", [7, 101, 9001])
@pytest.mark.parametrize("kind", ["partition_follower", "leader_kill",
                                  "append_drop"])
def test_chaos_run_commits_exactly_once(kind, seed):
    fired = []
    ctx = run_ledger(
        seconds=1.5, seed=seed, prepare=chaos(kind, seed, fired),
        # an op the window strands may fail: what is ACCEPTED is judged
        scale={"require_all_committed": False, "warmup_ops": 8,
               "provider_timeout_s": 2.0})
    # the window armed inside the offered load and was annotated
    assert len(fired) == 1 and fired[0] >= 0
    # the invariant: no double spends, no lost accepted commits, replicas
    # converge — regardless of what the window did
    for name in ("exactly_once_violations", "replica_disagreements",
                 "acknowledged_commits_not_read_back",
                 "hostile_submissions_accepted"):
        assert check(ctx, name)["ok"], (name, check(ctx, name))
    assert note(ctx, "window")["committed_ops"] > 0
