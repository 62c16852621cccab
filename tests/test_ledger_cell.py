"""The served commit path at a tiny size, through the benchmark's driver
(tests/ledger_cell.py).

ONE connected trace per committed transaction — flow.run → tx.verify →
notary.uniqueness → raft.commit → vault.update under a single trace id —
including when the device breaker is open and verification degrades to the
host route; and every replayed consumed ref refused by the notary.
"""
import pytest

from corda_tpu.observability import disable_tracing, enable_tracing
from ledger_cell import COMMIT_PATH_SPANS, check, note, on_deployment, \
    run_ledger


@pytest.fixture
def tracer():
    """The program's tracer on around a run: the driver turns it on itself
    only in a profiled run, which a CPU has no use for."""
    try:
        yield enable_tracing(65536)
    finally:
        disable_tracing()


def stitched(traces: dict) -> list:
    """The traces that hold every commit-path span."""
    return [spans for spans in traces.values()
            if set(COMMIT_PATH_SPANS) <= {s["name"] for s in spans}]


def walks_to_flow_run(span, by_id) -> bool:
    for _ in range(64):
        if span is None:
            return False
        if span["name"] == "flow.run":
            return True
        span = by_id.get(span["parent_id"])
    return False


@pytest.mark.ledger
def test_smoke_scenario_stitches_one_commit_path_trace(tracer):
    ctx = run_ledger()
    assert ctx.correct, [c for c in ctx.checks if not c["ok"]]
    assert ctx.outcome["failed"] == 0
    whole = stitched(tracer.traces())
    assert whole, "no trace holds the whole commit path"
    spans = whole[0]
    # one trace id across the whole tree
    assert len({s["trace_id"] for s in spans}) == 1
    by_id = {s["span_id"]: s for s in spans}
    # the vault write is REACHABLE from the flow.run root: walking parent
    # pointers from a vault.update span crosses the notary/raft boundary
    # and lands on flow.run — the cross-component stitching acceptance
    for name in ("vault.update", "raft.commit"):
        leaves = [s for s in spans if s["name"] == name]
        assert leaves and any(walks_to_flow_run(s, by_id) for s in leaves), \
            name


@pytest.mark.ledger
def test_degraded_breaker_open_route_still_stitches(tracer):
    """Open every device breaker and drop the host crossover to zero: all
    signature batches take the breaker_open host-verify route, and the
    commit path must STILL stitch end-to-end (degradation, not blindness).
    """
    def trip(dep):
        b = dep.verifier.batcher
        b.host_crossover = 0              # no small-batch bypass
        for br in b._breakers.values():
            br.state = br.OPEN
            br._opened_at = br.clock()
            br.cooldown_s = 1e9           # never half-opens

    ctx = run_ledger(prepare=on_deployment(trip))
    routed = check(ctx, "batcher_breaker_routed")
    assert routed["value"] > 0
    # still correct in everything but the two checks that say "degraded"
    failed = {c["check"] for c in ctx.checks if not c["ok"]}
    assert failed == {"batcher_breaker_routed", "breakers_not_closed"}
    assert ctx.outcome["failed"] == 0
    assert stitched(tracer.traces())


@pytest.mark.ledger
def test_hot_state_preset_rejects_every_double_spend():
    """Replays of already-consumed refs hit the uniqueness provider
    directly, among mis-signed transactions at the verifier: the notary
    must refuse every one naming the original consumer (``Hostile``'s own
    judgement), and the deployment must still commit."""
    ctx = run_ledger(scale={"hostile_ops": 12})
    assert ctx.correct, [c for c in ctx.checks if not c["ok"]]
    hostile = note(ctx, "hostile")
    assert hostile["injected"] == 12 and hostile["refused"] == 12
    assert check(ctx, "hostile_submissions_accepted")["value"] == 0
    assert note(ctx, "window")["committed_ops"] > 0
