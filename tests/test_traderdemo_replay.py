"""The ``traderdemo-replay`` deployment on the system's normal path: seeded
books of the trader-demo ledger go through
``TpuTransactionVerifierService.verify_levels`` with ``ResolvedFromWalk`` and
NOTHING recorded, and the answer ``(verified, error's class)`` equals the
plain reference's (``tests/trader_reference.py``, the repo's copy of the
benchmark's) for a valid book and for each of eight altered kinds: the cell's
four and four more.

Host-routed: a book of 8 trades has levels of 32 / 24 / 24 signature rows,
under the crossover, so every member is held and verified on the walk's one
task. Over the crossover: ONE service for the module, on the secp256k1 kernel
at the 8-row bucket (the shape the ECDSA corpus and the benchmark's
rehearsals compile), for a valid and an altered book, the tally meters and
the ``level`` tag."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent), str(pathlib.Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import trader_books  # noqa: E402
import trader_reference as ref  # noqa: E402
from drivers.bookwalk import class_of, members_reached  # noqa: E402

import corda_tpu.core.transactions  # noqa: E402,F401
from corda_tpu.core.serialization import deserialize  # noqa: E402
from corda_tpu.node.services import ResolvedFromWalk  # noqa: E402
from corda_tpu.testing.services import MockServices  # noqa: E402
from corda_tpu.utils.metrics import MetricRegistry  # noqa: E402
from corda_tpu.verifier.batcher import SignatureBatcher  # noqa: E402
from corda_tpu.verifier.service import (  # noqa: E402
    TpuTransactionVerifierService)

TRADES, BANKS = 8, 6
KIND_IDS = ["valid"] + [k.replace(" ", "_").replace("'", "")[:44]
                        for k in trader_books.KINDS]


def made_book(kind, seed=11):
    made = trader_books.make_book((seed, TRADES, BANKS, kind))
    levels = [[deserialize(b) for b in level] for level in made["levels"]]
    return made, levels, ResolvedFromWalk(
        MockServices(), [stx for level in levels for stx in level])


def answer(service, levels, services):
    verified, error = service.verify_levels(levels, services).result(
        timeout=300)
    return verified, class_of(error), error


@pytest.fixture(scope="module")
def host_service():
    service = TpuTransactionVerifierService(
        batcher=SignatureBatcher(metrics=MetricRegistry()),
        metrics=MetricRegistry())
    yield service
    service.shutdown()


@pytest.mark.parametrize("kind", [None, *range(len(trader_books.KINDS))],
                         ids=KIND_IDS)
def test_the_service_and_the_reference_judge_a_book_alike(host_service,
                                                          kind):
    made, levels, services = made_book(kind)
    want = ref.judge(made["facts"])
    assert want == tuple(made["expect"])
    held0 = host_service.metrics.meter("Verifier.WaveTx.held").count
    inline0 = host_service.batcher.metrics.meter(
        "SigBatcher.HostInline").count
    verified, found, error = answer(host_service, levels, services)
    assert (verified, found) == want, error
    if kind is None:
        assert want == (5 * TRADES, ref.VALID)
    else:
        # the members that passed before it in the order, and ITS error
        assert found == trader_books.CLASSES[kind] != ref.VALID
        level = trader_books.LEVELS[kind]
        assert sum(map(len, levels[:level])) <= verified \
            < sum(map(len, levels[:level + 1]))
        # the error is the altered member's own (a resolution failure names
        # the transaction its input points into: the trade)
        named = levels[1][verified - len(levels[0]) - len(levels[1])] \
            if kind == 7 else [stx for lv in levels for stx in lv][verified]
        assert named.id.prefix_chars() in str(error).upper()
    # host-routed: every level held, every row of the levels the walk reached
    # verified on its one task, and it stopped at the level of its first
    # failure
    reached = members_reached([len(lv) for lv in levels], verified)
    assert host_service.metrics.meter("Verifier.WaveTx.held").count \
        - held0 == reached
    assert host_service.metrics.meter("Verifier.WaveTx.bulk").count == 0
    assert host_service.batcher.metrics.meter(
        "SigBatcher.HostInline").count - inline0 \
        == sum(len(stx.sigs) for k, lv in enumerate(levels) for stx in lv
               if sum(map(len, levels[:k])) < reached)


def test_a_book_is_judged_from_the_walk_alone():
    """Nothing recorded: the hub behind the walk's view holds no state, and
    without the view the first member with an input does not resolve."""
    _made, levels, services = made_book(None)
    bare = MockServices()
    assert bare.load_state(levels[1][0].tx.inputs[0]) is None
    assert services.load_state(levels[1][0].tx.inputs[0]) is not None
    service = TpuTransactionVerifierService(
        batcher=SignatureBatcher(metrics=MetricRegistry()))
    try:
        verified, found, _error = answer(service, levels, bare)
    finally:
        service.shutdown()
    assert (verified, found) == (len(levels[0]), ref.RESOLUTION)


# -- over the crossover: bulk levels on the kernel ------------------------------

@pytest.fixture(scope="module")
def bulk_service():
    """Every level over the crossover (0), flushes of 8 rows: the one shape
    this module dispatches."""
    registry = MetricRegistry()
    service = TpuTransactionVerifierService(
        batcher=SignatureBatcher(metrics=registry, max_batch=8,
                                 host_crossover=0, bucket_ladder=[8]),
        metrics=registry)
    yield service
    service.shutdown()


def _counts(registry, prefix):
    return {name[len(prefix):]: row["count"]
            for name, row in registry.snapshot().items()
            if name.startswith(prefix)}


def test_bulk_levels_on_the_kernel_and_what_the_tally_meters_read(
        bulk_service):
    from corda_tpu.observability import disable_tracing, enable_tracing
    made, levels, services = made_book(None)
    tracer = enable_tracing()
    try:
        verified, found, error = answer(bulk_service, levels, services)
        spans = [s for trace in tracer.traces().values() for s in trace]
    finally:
        disable_tracing()
    assert (verified, found) == ref.judge(made["facts"]) \
        == (5 * TRADES, ref.VALID), error
    registry = bulk_service.metrics
    assert registry.meter("Verifier.WaveTx.bulk").count == 5 * TRADES
    assert registry.meter("Verifier.WaveTx.held").count == 0
    assert registry.meter("SigBatcher.DeviceChecked").count == 10 * TRADES
    assert registry.meter("SigBatcher.HostRouted").count == 0
    # what the contracts ran: Cash on both issues, the trade and the
    # redemption of every trade; CommercialPaper on its issue, the trade and
    # the redemption
    assert _counts(registry, "Verifier.ContractRuns.") \
        == {"Cash": 4 * TRADES, "CommercialPaper": 3 * TRADES}
    micros = _counts(registry, "Verifier.ContractMicros.")
    assert set(micros) == {"Cash", "CommercialPaper"}
    rules_s = sum(s["duration_s"] for s in spans
                  if s["name"] == "verifier.wave.rules")
    assert 0 < sum(micros.values()) / 1e6 < rules_s
    # one walk, three waves under it, tagged with their level in order
    (walk,) = [s for s in spans if s["name"] == "verifier.levels"]
    assert walk["tags"]["levels"] == 3 and walk["tags"]["verified"] == 40
    waves = sorted((s for s in spans if s["name"] == "verifier.wave"),
                   key=lambda s: s["start_s"])
    assert [w["tags"]["level"] for w in waves] == [0, 1, 2]
    assert {w["parent_id"] for w in waves} == {walk["span_id"]}
    assert [(w["tags"]["n_tx"], w["tags"]["n_sigs"], w["tags"]["admitted"])
            for w in waves] == [(3 * TRADES, 4 * TRADES, "bulk"),
                                (TRADES, 3 * TRADES, "bulk"),
                                (TRADES, 3 * TRADES, "bulk")]


@pytest.mark.parametrize("kind", [0, 1], ids=KIND_IDS[1:3])
def test_an_altered_book_over_the_crossover(bulk_service, kind):
    """A failed level in the middle: the walk answers with the count before
    the altered trade and its error, and never admits level 2."""
    made, levels, services = made_book(kind, seed=12)
    bulk0 = bulk_service.metrics.meter("Verifier.WaveTx.bulk").count
    runs0 = _counts(bulk_service.metrics, "Verifier.ContractRuns.")
    verified, found, error = answer(bulk_service, levels, services)
    assert (verified, found) == ref.judge(made["facts"]) \
        == tuple(made["expect"]), error
    assert bulk_service.metrics.meter("Verifier.WaveTx.bulk").count - bulk0 \
        == 4 * TRADES
    runs = _counts(bulk_service.metrics, "Verifier.ContractRuns.")
    # a bad signature's member never reaches the rules; a contract's
    # failure is tallied with the contract that refused it
    short = 1 if kind == 0 else 0
    assert runs["Cash"] - runs0.get("Cash", 0) == 3 * TRADES - short
    assert runs["CommercialPaper"] - runs0.get("CommercialPaper", 0) \
        >= 2 * TRADES - 1


def test_a_wave_outside_a_walk_carries_no_level_tag(bulk_service):
    from corda_tpu.observability import disable_tracing, enable_tracing
    _made, levels, services = made_book(None)
    tracer = enable_tracing()
    try:
        futures = bulk_service.verify_wave(levels[0], services)
        assert [f.exception(timeout=300) for f in futures] \
            == [None] * len(levels[0])
        spans = [s for trace in tracer.traces().values() for s in trace]
    finally:
        disable_tracing()
    (wave,) = [s for s in spans if s["name"] == "verifier.wave"]
    assert "level" not in wave["tags"] and wave["tags"]["admitted"] == "bulk"
