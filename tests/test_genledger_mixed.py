"""The ``genledger-mixed`` deployment on the system's normal path: seeded
mixed-scheme ledgers (Ed25519 + secp256k1 parties, CompositeKey owners, a
cluster notary) go through ``TpuTransactionVerifierService.verify_wave`` and
every member's verdict AND class equals the plain reference's, for every
altered kind; the reference's composite evaluator against
``CompositeKey.is_fulfilled_by`` on seeded random trees; the generator's
defaults byte for byte the parent's, and its composite arguments' shapes.

No EC kernel is compiled here: the device route runs behind a stand-in that
gives host verdicts (``_stub_device``)."""
import hashlib
import pathlib
import random
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
for _p in (str(BENCH), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import mixed_ledgers  # noqa: E402
from reference import genledger_mixed as ref  # noqa: E402

import corda_tpu.core.transactions  # noqa: E402,F401
import corda_tpu.testing.dummy  # noqa: E402,F401
from corda_tpu.core.crypto import generate_keypair  # noqa: E402
from corda_tpu.core.crypto.composite import CompositeKey  # noqa: E402
from corda_tpu.core.crypto.schemes import (  # noqa: E402
    ECDSA_SECP256K1_SHA256, EDDSA_ED25519_SHA512)
from corda_tpu.core.crypto.signatures import SignatureException  # noqa: E402
from corda_tpu.core.serialization import deserialize, serialize  # noqa: E402
from corda_tpu.core.transactions.signed import (  # noqa: E402
    SignaturesMissingException)
from corda_tpu.testing.generated_ledger import (  # noqa: E402
    CompositeSigner, composite_party_indices, make_generated_ledger)
from corda_tpu.testing.services import MockServices  # noqa: E402
from corda_tpu.verifier.batcher import SignatureBatcher  # noqa: E402
from corda_tpu.verifier.service import (  # noqa: E402
    TpuTransactionVerifierService)

#: (seed, transactions, parties, composite, nested, replicas, invalid_every,
#: first kind): 24 invalid (three of each kind) and 6 valid-shaped
JOB = (42, 192, 8, 4, 1, 3, 8, 0)
K1, ED = (ECDSA_SECP256K1_SHA256.scheme_number_id,
          EDDSA_ED25519_SHA512.scheme_number_id)


def _stub_device(batcher):
    flushes = []

    def device(bucket, items, reason="full", bctx=None):
        flushes.append((bucket, len(items)))
        batcher._mark_device(items)
        batcher._resolve(bucket, items, batcher._run_host(items), bctx)

    batcher._dispatch_device = device
    return flushes


def _class(exc) -> str:
    if exc is None:
        return ref.VALID
    if isinstance(exc, SignaturesMissingException):
        return ref.MISSING
    if isinstance(exc, SignatureException):
        assert "did not verify" in str(exc), exc
        return ref.BAD_SIGNATURE
    return f"other: {exc!r}"


@pytest.fixture(scope="module")
def judged():
    """One seeded ledger through ``verify_wave`` (bulk, stubbed device) and
    through the reference: (the ledger as made, the program's classes, the
    reference's, the service's meters, the flushes)."""
    made = mixed_ledgers.make_ledger(JOB)
    services = MockServices()
    txs = [deserialize(b) for b in made["stx"]]
    services.record_transactions(*txs)
    batcher = SignatureBatcher(max_batch=64, host_crossover=16)
    flushes = _stub_device(batcher)
    svc = TpuTransactionVerifierService(batcher=batcher)
    try:
        futures = svc.verify_wave(txs, services)
        got = [_class(f.exception(timeout=120)) for f in futures]
    finally:
        svc.shutdown()
    return (made, got, ref.verdicts(made["facts"]), svc.metrics.snapshot(),
            batcher.metrics.snapshot(), flushes)


@pytest.mark.parametrize("kind", range(len(mixed_ledgers.KINDS)),
                         ids=[k.replace(" ", "_")[:48]
                              for k in mixed_ledgers.KINDS])
def test_the_program_and_the_reference_judge_each_kind_alike(judged, kind):
    made, got, want, _svc, _dev, _flushes = judged
    mine = [i for i, k in made["kinds"].items() if k == kind]
    assert len(mine) == 3
    for i in mine:
        assert got[i] == want[i] == mixed_ledgers.VERDICTS[kind], (i, kind)


def test_every_member_of_the_wave_is_judged_as_the_reference_judges_it(
        judged):
    made, got, want, svc, dev, flushes = judged
    assert got == want
    assert {v for i, v in enumerate(want) if i not in made["kinds"]} \
        == {ref.VALID}
    assert sorted(set(made["kinds"].values())) == list(range(10))
    # one bulk wave; both schemes' rows went down the device route, in
    # flushes of their own
    assert svc["Verifier.WaveTx.bulk"]["count"] == len(got)
    assert "Verifier.WaveTx.held" not in svc
    rows = {c: sum(s[0] == sid for f in made["facts"] for s in f[1])
            for c, sid in (("ed25519", ED), ("secp256k1", K1))}
    assert {b for b, _n in flushes} == {"ed25519", "secp256k1"}
    for bucket, n in rows.items():
        # a sub-crossover tail of a queue is host-routed by the planner
        assert 0 <= n - dev[f"SigBatcher.DeviceChecked.{bucket}"]["count"] \
            < 16, bucket
    assert svc["Verifier.CompositeRequired"]["count"] > 0
    assert svc["Verifier.CompositeLeafVisits"]["count"] \
        >= svc["Verifier.CompositeRequired"]["count"]
    assert svc["Verifier.RequiredKeys"]["count"] \
        > svc["Verifier.CompositeRequired"]["count"]


def test_the_host_path_judges_the_ledger_alike(judged):
    """``SignedTransaction.verify`` (the rule the service has to equal),
    transaction by transaction on the host."""
    made, got, _want, _svc, _dev, _flushes = judged
    services = MockServices()
    txs = [deserialize(b) for b in made["stx"]]
    services.record_transactions(*txs)
    for i, stx in enumerate(txs):
        try:
            stx.verify(services)
            mine = None
        except Exception as e:
            mine = e
        assert _class(mine) == got[i], i


def _random_tree(rng, leaves, depth=0):
    """A seeded composite over some of ``leaves`` (PublicKeys): weights 1-4,
    nested up to two levels, and the weight its children sum to."""
    n = rng.randint(2, 4)
    picked = rng.sample(leaves, n)
    builder, total = CompositeKey.Builder(), 0
    for key in picked:
        weight = rng.randint(1, 4)
        if depth < 2 and rng.random() < 0.35:
            inner, reach = _random_tree(
                rng, [k for k in leaves if k != key], depth + 1)
            builder.add_key(inner.build(rng.randint(1, reach)), weight)
        else:
            builder.add_key(key, weight)
        total += weight
    return builder, total


@pytest.mark.parametrize("seed", range(8))
def test_the_references_composite_rule_is_composite_keys(seed):
    """Seeded random trees (weights, nesting) at thresholds from 1 to the
    children's whole weight, against seeded signer sets, and for each tree
    the sets that reach the threshold exactly, fall one short and pass it by
    one: the reference's own decoder and recursion answer as
    ``CompositeKey.is_fulfilled_by`` does."""
    rng = random.Random(f"trees:{seed}")
    leaves = [generate_keypair(
        ECDSA_SECP256K1_SHA256 if i % 2 else EDDSA_ED25519_SHA512,
        entropy=rng.randbytes(32)).public for i in range(7)]
    compared = fulfilled = 0
    for _tree in range(12):
        builder, total = _random_tree(rng, leaves)
        for threshold in {1, max(1, total // 2), total}:
            key = builder.build(threshold)
            if not isinstance(key, CompositeKey):
                continue
            assert ref.decode_composite(key.encoded)[0] == threshold
            sets = [set(rng.sample(leaves, rng.randint(0, len(leaves))))
                    for _ in range(6)]
            # by the flat top level's weights: at, one under, one over
            plain = [c for c in key.children
                     if not isinstance(c.node, CompositeKey)]
            for target in (threshold - 1, threshold, threshold + 1):
                reach, chosen = 0, set()
                for c in plain:
                    if reach + c.weight <= target:
                        reach += c.weight
                        chosen.add(c.node)
                sets.append(chosen)
            for signers in sets:
                want = key.is_fulfilled_by(signers)
                mine = ref.required_fulfilled(
                    ref.COMPOSITE, key.encoded,
                    {(k.scheme.scheme_number_id, k.encoded)
                     for k in signers})
                assert mine == want, (seed, threshold, len(signers))
                compared += 1
                fulfilled += want
    assert compared > 100 and 0 < fulfilled < compared


def test_the_reference_refuses_what_is_not_a_composite_encoding():
    key = CompositeKey.Builder().add_keys(
        *(generate_keypair(entropy=bytes([i]) * 32).public
          for i in (1, 2, 3))).build(2)
    assert ref.decode_composite(key.encoded)[0] == 2
    for bad in (key.encoded + b"\x00", key.encoded[:-1],
                b"\xc1" + key.encoded[1:]):
        with pytest.raises((ValueError, Exception)):
            ref.decode_composite(bad)
        with pytest.raises(ValueError):
            CompositeKey.decode(bad)


def _digest(ledger) -> str:
    h = hashlib.sha256()
    for stx in ledger.transactions:
        h.update(serialize(stx))
    return h.hexdigest()


@pytest.mark.parametrize("args,digest", [
    (dict(n_transactions=24, seed=11, n_parties=6, scheme_mix=False),
     "fac8c936eb8ff8dcc702b81709ea29b5f615426293558cfae3ad1c8bfc14af1e"),
    (dict(n_transactions=6, seed=12, n_parties=4),
     "f00da7473022d9e9ac559321e32a24791b3122c6adf31e302180817dabafcfd6"),
], ids=["ed25519_alone", "scheme_mix"])
def test_the_generators_defaults_draw_what_the_parent_drew(args, digest):
    """The digests were taken on the parent of PR 42 (the oop cell's
    ledgers at a seed have to stay byte-identical)."""
    assert _digest(make_generated_ledger(**args)) == digest


def test_the_generators_composite_arguments_give_the_stated_shapes():
    assert composite_party_indices(64, 16) == [
        2, 7, 10, 15, 18, 23, 26, 31, 34, 39, 42, 47, 50, 55, 58, 63]
    assert composite_party_indices(64, 0) == []
    signer = mixed_ledgers.make_signer()
    ledger = make_generated_ledger(
        160, seed=7, n_parties=8, composite_parties=4, nested_composites=1,
        notary_replicas=3, signer=signer)
    shapes = {}
    for i, (party, kp) in enumerate(ledger.parties):
        if not isinstance(kp, CompositeSigner):
            # plain parties alternate Ed25519 / secp256k1 by index
            assert kp.public.scheme.scheme_number_id == (K1 if i % 2 else ED)
            continue
        key = party.owning_key
        assert isinstance(key, CompositeKey) and key == kp.public
        assert len(kp.leaves) == 3 and len(key.keys) == 3
        assert {leaf.public.scheme.scheme_number_id
                for leaf in kp.leaves} == {K1, ED}
        # the leaves that sign reach the threshold and no leaf more
        signing = {leaf.public for leaf in kp.signing}
        assert key.is_fulfilled_by(signing)
        for leaf in signing:
            assert not key.is_fulfilled_by(signing - {leaf})
        shapes[i] = (key.threshold, sorted(c.weight for c in key.children))
    assert shapes == {0: (3, [1, 2]), 3: (2, [1, 1, 1]),
                      4: (2, [1, 1, 1]), 7: (2, [1, 1, 1])}
    # the notary: a 1-of-3 identity of Ed25519 replicas, ONE signs
    notary = ledger.notary.owning_key
    assert isinstance(notary, CompositeKey) and notary.threshold == 1
    replicas = {kp.public for kp in ledger.notary_kp.leaves}
    assert len(replicas) == 3 and notary.keys == replicas
    assert {k.scheme.scheme_number_id for k in replicas} == {ED}
    seen = set()
    services = MockServices()
    services.record_transactions(*ledger.transactions)
    for stx in ledger.transactions:
        by_replicas = [s.by for s in stx.sigs if s.by in replicas]
        assert len(by_replicas) == (1 if stx.tx.inputs else 0)
        seen.update(by_replicas)
        stx.verify(services)            # every transaction is valid
        owner = dict((p.owning_key, kp) for p, kp in ledger.parties)[
            stx.tx.must_sign[0]]
        if isinstance(owner, CompositeSigner):
            assert [s.by for s in stx.sigs][:len(owner.signing)] \
                == [kp.public for kp in owner.signing]
    assert seen == replicas             # drawn per transaction
    # the signer's ECDSA is not normalised: some s are high
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    high = [decode_dss_signature(s.bytes)[1] > mixed_ledgers.K1_ORDER // 2
            for stx in ledger.transactions for s in stx.sigs
            if s.by.scheme.scheme_number_id == K1]
    assert 0.25 < sum(high) / len(high) < 0.75
