"""Canonical codec tests: determinism, whitelisting, round-trips.

(Reference analog: KryoTests + CordaClassResolver whitelist tests.)
"""
import datetime

import pytest

from corda_tpu.core.serialization import (
    serialize, deserialize, serialized_hash, SerializationError, serializable)
from corda_tpu.core.crypto import SecureHash, generate_keypair, CompositeKey, Crypto


def test_primitive_roundtrips():
    for v in [None, True, False, 0, -1, 2**62, 2**100, -(2**100), "héllo", b"bytes",
              [1, [2, 3], "x"], {"a": 1, "b": [2]}, frozenset({1, 2, 3}),
              datetime.datetime(2026, 7, 29, 12, 0, tzinfo=datetime.timezone.utc)]:
        assert deserialize(serialize(v)) == v, v


def test_determinism_of_maps_and_sets():
    a = serialize({"x": 1, "y": 2, "z": {1, 2, 3}})
    b = serialize({"z": {3, 2, 1}, "y": 2, "x": 1})
    assert a == b
    # bytes are stable across processes by construction (no ids/hash seeds)
    assert serialized_hash({"x": 1}).hex() == serialized_hash({"x": 1}).hex()


def test_floats_rejected():
    with pytest.raises(SerializationError):
        serialize(1.5)


def test_whitelist_enforced():
    class NotRegistered:
        pass

    with pytest.raises(SerializationError):
        serialize(NotRegistered())
    # Unknown type name on deserialize is rejected too.
    import msgpack
    from corda_tpu.core.serialization.codec import _MAGIC, _EXT_OBJ
    evil = _MAGIC + msgpack.packb(
        msgpack.ExtType(_EXT_OBJ, msgpack.packb(["EvilType", []], use_bin_type=True)),
        use_bin_type=True)
    with pytest.raises(SerializationError):
        deserialize(evil)


def test_bad_magic_and_version():
    with pytest.raises(SerializationError):
        deserialize(b"nope")
    good = serialize(1)
    with pytest.raises(SerializationError):
        deserialize(good[:3] + bytes([99]) + good[4:])


def test_crypto_types_roundtrip():
    kp = generate_keypair(entropy=b"\x09" * 32)
    assert deserialize(serialize(kp.public)) == kp.public
    h = SecureHash.sha256(b"x")
    assert deserialize(serialize(h)) == h
    sig = Crypto.sign_with_key(kp, b"msg")
    sig2 = deserialize(serialize(sig))
    assert sig2 == sig and sig2.is_valid(b"msg")
    # Composite keys travel as PublicKey wire shape.
    k2 = generate_keypair(entropy=b"\x0a" * 32)
    comp = CompositeKey.Builder().add_keys(kp.public, k2.public).build(threshold=2)
    assert deserialize(serialize(comp)) == comp


def _mutation_base() -> bytes:
    from corda_tpu.core.crypto.secure_hash import SecureHash
    from corda_tpu.core.serialization import serialize

    return serialize({
        "refs": [SecureHash.sha256(bytes([i])) for i in range(4)],
        "amounts": [10**20, -5, 0],
        "nested": {"a": (1, 2, b"\x00\xff"), "b": frozenset((1, 2, 3))},
    })


def _mutations(base: bytes, seed: int = 99, n: int = 500):
    """``n`` copies of ``base`` with one to three bytes redrawn."""
    import numpy as np

    rng = np.random.default_rng(seed)
    for _ in range(n):
        mutated = bytearray(base)
        for _ in range(int(rng.integers(1, 4))):
            mutated[int(rng.integers(0, len(base)))] = int(rng.integers(256))
        yield bytes(mutated)


def test_fuzz_mutated_bytes_fail_typed():
    """Untrusted wire bytes: random mutations of valid canonical bytes must
    either deserialize (benign mutation) or raise SerializationError — never
    any other exception type (the deserialize() hardening contract)."""
    from corda_tpu.core.serialization import SerializationError, deserialize

    base = _mutation_base()
    survived, rejected = 0, 0
    for mutated in _mutations(base):
        try:
            deserialize(mutated)
            survived += 1
        except SerializationError:
            rejected += 1
    assert survived + rejected == 500
    assert rejected > 0           # sanity: mutations do get caught

    # truncations at every boundary fail typed too
    for cut in range(len(base)):
        try:
            deserialize(base[:cut])
        except SerializationError:
            pass


def _random_values(seed: int = 17):
    """The generator of ``test_fuzz_random_structures_roundtrip``: call the
    function it returns for one random wire tree after another."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def random_value(depth=0):
        kinds = ["int", "bigint", "str", "bytes", "bool", "none"]
        if depth < 3:
            kinds += ["list", "dict"] * 2
        kind = kinds[int(rng.integers(len(kinds)))]
        if kind == "int":
            return int(rng.integers(-2**62, 2**62))
        if kind == "bigint":
            return int(rng.integers(0, 2**62)) << int(rng.integers(64, 200))
        if kind == "str":
            return "".join(chr(0x20 + int(c) % 0x5F)
                           for c in rng.integers(0, 255, size=8))
        if kind == "bytes":
            return bytes(rng.integers(0, 255, size=int(rng.integers(0, 16)),
                                      dtype=np.uint8))
        if kind == "bool":
            return bool(rng.integers(2))
        if kind == "none":
            return None
        if kind == "list":
            return [random_value(depth + 1)
                    for _ in range(int(rng.integers(0, 4)))]
        return {f"k{i}": random_value(depth + 1)
                for i in range(int(rng.integers(0, 4)))}

    return random_value


def test_fuzz_random_structures_roundtrip():
    """Property: generator-built random wire trees round-trip exactly."""
    from corda_tpu.core.serialization import deserialize, serialize

    random_value = _random_values()
    for _ in range(100):
        value = random_value()
        back = deserialize(serialize(value))
        norm = _normalize_tuples(value)
        assert back == norm, (value, back)


def _normalize_tuples(v):
    if isinstance(v, (list, tuple)):
        return [_normalize_tuples(x) for x in v]
    if isinstance(v, dict):
        return {k: _normalize_tuples(x) for k, x in v.items()}
    return v


def test_registered_dataclass_roundtrip():
    from corda_tpu.testing import DummyState
    kp = generate_keypair(entropy=b"\x0b" * 32)
    s = DummyState(magic_number=42, owners=(kp.public,))
    s2 = deserialize(serialize(s))
    assert s2 == s
    assert isinstance(s2.owners, tuple)


# ---------------------------------------------------------------------------
# Schema-carrying deserialization of unknown types (ClassCarpenter analog,
# reference ClassCarpenter.kt:30-447; VERDICT r3 missing #5)
# ---------------------------------------------------------------------------

def test_carpented_unknown_type_roundtrip():
    import dataclasses

    from corda_tpu.core.serialization import codec

    @dataclasses.dataclass(frozen=True)
    class ThirdPartyState:
        issuer: str
        quantity: int
        memo: bytes

    name = "test.carpenter.ThirdPartyState"
    codec.register_type(name, ThirdPartyState, carry_schema=True)
    try:
        blob = codec.serialize(ThirdPartyState("O=Issuer", 42, b"\x01\x02"))

        # simulate a receiver WITHOUT the defining module
        del codec._REGISTRY[name]
        del codec._BY_CLASS[ThirdPartyState]
        got = codec.deserialize(blob)
        assert type(got) is not ThirdPartyState
        assert getattr(type(got), "__corda_carpented__", None) == name
        assert (got.issuer, got.quantity, got.memo) == ("O=Issuer", 42,
                                                        b"\x01\x02")
        # the bag re-serializes BIT-EXACTLY (relay/storage round-trip)
        assert codec.serialize(got) == blob
        # same schema carpents once; a DIFFERENT schema unions (evolution —
        # see tests/test_schema_evolution.py), while hostile names still fail
        assert type(codec.deserialize(blob)) is type(got)
        union_cls = codec.carpented_class(name, ["issuer", "extra_field"])
        assert union_cls is not type(got)
        assert union_cls.__corda_carpented_fields__ == [
            "issuer", "quantity", "memo", "extra_field"]
        with pytest.raises(SerializationError):
            codec.carpented_class(name, ["__class__"])

        # once the real class IS registered, it wins for new decodes
        codec.register_type(name, ThirdPartyState, carry_schema=True)
        again = codec.deserialize(blob)
        assert type(again) is ThirdPartyState
    finally:
        codec._REGISTRY.pop(name, None)
        codec._BY_CLASS.pop(ThirdPartyState, None)
        codec._SCHEMA_NAMES.pop(name, None)
        cls_entry = codec._CARPENTED.pop(name, None)
        if cls_entry is not None:
            codec._CARPENTED_BY_CLASS.pop(cls_entry[0], None)


def test_carpenter_rejects_hostile_field_names():
    from corda_tpu.core.serialization import codec
    with pytest.raises(SerializationError):
        codec.carpented_class("evil.Type", ["__class__"])
    with pytest.raises(SerializationError):
        codec.carpented_class("evil.Type2", ["not an identifier!"])


def test_plain_unknown_type_still_rejected():
    """The whitelist stays authoritative for schema-LESS objects."""
    import msgpack

    from corda_tpu.core.serialization import codec
    wire = msgpack.ExtType(codec._EXT_OBJ,
                           codec._packb(["no.such.Type", [1, 2]]))
    blob = codec._MAGIC + codec._packb(wire)
    with pytest.raises(SerializationError):
        codec.deserialize(blob)


def test_carpenter_rejects_huge_field_count():
    """ADVICE r4 (medium): a hostile peer must not be able to force
    synthesis of an arbitrarily wide (then pinned-forever) class via one
    schema'd object — field count is bounded like the name count."""
    import msgpack

    from corda_tpu.core.serialization import codec
    names = [f"f{i}" for i in range(codec._CARPENTED_MAX_FIELDS + 1)]
    with pytest.raises(SerializationError):
        codec.carpented_class("evil.Wide", names)
    # and via the wire (the hostile-peer path)
    wire = msgpack.ExtType(
        codec._EXT_OBJ_SCHEMA,
        codec._packb(["evil.Wide2", names, [0] * len(names)]))
    blob = codec._MAGIC + codec._packb(wire)
    with pytest.raises(SerializationError):
        codec.deserialize(blob)
    # the boundary itself is fine
    ok = codec.carpented_class(
        "test.carpenter.ExactlyMax",
        [f"f{i}" for i in range(codec._CARPENTED_MAX_FIELDS)])
    codec._CARPENTED.pop("test.carpenter.ExactlyMax", None)
    codec._CARPENTED_BY_CLASS.pop(ok, None)


def test_schema_skew_binds_by_name_not_position():
    """ADVICE r4 (low): when the real class IS registered, carried field
    names from a peer with a different declaration ORDER must bind by
    name; disjoint field sets must be a SerializationError, not a
    positional misbind or raw TypeError."""
    import dataclasses

    import msgpack

    from corda_tpu.core.serialization import codec

    @dataclasses.dataclass(frozen=True)
    class SkewState:
        issuer: str
        quantity: int

    name = "test.skew.SkewState"
    codec.register_type(name, SkewState, carry_schema=True)
    try:
        # peer serialized under a REVERSED declaration order
        wire = msgpack.ExtType(
            codec._EXT_OBJ_SCHEMA,
            codec._packb([name, ["quantity", "issuer"], [42, "O=Issuer"]]))
        blob = codec._MAGIC + codec._packb(wire)
        got = codec.deserialize(blob)
        assert got == SkewState(issuer="O=Issuer", quantity=42)

        # disjoint field names: rejected, not positionally bound
        wire = msgpack.ExtType(
            codec._EXT_OBJ_SCHEMA,
            codec._packb([name, ["issuer", "totally_else"], ["O=X", 1]]))
        blob = codec._MAGIC + codec._packb(wire)
        with pytest.raises(SerializationError):
            codec.deserialize(blob)
    finally:
        codec._REGISTRY.pop(name, None)
        codec._BY_CLASS.pop(SkewState, None)
        codec._SCHEMA_NAMES.pop(name, None)


# ---------------------------------------------------------------------------
# The codec against its plain reference (tests/codec_reference.py: the walk
# that stood before PR 46, unedited): the same bytes, the same objects, the
# same refusals; and the table of learnt encoders forgets when it has to
# ---------------------------------------------------------------------------

def _verdict(decode, blob):
    from corda_tpu.core.serialization import SerializationError
    try:
        return "accepted", decode(blob)
    except SerializationError:
        return "refused", None


@pytest.mark.parametrize("seed", [17, 18, 19, 20])
def test_both_walks_agree_on_random_structures(seed):
    import codec_reference as ref

    random_value = _random_values(seed)
    for _ in range(100):
        value = random_value()
        blob = serialize(value)
        assert blob == ref.serialize(value), value
        back = deserialize(blob)
        assert back == ref.deserialize(blob), value
        # the types too: a tuple is not a list, True is not 1
        assert repr(back) == repr(ref.deserialize(blob)), value


@pytest.fixture(scope="module")
def ledger_requests():
    """The ``genledger-oop`` cell's messages: 1,500 transactions of its
    generator, each as the ``VerificationRequest`` the requestor sends."""
    import corda_tpu.core.transactions  # noqa: F401
    import corda_tpu.testing.dummy  # noqa: F401
    from corda_tpu.testing.generated_ledger import make_generated_ledger
    from corda_tpu.testing.services import MockServices
    from corda_tpu.verifier.out_of_process import VerificationRequest

    ledger = make_generated_ledger(1500, seed=0, n_parties=64,
                                   scheme_mix=False)
    services = MockServices()
    services.record_transactions(*ledger.transactions)
    requests = []
    for i, stx in enumerate(ledger.transactions):
        sigs = tuple((s.by, s.bytes, stx.id.bytes) for s in stx.sigs)
        requests.append(VerificationRequest(
            i + 1, stx.to_ledger_transaction(services), "127.0.0.1:40123",
            sigs))
    return ledger.transactions, requests


def test_both_walks_agree_on_the_generated_ledgers_requests(ledger_requests):
    import codec_reference as ref
    from corda_tpu.verifier.out_of_process import VerificationResponse

    transactions, requests = ledger_requests
    assert len(requests) == 1500
    for stx, request in zip(transactions, requests):
        blob = serialize(request)
        assert blob == ref.serialize(request)
        back = deserialize(blob)
        assert back == ref.deserialize(blob)
        assert serialize(back) == blob
        assert back.transaction.id == stx.id
        assert serialize(stx) == ref.serialize(stx)
        assert deserialize(serialize(stx)) == stx == \
            ref.deserialize(serialize(stx))
        response = VerificationResponse(request.verification_id, None)
        assert serialize(response) == ref.serialize(response)
        assert deserialize(serialize(response)) == response


def test_both_walks_judge_every_mutated_case_alike():
    """Every case of ``test_fuzz_mutated_bytes_fail_typed``: refused by both
    walks, or accepted by both with equal results."""
    import codec_reference as ref

    base = _mutation_base()
    cases = list(_mutations(base)) + [base[:cut] for cut in range(len(base))]
    accepted = 0
    for blob in cases:
        ours, theirs = _verdict(deserialize, blob), \
            _verdict(ref.deserialize, blob)
        assert ours == theirs, blob.hex()
        assert repr(ours) == repr(theirs), blob.hex()
        accepted += ours[0] == "accepted"
    assert 0 < accepted < len(cases)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_what_a_mutated_request_decodes_to_the_reference_decodes_too(
        seed, ledger_requests):
    """A request's bytes hold registered objects at every level, which the
    older fuzz's base does not. The codec may refuse hostile bytes the
    reference lets through (a map or a string where an object's LIST of
    fields belongs, which the old walk happened to iterate); whatever it
    accepts the reference accepts, as the same object."""
    import codec_reference as ref

    _transactions, requests = ledger_requests
    accepted = stricter = 0
    for request in requests[seed::300]:
        for blob in _mutations(serialize(request), seed=seed, n=400):
            ours = _verdict(deserialize, blob)
            theirs = _verdict(ref.deserialize, blob)
            if ours[0] == "accepted":
                assert ours == theirs and repr(ours) == repr(theirs), \
                    blob.hex()
                accepted += 1
            else:
                stricter += theirs[0] == "accepted"
    assert accepted > 100
    assert stricter <= 2       # one in tens of thousands (an EMPTY map
    #                            for an empty list of fields), not a class


def test_a_class_registered_after_it_was_refused_is_encoded_at_once():
    import dataclasses

    from corda_tpu.core.serialization import codec

    @dataclasses.dataclass(frozen=True)
    class Latecomer:
        amount: int
        tags: tuple = ()

    name = "test.forget.Latecomer"
    value = Latecomer(7, ("a", "b"))
    with pytest.raises(SerializationError):
        serialize(value)
    with pytest.raises(SerializationError):
        serialize([value])
    codec.register_type(name, Latecomer)
    try:
        assert deserialize(serialize(value)) == value
        assert deserialize(serialize([1, value])) == [1, value]
    finally:
        codec._REGISTRY.pop(name, None)
        codec._BY_CLASS.pop(Latecomer, None)
    # and taken out of the whitelist again it is refused again, at once
    with pytest.raises(SerializationError):
        serialize(value)


def test_a_carpented_name_registered_for_real_wins_at_once():
    import dataclasses

    import codec_reference as ref
    from corda_tpu.core.serialization import codec

    @dataclasses.dataclass(frozen=True)
    class Parcel:
        sender: str
        weight: int

    name = "test.forget.Parcel"
    codec.register_type(name, Parcel, carry_schema=True)
    try:
        blob = serialize(Parcel("O=Sender", 3))
        del codec._REGISTRY[name]
        del codec._BY_CLASS[Parcel]
        with pytest.raises(SerializationError):      # forgotten at once
            serialize(Parcel("O=Sender", 3))
        bag = deserialize(blob)
        assert type(bag) is not Parcel and serialize(bag) == blob
        assert serialize(bag) == ref.serialize(bag)
        codec.register_type(name, Parcel, carry_schema=True)
        assert type(deserialize(blob)) is Parcel
        assert serialize(Parcel("O=Sender", 3)) == blob
        # the bag decoded before keeps its own class and its own bytes
        assert serialize(bag) == blob == ref.serialize(bag)
    finally:
        codec._REGISTRY.pop(name, None)
        codec._BY_CLASS.pop(Parcel, None)
        codec._SCHEMA_NAMES.pop(name, None)
        entry = codec._CARPENTED.pop(name, None)
        if entry is not None:
            codec._CARPENTED_BY_CLASS.pop(entry[0], None)


def test_a_reregistered_enum_takes_its_new_name_at_once():
    import enum

    import codec_reference as ref
    from corda_tpu.core.serialization import codec

    class Phase(enum.Enum):
        OPEN = 1
        SHUT = 2

    with pytest.raises(SerializationError):
        serialize(Phase.OPEN)
    serializable("test.forget.Phase")(Phase)
    try:
        first = serialize(Phase.OPEN)
        assert deserialize(first) is Phase.OPEN
        serializable("test.forget.PhaseRenamed")(Phase)
        second = serialize(Phase.OPEN)
        assert second != first and b"PhaseRenamed" in second
        assert second == ref.serialize(Phase.OPEN)
        assert deserialize(second) is Phase.OPEN
    finally:
        codec._ENUM_REGISTRY.pop("test.forget.Phase", None)
        codec._ENUM_REGISTRY.pop("test.forget.PhaseRenamed", None)
    with pytest.raises(SerializationError):
        deserialize(second)


def test_the_table_keeps_no_class_it_refused():
    from corda_tpu.core.serialization import codec

    class Stranger:
        pass

    with pytest.raises(SerializationError):
        serialize(Stranger())
    assert Stranger not in codec._ENCODERS
    serialize([1, "a", b"b", None, True, (2,), {"k": 1}, {3}])
    assert {int, list, tuple, dict, set} <= set(codec._ENCODERS)


@pytest.mark.parametrize("value", [
    type("Int", (int,), {})(5), type("Str", (str,), {})("s")])
def test_a_subclass_of_a_plain_type_is_judged_as_the_reference_judges_it(value):
    """An ``int`` or ``str`` subclass is classified as its base and then
    refused by the strict packer: a TypeError from both walks, as before."""
    import codec_reference as ref

    for encode in (serialize, ref.serialize):
        with pytest.raises(TypeError):
            encode(value)


def test_a_tuple_subclass_and_byte_likes_encode_as_the_reference_encodes_them():
    import collections

    import codec_reference as ref

    Point = collections.namedtuple("Point", "x y")
    for value in (Point(1, (2, 3)), bytearray(b"ab"), memoryview(b"cd"),
                  [Point(0, 0), bytearray(b"")]):
        assert serialize(value) == ref.serialize(value)


def _message(wire) -> bytes:
    from corda_tpu.core.serialization import codec
    return codec._MAGIC + codec._packb(wire)


def _hostile_wires():
    """A float, a native msgpack map and a msgpack timestamp wherever a
    value can sit: none has a place in the wire model."""
    import msgpack

    from corda_tpu.core.serialization import codec

    def ext(code, payload):
        return msgpack.ExtType(code, msgpack.packb(payload, use_bin_type=True))

    stamp = msgpack.Timestamp(1, 0)
    for name, bad in (("float", 1.5), ("map", {"a": 1}), ("empty_map", {}),
                      ("timestamp", stamp)):
        packed = msgpack.packb(bad, use_bin_type=True)
        yield f"{name}_alone", bad
        yield f"{name}_in_a_list", [1, bad]
        yield f"{name}_in_a_nested_list", [1, [2, [bad]]]
        yield f"{name}_as_a_field", ext(codec._EXT_OBJ,
                                        ["DigitalSignature", [bad]])
        yield f"{name}_in_a_fields_list", ext(
            codec._EXT_OBJ, ["DigitalSignature", [[b"x", [bad]]]])
        yield f"{name}_as_a_map_value", ext(
            codec._EXT_MAP, [[msgpack.packb("k"), bad]])
        yield f"{name}_in_a_map_values_list", ext(
            codec._EXT_MAP, [[msgpack.packb("k"), [bad]]])
        yield f"{name}_as_a_map_key", ext(codec._EXT_MAP, [[packed, 1]])
        yield f"{name}_in_a_map_key", ext(
            codec._EXT_MAP, [[msgpack.packb([bad], use_bin_type=True), 1]])
        yield f"{name}_as_a_set_element", ext(codec._EXT_SET, [packed])
        yield f"{name}_in_a_schemad_object", ext(
            codec._EXT_OBJ_SCHEMA, ["test.hostile.Bag", ["a"], [[bad]]])


@pytest.mark.parametrize("case", [n for n, _w in _hostile_wires()])
def test_what_the_wire_model_has_no_place_for_is_refused_everywhere(case):
    import msgpack

    import codec_reference as ref

    wire = dict(_hostile_wires())[case]
    blob = b"\xc0\x9d\xa1\x01" + msgpack.packb(wire, use_bin_type=True)
    for decode in (deserialize, ref.deserialize):
        with pytest.raises(SerializationError):
            decode(blob)


def test_an_objects_fields_are_a_list_or_the_object_is_refused():
    """Where the old walk iterated whatever stood in the place of the fields
    (a string's characters, a bytes' integers), the codec wants the list."""
    import msgpack

    from corda_tpu.core.serialization import codec

    for fields in ("x", b"\x01", 5, None, True):
        with pytest.raises(SerializationError):
            deserialize(_message(msgpack.ExtType(
                codec._EXT_OBJ, codec._packb(["DigitalSignature", fields]))))
    ok = deserialize(_message(msgpack.ExtType(
        codec._EXT_OBJ, codec._packb(["DigitalSignature", [b"\x01"]]))))
    assert ok.bytes == b"\x01"


def _nested(levels: int) -> bytes:
    """``levels`` sets, each the only element of the next: every level is a
    packed payload inside a packed payload."""
    import msgpack

    from corda_tpu.core.serialization import codec

    packed = msgpack.packb(7)
    for _ in range(levels):
        packed = msgpack.packb(msgpack.ExtType(
            codec._EXT_SET, msgpack.packb([packed], use_bin_type=True)))
    return b"\xc0\x9d\xa1\x01" + packed


def test_a_message_nested_past_the_bound_is_refused_not_a_crash():
    """Each nesting level holds a msgpack context on the C stack: a peer
    that nests payloads a thousand deep gets a SerializationError, from any
    thread, and the process goes on (the old walk took ~300 levels and a
    RecursionError it caught; an unbounded one-pass decode overflows the C
    stack at ~190)."""
    import threading

    from corda_tpu.core.serialization import codec

    value = deserialize(_nested(codec._MAX_DEPTH // 2 - 1))
    for _ in range(codec._MAX_DEPTH // 2 - 1):
        (value,) = value
    assert value == 7
    outcomes = []

    def decode(levels):
        outcomes.append(_verdict(deserialize, _nested(levels))[0])

    for levels in (codec._MAX_DEPTH, 200, 1000):
        thread = threading.Thread(target=decode, args=(levels,))
        thread.start()
        thread.join()
    assert outcomes == ["refused"] * 3


def test_the_deepest_honest_messages_are_far_from_the_bound(ledger_requests):
    """How deep the ledger's own messages nest, measured with the codec's
    own hook: a request, a checkpoint-like list of transactions in a map."""
    from corda_tpu.core.serialization import codec

    _transactions, requests = ledger_requests
    deepest = 0
    for value in (requests[3], {"log": [[requests[5]], {"k": {1, 2}}]}):
        blob = serialize(value)
        # the smallest bound that still decodes it
        for bound in range(1, codec._MAX_DEPTH + 1):
            unpack = codec._too_deep
            for _ in range(bound):
                _hook, unpack = codec._level(unpack)
            try:
                unpack(blob[4:])
            except SerializationError:
                continue
            deepest = max(deepest, bound)
            break
    assert 0 < deepest <= codec._MAX_DEPTH // 2, deepest


def test_threads_encode_side_by_side_to_the_same_bytes(ledger_requests):
    """One Packer a thread and one table for all of them: sixteen threads
    (more than the cores) encode and decode the same requests under a
    shortened switch interval, while another registers and takes out a
    class (every write empties the table under them), and each gives the
    bytes one thread gives."""
    import dataclasses
    import sys
    import threading

    from corda_tpu.core.serialization import codec

    @dataclasses.dataclass(frozen=True)
    class Passerby:
        n: int

    _transactions, requests = ledger_requests
    sample = requests[:60]
    expected = [serialize(r) for r in sample]
    failures, done = [], threading.Event()

    def work():
        try:
            for _ in range(4):
                blobs = [serialize(r) for r in sample]
                if blobs != expected or \
                        [serialize(deserialize(b)) for b in blobs] != expected:
                    failures.append("differs")
        except Exception as e:      # a worker's failure fails the test
            failures.append(repr(e))

    def churn():
        while not done.is_set():
            codec.register_type("test.forget.Passerby", Passerby)
            if deserialize(serialize(Passerby(1))) != Passerby(1):
                failures.append("the churner's own class")
            codec._REGISTRY.pop("test.forget.Passerby", None)
            codec._BY_CLASS.pop(Passerby, None)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(16)]
        churner = threading.Thread(target=churn)
        for t in threads + [churner]:
            t.start()
        for t in threads:
            t.join(timeout=120)
        done.set()
        churner.join(timeout=30)
    finally:
        done.set()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads + [churner])
    assert not failures, failures[:3]


def test_the_generated_constructor_is_the_old_lambda(ledger_requests):
    """The default ``from_fields`` of a dataclass, generated per class: list
    fields frozen to tuples, a shorter message filled from trailing
    defaults, too many fields a TypeError."""
    import dataclasses

    from corda_tpu.core.serialization import codec

    @dataclasses.dataclass(frozen=True)
    class Row:
        key: bytes
        count: int
        parts: tuple
        note: str | None = None
        anything: object = None

    build = codec._constructor(Row)
    assert build([b"k", 1, [1, [2]], "n", [3]]) == \
        Row(b"k", 1, (1, [2]), "n", (3,))
    assert build([b"k", 1, []]) == Row(b"k", 1, ())
    assert build([b"k", 1, [], None, "x"]).anything == "x"
    with pytest.raises(TypeError):
        build([b"k", 1, [], None, None, "one too many"])
    with pytest.raises(TypeError):
        build([b"k"])
    assert codec._is_scalar("int | None") and codec._is_scalar(int) \
        and not codec._is_scalar("tuple") and not codec._is_scalar("Any") \
        and not codec._is_scalar("int | tuple")
