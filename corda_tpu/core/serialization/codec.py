"""The canonical binary codec.

Wire model: every value is transformed into a *wire tree* of msgpack-safe primitives
(None, bool, int64, bytes, str, list) plus tagged ExtType wrappers for everything
else, then packed with msgpack (C implementation):

- ``ExtType(1, …)``  OBJ     — registered type: packb([type_name, [field wires…]])
- ``ExtType(2, …)``  MAP     — dict: packb([[k, v]…]) sorted by packed key bytes
- ``ExtType(3, …)``  SET     — set/frozenset: packb([…]) sorted by packed bytes
- ``ExtType(4, …)``  BIGINT  — arbitrary-precision int: sign byte + magnitude
- ``ExtType(5, …)``  ENUM    — packb([enum_type_name, member_name])
- ``ExtType(6, …)``  INSTANT — UTC datetime as epoch-microseconds (big-endian i64)
- ``ExtType(7, …)``  OBJ with its field names — a carpentable object

Registered types declare their wire fields; deserialization only ever constructs
registered types (whitelist enforcement).

**How a value finds its encoder.** ``to_wire`` looks ``type(obj)`` up in ONE table
(``_ENCODERS``). A type met for the first time is classified by ``_learn``, the ladder
of subclass tests in the order the wire model fixes (``bool`` before ``int``, an ``int``
subclass as an int, a ``tuple`` subclass as a sequence, ``bytearray`` / ``memoryview``
as bytes, floats refused, then enums, registered classes, carpented bags), and the
answer is kept: a registered class's encoder holds its field getter, its name and
whether it carries its schema, so an object costs one look-up, not one per registry.
A class nobody registered is refused every time and never kept, so the table holds no
class that is not the codec's to encode.

**When the table forgets.** The registries (``_REGISTRY``, ``_BY_CLASS``,
``_SCHEMA_NAMES``, ``_ENUM_REGISTRY``, ``_CARPENTED``, ``_CARPENTED_BY_CLASS``) are
``_Table`` dicts: ANY write to one of them, by ``register_type``, ``serializable``,
``carpented_class`` or a test that takes a name out again, empties ``_ENCODERS``, so a
later real registration wins at once. Decoding keeps no table of its own: it reads the
registries, one look-up an object.

**How a message is decoded.** ONE unpack, whose ``ext_hook`` builds each object as the
unpacker meets its payload (no ``ExtType`` is made, nothing is walked twice). A payload
is itself packed, so the hook unpacks it with the hook of the next nesting level;
``_MAX_DEPTH`` levels are made once at import and the last refuses, because every level
holds a msgpack context (~40 KB) on the C stack and a peer must not be able to overflow
it. What the unpacker can produce and the wire model has no place for (a float, a
native map, a msgpack timestamp) is refused wherever a list reaches the result.

The walk this module had before, a ladder of ``isinstance`` tests a value and a second
walk over a tree of ``ExtType``, lives on unedited as the plain reference the tests hold
this one to: ``tests/codec_reference.py``.
"""
from __future__ import annotations

import dataclasses
import datetime
import enum
import operator
import threading
from typing import Any, Callable

import msgpack

from ..crypto.secure_hash import SecureHash

FORMAT_VERSION = 1
_MAGIC = b"\xc0\x9d\xa1" + bytes([FORMAT_VERSION])  # leads every top-level message

_EXT_OBJ = 1
_EXT_MAP = 2
_EXT_SET = 3
_EXT_BIGINT = 4
_EXT_ENUM = 5
_EXT_INSTANT = 6  # UTC datetime as epoch-microseconds (big-endian i64)
_EXT_OBJ_SCHEMA = 7  # [name, [field names], fields] — carpentable object

_I64_MIN, _I64_MAX = -(2**63), 2**63 - 1


class SerializationError(Exception):
    pass


_EPOCH = datetime.datetime(1970, 1, 1, tzinfo=datetime.timezone.utc)


def exact_epoch_micros(t: datetime.datetime) -> int:
    """Exact integer epoch-microseconds (no float path — ``timestamp()`` truncation
    corrupts ~1% of microsecond values, which would fork consensus hashes)."""
    if t.tzinfo is None:
        t = t.replace(tzinfo=datetime.timezone.utc)
    return (t - _EPOCH) // datetime.timedelta(microseconds=1)


# ---------------------------------------------------------------------------
# Type registry (the whitelist)
# ---------------------------------------------------------------------------

#: encoders learnt by class (module docstring): emptied by any write to a registry
_ENCODERS: dict[type, Callable] = {}
_generation = 0      # counts the forgettings: what was learnt across one is not kept


def _forget() -> None:
    global _generation
    _generation += 1
    _ENCODERS.clear()


class _Table(dict):
    """A registry: a dict whose every write forgets the learnt encoders."""

    __slots__ = ()


def _forgetting(method):
    def write(self, *args, **kwargs):
        try:
            return method(self, *args, **kwargs)
        finally:
            _forget()
    return write


for _name in ("__setitem__", "__delitem__", "__ior__", "pop", "popitem",
              "clear", "update", "setdefault"):
    setattr(_Table, _name, _forgetting(getattr(dict, _name)))
del _name


# name -> (cls, to_fields, from_fields)
_REGISTRY: dict[str, tuple[type, Callable, Callable]] = _Table()
_BY_CLASS: dict[type, str] = _Table()
_ENUM_REGISTRY: dict[str, type] = _Table()
# schema-carrying types (name -> field names); their wire form embeds the
# field names so receivers WITHOUT the class can still materialize them
_SCHEMA_NAMES: dict[str, list[str]] = _Table()
# receiver-side synthesized classes for unknown schema'd names
# (ClassCarpenter.kt:30-447 analog) — deliberately NOT in _REGISTRY: the
# trusted whitelist stays authoritative, and a later real registration of
# the same name simply wins for subsequent decodes
_CARPENTED: dict[str, tuple[type, list[str]]] = _Table()
_CARPENTED_BY_CLASS: dict[type, str] = _Table()


def _field_getter(names: list[str]) -> Callable[[Any], tuple]:
    """``obj -> (obj.a, obj.b, ...)`` in one C call."""
    if len(names) >= 2:
        return operator.attrgetter(*names)
    if not names:
        return lambda obj: ()
    one = operator.attrgetter(names[0])
    return lambda obj: (one(obj),)


#: a field annotated with nothing but these cannot hold a sequence, so the
#: generated constructor does not look for one to freeze
_SCALAR_ANNOTATIONS = frozenset(("int", "str", "bytes", "bool", "None"))


def _is_scalar(annotation) -> bool:
    if not isinstance(annotation, str):
        annotation = getattr(annotation, "__name__", None) or str(annotation)
    return all(part.strip() in _SCALAR_ANNOTATIONS
               for part in annotation.split("|"))


def _constructor(cls: type) -> Callable[[list], Any]:
    """The default ``from_fields`` of a dataclass, generated once per class:
    positional construction in declaration order. Sequences decode as lists
    and dataclass wire types are immutable, so a top-level list field is
    coerced back to a tuple for equality/hashability: the generated code
    tests only the fields whose annotation leaves room for a sequence, and a
    message with another number of fields than the class declares (a peer of
    another version: trailing defaults) takes the general form."""
    def general(fields):
        return cls(*[tuple(f) if type(f) is list else f for f in fields])

    declared = dataclasses.fields(cls)
    if not declared:
        return general
    names = [f"a{i}" for i in range(len(declared))]
    args = [n if _is_scalar(f.type) else f"tuple({n}) if type({n}) is list else {n}"
            for n, f in zip(names, declared)]
    source = (f"def from_fields(fields):\n"
              f"    if len(fields) != {len(declared)}:\n"
              f"        return general(fields)\n"
              f"    {', '.join(names)}, = fields\n"
              f"    return cls({', '.join(args)})\n")
    scope = {"cls": cls, "general": general}
    exec(source, scope)
    return scope["from_fields"]


def register_type(name: str, cls: type,
                  to_fields: Callable[[Any], list] | None = None,
                  from_fields: Callable[[list], Any] | None = None,
                  carry_schema: bool = False) -> None:
    """Register a type for serialization. Defaults handle dataclasses (fields in
    declaration order — deterministic).

    ``carry_schema=True`` writes the field NAMES onto the wire so a receiver
    that does not know the class can carpent a property-bag stand-in
    (see :func:`carpented_class`) — use it for types expected to travel to
    nodes without the defining CorDapp module."""
    if name in _REGISTRY and _REGISTRY[name][0] is not cls:
        raise SerializationError(f"Serialization name collision: {name!r}")
    if carry_schema and (to_fields is not None or from_fields is not None):
        # the carried names are the dataclass's declared fields; a custom
        # codec could reorder/transform values, silently binding receivers'
        # carpented attributes to the wrong values
        raise SerializationError(
            "carry_schema requires the default dataclass field codec")
    if to_fields is None or from_fields is None or carry_schema:
        if not dataclasses.is_dataclass(cls):
            raise SerializationError(
                f"{cls!r} is not a dataclass; provide to_fields/from_fields"
                + (" (carry_schema needs dataclass field names)"
                   if carry_schema else ""))
        field_names = [f.name for f in dataclasses.fields(cls)]
        to_fields = to_fields or _field_getter(field_names)
        from_fields = from_fields or _constructor(cls)
        if carry_schema:
            _SCHEMA_NAMES[name] = field_names
    _REGISTRY[name] = (cls, to_fields, from_fields)
    _BY_CLASS[cls] = name


#: Cap on distinct carpented names: classes are heavyweight and live
#: instances pin them, so eviction would fork a name across two classes —
#: refuse instead (no legitimate peer set ships thousands of state types).
_CARPENTED_MAX = 4096
#: Cap on fields per carpented schema: make_dataclass execs a class body
#: sized by the field count, and carpented classes are pinned for the
#: process lifetime — an unbounded count is a wire-reachable memory/CPU
#: sink. No legitimate state type approaches this.
_CARPENTED_MAX_FIELDS = 256


def carpented_class(name: str, field_names: list[str]) -> type:
    """Synthesize (once per name+schema) a frozen-dataclass property bag for
    a schema'd wire object whose real class is absent — the runtime class
    synthesis of the reference's ClassCarpenter, minus bytecode: the bag is
    inert data (no methods), so the deserialization whitelist's gadget
    protection is preserved.

    SCHEMA EVOLUTION: a second schema under the same name carpents the
    UNION of all fields seen so far (stable order: first-seen first) and
    becomes the name's class for subsequent decodes — every field defaults
    to None, so a wire form carrying any subset still materializes
    (reference evolution direction: ClassCarpenter.kt:30-447 +
    amqp/SerializerFactory.kt).  Each carpented CLASS remembers its own
    schema (``__corda_carpented_fields__``): instances re-serialize under
    the schema they were built with — a bag decoded before an evolution
    stays bit-exact on re-serialization; a union bag re-serializes under
    the union schema.  Unions grow monotonically and the per-schema field
    cap bounds them, so a hostile peer cannot mint unbounded classes for
    one name.  Every hostile-input failure mode is a SerializationError."""
    entry = _CARPENTED.get(name)
    if entry is not None:
        cls, known = entry
        if known == list(field_names):
            return cls
        union = list(known) + [fn for fn in field_names if fn not in known]
        if union == known:        # subset of what we already know
            return cls
        return _carpent(name, union)
    return _carpent(name, list(field_names))


#: Total class syntheses (first carpents AND union evolutions): every
#: synthesized class is pinned for the process lifetime, so the budget
#: must count evolutions too — otherwise a hostile peer could stream
#: one-field-at-a-time schema changes and mint ~256 classes per name
#: beyond the name cap.
_carpent_count = 0


def _carpent(name: str, field_names: list[str]) -> type:
    import keyword

    global _carpent_count
    if _carpent_count >= _CARPENTED_MAX:
        raise SerializationError(
            f"Carpented-class budget ({_CARPENTED_MAX}) exhausted; "
            f"refusing to synthesize {name!r}")
    if not isinstance(name, str) or not name:
        raise SerializationError(f"Bad carpented type name {name!r}")
    if len(field_names) > _CARPENTED_MAX_FIELDS:
        raise SerializationError(
            f"Carpented schema for {name!r} has {len(field_names)} fields "
            f"(limit {_CARPENTED_MAX_FIELDS})")
    seen = set()
    for fn in field_names:
        if (not isinstance(fn, str) or not fn.isidentifier()
                or fn.startswith("__") or keyword.iskeyword(fn)
                or fn in seen):
            raise SerializationError(f"Bad carpented field name {fn!r}")
        seen.add(fn)
    try:
        cls = dataclasses.make_dataclass(
            name.rsplit(".", 1)[-1] or "Carpented",
            [(fn, Any, dataclasses.field(default=None))
             for fn in field_names],
            frozen=True, eq=True)
    except (TypeError, ValueError) as e:
        raise SerializationError(
            f"Cannot carpent {name!r}: {e}") from e
    cls.__corda_carpented__ = name
    cls.__corda_carpented_fields__ = list(field_names)
    _CARPENTED[name] = (cls, list(field_names))
    _CARPENTED_BY_CLASS[cls] = name
    _carpent_count += 1
    return cls


def serializable(name: str | None = None,
                 to_fields: Callable | None = None,
                 from_fields: Callable | None = None):
    """Class decorator: ``@serializable()`` registers the class under its qualname."""
    def wrap(cls):
        reg_name = name or cls.__name__
        if issubclass(cls, enum.Enum):
            cls.__corda_enum_name__ = reg_name
            _ENUM_REGISTRY[reg_name] = cls      # the write forgets: last
        else:
            register_type(reg_name, cls, to_fields, from_fields)
        return cls
    return wrap


def registered_name(cls: type) -> str | None:
    return _BY_CLASS.get(cls)


# ---------------------------------------------------------------------------
# Encoding: value -> wire tree, by the encoder its type learnt
# ---------------------------------------------------------------------------

class _Packers(threading.local):
    """One ``msgpack.Packer`` a thread, reused for every object: a Packer is
    not to be shared between threads, and ``msgpack.packb`` makes (and maps
    a buffer for) a new one a call."""

    def __init__(self):
        self.pack = msgpack.Packer(use_bin_type=True, strict_types=True).pack


_packers = _Packers()
_ExtType = msgpack.ExtType
_new_tuple = tuple.__new__      # an ExtType without its constructor's checks


def _packb(wire) -> bytes:
    return _packers.pack(wire)


def to_wire(obj: Any) -> Any:
    encode = _ENCODERS.get(type(obj))
    if encode is None:
        encode = _learn(type(obj))
    return encode(obj)


#: values of exactly these types ARE their wire form: a sequence's or an
#: object's loop passes them on without a call (the table says the same)
_PLAIN = frozenset((type(None), bool, str, bytes))


def _wires(values) -> list:
    """``[to_wire(v) for v in values]``, without a call a value for the
    look-up."""
    known = _ENCODERS.get
    return [v if type(v) in _PLAIN
            else (known(type(v)) or _learn(type(v)))(v) for v in values]


def _same(obj):
    return obj


def _int_to_wire(obj):
    if _I64_MIN <= obj <= _I64_MAX:
        return obj
    sign = 1 if obj >= 0 else 0
    mag = abs(obj)
    return _ExtType(_EXT_BIGINT, bytes([sign]) +
                    mag.to_bytes((mag.bit_length() + 7) // 8 or 1, "big"))


def _float_refused(obj):
    raise SerializationError(
        "Floats are not permitted in consensus data (non-deterministic); "
        "use integer quantities (Amount semantics)")


_first = operator.itemgetter(0)


def _map_to_wire(obj):
    pack = _packers.pack
    pairs = sorted(([pack(to_wire(k)), to_wire(v)] for k, v in obj.items()),
                   key=_first)
    return _ExtType(_EXT_MAP, pack(pairs))


def _set_to_wire(obj):
    pack = _packers.pack
    return _ExtType(_EXT_SET, pack(sorted(pack(to_wire(x)) for x in obj)))


def _instant_to_wire(obj):
    return _ExtType(_EXT_INSTANT,
                    exact_epoch_micros(obj).to_bytes(8, "big", signed=True))


def _enum_encoder(ename: str):
    def enum_to_wire(obj):
        return _ExtType(_EXT_ENUM, _packers.pack([ename, obj.name]))
    return enum_to_wire


def _object_encoder(name: str, to_fields: Callable, schema: list | None):
    """A registered class's encoder (or a carpented bag's, under its own
    schema): the field getter, the name and the schema are bound here, once.
    The loop over the fields is ``_wires``'s, written out: an object is the
    codec's innermost loop."""
    known = _ENCODERS.get
    if schema is None:
        def object_to_wire(obj):
            return _new_tuple(_ExtType, (_EXT_OBJ, _packers.pack([name, [
                f if type(f) in _PLAIN
                else (known(type(f)) or _learn(type(f)))(f)
                for f in to_fields(obj)]])))
    else:
        def object_to_wire(obj):
            return _new_tuple(_ExtType, (_EXT_OBJ_SCHEMA, _packers.pack(
                [name, schema, _wires(to_fields(obj))])))
    return object_to_wire


def _learn(cls: type) -> Callable[[Any], Any]:
    """Classify a type met for the first time, as the wire model orders the
    tests, and keep the answer in ``_ENCODERS`` (not a refusal of a class
    nobody registered: that is found again every time)."""
    generation = _generation
    if cls is type(None) or issubclass(cls, (bool, str)):
        encode = _same
    elif issubclass(cls, int):
        encode = _int_to_wire
    elif issubclass(cls, (bytes, bytearray, memoryview)):
        encode = bytes
    elif issubclass(cls, float):
        encode = _float_refused
    elif issubclass(cls, (list, tuple)):
        encode = _wires
    elif issubclass(cls, dict):
        encode = _map_to_wire
    elif issubclass(cls, (set, frozenset)):
        encode = _set_to_wire
    elif issubclass(cls, datetime.datetime):
        encode = _instant_to_wire
    elif issubclass(cls, enum.Enum):
        ename = getattr(cls, "__corda_enum_name__", None)
        if ename is None:
            raise SerializationError(f"Enum {cls!r} is not @serializable")
        encode = _enum_encoder(ename)
    else:
        name = _BY_CLASS.get(cls)
        if name is not None:
            encode = _object_encoder(name, _REGISTRY[name][1],
                                     _SCHEMA_NAMES.get(name))
        else:
            cname = _CARPENTED_BY_CLASS.get(cls)
            if cname is None:
                raise SerializationError(
                    f"Type {cls.__module__}.{cls.__qualname__} is not registered "
                    f"for serialization (whitelist violation)")
            # carpented bag: re-serializes under ITS OWN schema (the one
            # its class was built with), so pre-evolution instances stay
            # bit-exact and union bags emit the union schema
            field_names = cls.__corda_carpented_fields__
            encode = _object_encoder(cname, _field_getter(field_names),
                                     field_names)
    if generation == _generation:       # no registry was written meanwhile
        _ENCODERS[cls] = encode
    return encode


# ---------------------------------------------------------------------------
# Decoding: ONE unpack, the objects built as the unpacker meets them
# ---------------------------------------------------------------------------

#: nesting levels of packed payloads a message may have (module docstring):
#: a ledger's deepest honest message has about a dozen
_MAX_DEPTH = 32

#: what the unpacker can hand over that the wire model has no place for
_UNEXPECTED = (float, msgpack.Timestamp)
#: a list holding one of these is looked into (``_vet``)
_SUSPECT = frozenset(_UNEXPECTED + (list,))


def _unexpected(value):
    return SerializationError(
        f"Unexpected wire value of type {type(value)!r}")


def _map_refused(pairs):
    raise SerializationError(f"Unexpected wire value of type {dict!r}")


def _vet(values: list) -> None:
    """Refuse a float or a msgpack timestamp anywhere in a list the unpacker
    made, lists inside it included."""
    for v in values:
        kind = type(v)
        if kind is list:
            _vet(v)
        elif kind in _UNEXPECTED:
            raise _unexpected(v)


def _vetted(value):
    """A whole unpacked value (a message, a map's key, a set's element)."""
    kind = type(value)
    if kind is list:
        _vet(value)
    elif kind in _UNEXPECTED:
        raise _unexpected(value)
    return value


def _freeze(v):
    return tuple(v) if type(v) is list else v


def _too_deep(data):
    raise SerializationError(
        f"Nested deeper than {_MAX_DEPTH} packed payloads")


def _bigint(data):
    if len(data) < 2:
        raise SerializationError("Truncated bigint")
    val = int.from_bytes(data[1:], "big")
    return val if data[0] else -val


def _instant(data):
    micros = int.from_bytes(data, "big", signed=True)
    return datetime.datetime.fromtimestamp(micros / 1_000_000,
                                           tz=datetime.timezone.utc)


def _a_list(value, what: str) -> list:
    """The wire model's containers are lists: where the old walk iterated
    whatever stood in a list's place, this one wants the list."""
    if type(value) is not list:
        raise SerializationError(f"{what}: a list was expected, "
                                 f"not {type(value).__name__}")
    return value


def _level(unpack_payload: Callable[[bytes], Any]):
    """One nesting level's ``(ext_hook, unpack)``: the hook turns an ext
    payload into its object, unpacking what the payload packs with
    ``unpack_payload``, the level below's."""

    def a_map(data):
        pairs = _a_list(unpack_payload(data), "Map")
        _vet(pairs)
        return {_freeze(_vetted(unpack_payload(k))): v for k, v in pairs}

    def a_set(data):
        return frozenset(_freeze(_vetted(unpack_payload(e)))
                         for e in _a_list(unpack_payload(data), "Set"))

    def an_enum(data):
        ename, member = unpack_payload(data)
        cls = _ENUM_REGISTRY.get(ename)
        if cls is None:
            raise SerializationError(f"Enum {ename!r} is not whitelisted")
        return cls[member]

    def a_schemad_object(data):
        name, field_names, fields = unpack_payload(data)
        _a_list(field_names, f"Schema'd object {name!r}")
        _vet(_a_list(fields, f"Schema'd object {name!r}"))
        return _schemad(name, field_names, fields)

    decoders = {_EXT_MAP: a_map, _EXT_SET: a_set, _EXT_BIGINT: _bigint,
                _EXT_ENUM: an_enum, _EXT_INSTANT: _instant,
                _EXT_OBJ_SCHEMA: a_schemad_object}

    def ext_hook(code, data):
        if code == _EXT_OBJ:            # the common one, without a second call
            name, fields = unpack_payload(data)
            entry = _REGISTRY.get(name)
            if entry is None:
                raise SerializationError(f"Type {name!r} is not whitelisted")
            if type(fields) is not list:
                _a_list(fields, f"Object {name!r}")
            for f in fields:
                if type(f) in _SUSPECT:
                    _vet(fields)
                    break
            return entry[2](fields)
        decode = decoders.get(code)
        if decode is None:
            raise SerializationError(f"Unknown ext code {code}")
        return decode(data)

    def unpack(data):
        return msgpack.unpackb(data, raw=False, strict_map_key=False,
                               ext_hook=ext_hook,
                               object_pairs_hook=_map_refused)

    return ext_hook, unpack


def _levels():
    hook, unpack = None, _too_deep
    for _ in range(_MAX_DEPTH):
        hook, unpack = _level(unpack)
    return hook, unpack


_ext_hook, _unpackb = _levels()     # the outermost level's


def _schemad(name, field_names: list, fields: list):
    """A schema-carrying object's ``(name, field names, decoded fields)`` ->
    the registered class's instance, bound by name, or a carpented bag."""
    if len(field_names) != len(fields):
        raise SerializationError(
            f"Schema'd object {name!r}: {len(field_names)} names "
            f"vs {len(fields)} fields")
    if len(set(field_names)) != len(field_names):
        # a duplicated name is always hostile/corrupt wire: binding
        # would silently keep only the last value (dict semantics in
        # both the by-name rebind and the carpenter kwargs)
        seen: set = set()
        dupes = sorted({fn for fn in field_names
                        if fn in seen or seen.add(fn)})
        raise SerializationError(
            f"Schema'd object {name!r}: duplicate field names "
            f"{dupes}")
    entry = _REGISTRY.get(name)
    if entry is None:
        cls = carpented_class(name, field_names)
        return cls(**{fn: _freeze(f) for fn, f in zip(field_names, fields)})
    # the real class is known: it wins
    cls, _, from_fields = entry
    # Bind by NAME against the local declaration, never by wire
    # position: a peer whose version declares fields in a
    # different order (schema skew) must not silently bind
    # values to the wrong attributes.
    local = _SCHEMA_NAMES.get(name)
    if local is None and dataclasses.is_dataclass(cls):
        local = [f.name for f in dataclasses.fields(cls)]
    if local is not None and field_names != local:
        if sorted(field_names) == sorted(local):
            by_name = dict(zip(field_names, fields))
            fields = [by_name[n] for n in local]
        elif name in _SCHEMA_NAMES:
            # SCHEMA EVOLUTION (reference ClassCarpenter.kt +
            # amqp/SerializerFactory.kt evolution direction):
            # a peer on another VERSION of the type — fields
            # it doesn't carry fill from local dataclass
            # defaults; fields the local version dropped are
            # ignored. Only carry_schema types qualify (their
            # codec is the default dataclass one, so binding
            # by declaration order is sound); no default for
            # a missing field ⇒ genuinely incompatible.
            return _evolved_decode(name, cls, local, field_names, fields)
        else:
            raise SerializationError(
                f"Schema'd object {name!r}: carried fields "
                f"{sorted(field_names)} do not match local "
                f"declaration {sorted(local)}")
    try:
        return from_fields(fields)
    except TypeError as e:
        raise SerializationError(
            f"Schema'd object {name!r} does not fit local "
            f"class: {e}") from e


def _evolved_decode(name: str, cls, local: list[str], field_names, fields):
    """Decode a schema'd object whose carried field set differs from the
    local version of the class: carried-and-local fields bind by name,
    locally-ADDED fields take the dataclass default (the v1→v2 direction),
    carried-but-REMOVED fields are dropped (v2→v1).  A locally-added field
    WITHOUT a default is a genuine incompatibility and fails typed."""
    by_name = dict(zip(field_names, fields))
    spec = {f.name: f for f in dataclasses.fields(cls)}
    vals = []
    for n in local:
        if n in by_name:
            vals.append(_freeze(by_name[n]))
            continue
        f = spec[n]
        # defaults freeze like carried values do (a list default becomes a
        # tuple): evolved instances must hash/compare like native ones
        if f.default is not dataclasses.MISSING:
            vals.append(_freeze(f.default))
        elif f.default_factory is not dataclasses.MISSING:
            vals.append(_freeze(f.default_factory()))
        else:
            raise SerializationError(
                f"Schema'd object {name!r}: peer version lacks field "
                f"{n!r} and the local class declares no default for it")
    try:
        return cls(*vals)
    except TypeError as e:
        raise SerializationError(
            f"Schema'd object {name!r} does not fit local class: {e}"
        ) from e


def from_wire(wire: Any) -> Any:
    """A wire tree (what ``to_wire`` gives) -> the value. ``deserialize``
    does not come this way: its unpacker never makes the tree."""
    if wire is None or isinstance(wire, (bool, int, str, bytes)):
        return wire
    # NB: ExtType subclasses tuple, so it must be checked before the sequence case.
    if isinstance(wire, msgpack.ExtType):
        return _ext_hook(wire.code, wire.data)
    if isinstance(wire, (list, tuple)):
        return [from_wire(x) for x in wire]
    raise _unexpected(wire)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def serialize(obj: Any) -> bytes:
    return _MAGIC + _packers.pack(to_wire(obj))


def deserialize(data: bytes) -> Any:
    if len(data) < 4 or data[:3] != _MAGIC[:3]:
        raise SerializationError("Bad magic: not corda_tpu canonical bytes")
    if data[3] != FORMAT_VERSION:
        raise SerializationError(f"Unsupported format version {data[3]}")
    try:
        return _vetted(_unpackb(data[4:]))
    except SerializationError:
        raise
    except Exception as e:
        # Untrusted wire bytes must always fail typed, never leak raw decode errors.
        raise SerializationError(f"Malformed canonical bytes: {type(e).__name__}: {e}") from e


def serialized_hash(obj: Any) -> SecureHash:
    """Merkle component leaf hash: SHA-256 of the canonical bytes (magic included,
    so leaves are domain-separated from raw user bytes)."""
    return SecureHash.sha256(serialize(obj))
