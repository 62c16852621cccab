"""The codec's plain reference: the walk that stood before PR 46, moved here
UNEDITED (``to_wire`` / ``from_wire`` / ``_evolved_decode`` and their two
msgpack helpers, ``serialize`` / ``deserialize`` over them), for the tests to
hold the production codec to, byte for byte and object for object. Every
value climbs the ladder of ``isinstance`` tests; a message is unpacked into a
tree of ``ExtType`` and walked a second time. It reads the production
module's registries (the whitelist is one), and no production module imports
it."""
import dataclasses
import datetime
import enum
from typing import Any

import msgpack

from corda_tpu.core.serialization.codec import (  # noqa: F401
    _BY_CLASS, _CARPENTED_BY_CLASS, _ENUM_REGISTRY, _EXT_BIGINT, _EXT_ENUM,
    _EXT_INSTANT, _EXT_MAP, _EXT_OBJ, _EXT_OBJ_SCHEMA, _EXT_SET, _I64_MAX,
    _I64_MIN, _MAGIC, _REGISTRY, _SCHEMA_NAMES, FORMAT_VERSION,
    SerializationError, carpented_class, exact_epoch_micros)


def _packb(wire) -> bytes:
    return msgpack.packb(wire, use_bin_type=True, strict_types=True)


def to_wire(obj: Any) -> Any:
    if obj is None or isinstance(obj, (bool, str)):
        return obj
    if isinstance(obj, int) and not isinstance(obj, bool):
        if _I64_MIN <= obj <= _I64_MAX:
            return obj
        sign = 1 if obj >= 0 else 0
        mag = abs(obj)
        return msgpack.ExtType(_EXT_BIGINT, bytes([sign]) +
                               mag.to_bytes((mag.bit_length() + 7) // 8 or 1, "big"))
    if isinstance(obj, (bytes, bytearray, memoryview)):
        return bytes(obj)
    if isinstance(obj, float):
        raise SerializationError(
            "Floats are not permitted in consensus data (non-deterministic); "
            "use integer quantities (Amount semantics)")
    if isinstance(obj, (list, tuple)):
        return [to_wire(x) for x in obj]
    if isinstance(obj, dict):
        pairs = sorted(([_packb(to_wire(k)), to_wire(v)] for k, v in obj.items()),
                       key=lambda kv: kv[0])
        return msgpack.ExtType(_EXT_MAP, _packb(pairs))
    if isinstance(obj, (set, frozenset)):
        elems = sorted(_packb(to_wire(x)) for x in obj)
        return msgpack.ExtType(_EXT_SET, _packb(elems))
    if isinstance(obj, datetime.datetime):
        return msgpack.ExtType(_EXT_INSTANT,
                               exact_epoch_micros(obj).to_bytes(8, "big", signed=True))
    if isinstance(obj, enum.Enum):
        ename = getattr(type(obj), "__corda_enum_name__", None)
        if ename is None:
            raise SerializationError(f"Enum {type(obj)!r} is not @serializable")
        return msgpack.ExtType(_EXT_ENUM, _packb([ename, obj.name]))
    name = _BY_CLASS.get(type(obj))
    if name is None:
        cname = _CARPENTED_BY_CLASS.get(type(obj))
        if cname is not None:
            # carpented bag: re-serializes under ITS OWN schema (the one
            # its class was built with), so pre-evolution instances stay
            # bit-exact and union bags emit the union schema
            field_names = type(obj).__corda_carpented_fields__
            fields = [to_wire(getattr(obj, fn)) for fn in field_names]
            return msgpack.ExtType(_EXT_OBJ_SCHEMA,
                                   _packb([cname, field_names, fields]))
        raise SerializationError(
            f"Type {type(obj).__module__}.{type(obj).__qualname__} is not registered "
            f"for serialization (whitelist violation)")
    _, to_fields, _ = _REGISTRY[name]
    fields = [to_wire(f) for f in to_fields(obj)]
    schema = _SCHEMA_NAMES.get(name)
    if schema is not None:
        return msgpack.ExtType(_EXT_OBJ_SCHEMA, _packb([name, schema, fields]))
    return msgpack.ExtType(_EXT_OBJ, _packb([name, fields]))


def _unpackb(data: bytes):
    return msgpack.unpackb(data, raw=False, strict_map_key=False,
                           ext_hook=lambda c, d: msgpack.ExtType(c, d))


def from_wire(wire: Any) -> Any:
    if wire is None or isinstance(wire, (bool, int, str, bytes)):
        return wire
    # NB: ExtType subclasses tuple, so it must be checked before the sequence case.
    if isinstance(wire, msgpack.ExtType):
        code, data = wire.code, wire.data
        if code == _EXT_BIGINT:
            if len(data) < 2:
                raise SerializationError("Truncated bigint")
            val = int.from_bytes(data[1:], "big")
            return val if data[0] else -val
        if code == _EXT_MAP:
            return {_freeze(from_wire(_unpackb(k))): from_wire(v)
                    for k, v in _unpackb(data)}
        if code == _EXT_SET:
            return frozenset(_freeze(from_wire(_unpackb(e))) for e in _unpackb(data))
        if code == _EXT_INSTANT:
            micros = int.from_bytes(data, "big", signed=True)
            return datetime.datetime.fromtimestamp(micros / 1_000_000,
                                                   tz=datetime.timezone.utc)
        if code == _EXT_ENUM:
            ename, member = _unpackb(data)
            cls = _ENUM_REGISTRY.get(ename)
            if cls is None:
                raise SerializationError(f"Enum {ename!r} is not whitelisted")
            return cls[member]
        if code == _EXT_OBJ:
            name, fields = _unpackb(data)
            entry = _REGISTRY.get(name)
            if entry is None:
                raise SerializationError(f"Type {name!r} is not whitelisted")
            _, _, from_fields = entry
            return from_fields([from_wire(f) for f in fields])
        if code == _EXT_OBJ_SCHEMA:
            name, field_names, fields = _unpackb(data)
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
            if entry is not None:       # the real class is known: it wins
                cls, _, from_fields = entry
                # Bind by NAME against the local declaration, never by wire
                # position: a peer whose version declares fields in a
                # different order (schema skew) must not silently bind
                # values to the wrong attributes.
                local = _SCHEMA_NAMES.get(name)
                if local is None and dataclasses.is_dataclass(cls):
                    local = [f.name for f in dataclasses.fields(cls)]
                if local is not None and list(field_names) != local:
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
                        return _evolved_decode(name, cls, local,
                                               field_names, fields)
                    else:
                        raise SerializationError(
                            f"Schema'd object {name!r}: carried fields "
                            f"{sorted(field_names)} do not match local "
                            f"declaration {sorted(local)}")
                try:
                    return from_fields([from_wire(f) for f in fields])
                except TypeError as e:
                    raise SerializationError(
                        f"Schema'd object {name!r} does not fit local "
                        f"class: {e}") from e
            cls = carpented_class(name, field_names)
            return cls(**{fn: _freeze(from_wire(f))
                          for fn, f in zip(field_names, fields)})
        raise SerializationError(f"Unknown ext code {code}")
    if isinstance(wire, (list, tuple)):
        return [from_wire(x) for x in wire]
    raise SerializationError(f"Unexpected wire value of type {type(wire)!r}")


def _freeze(v):
    return tuple(v) if isinstance(v, list) else v


def _evolved_decode(name: str, cls, local: list[str], field_names, fields):
    """Decode a schema'd object whose carried field set differs from the
    local version of the class: carried-and-local fields bind by name,
    locally-ADDED fields take the dataclass default (the v1→v2 direction),
    carried-but-REMOVED fields are dropped (v2→v1).  A locally-added field
    WITHOUT a default is a genuine incompatibility and fails typed."""
    by_name = {fn: from_wire(v) for fn, v in zip(field_names, fields)}
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


def serialize(obj: Any) -> bytes:
    return _MAGIC + _packb(to_wire(obj))


def deserialize(data: bytes) -> Any:
    if len(data) < 4 or data[:3] != _MAGIC[:3]:
        raise SerializationError("Bad magic: not corda_tpu canonical bytes")
    if data[3] != FORMAT_VERSION:
        raise SerializationError(f"Unsupported format version {data[3]}")
    try:
        return from_wire(_unpackb(data[4:]))
    except SerializationError:
        raise
    except Exception as e:
        # Untrusted wire bytes must always fail typed, never leak raw decode errors.
        raise SerializationError(f"Malformed canonical bytes: {type(e).__name__}: {e}") from e
