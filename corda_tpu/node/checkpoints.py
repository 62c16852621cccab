"""Checkpoint model + storage for the replay-based flow state machine.

Reference parity: CheckpointStorage (node/services/api/CheckpointStorage.kt:10-28)
and DBCheckpointStorage (persistence/DBCheckpointStorage.kt:18-25). A checkpoint
here is NOT a serialized continuation (no Quasar): it is the *replay record* —
flow class + flow fields + the ordered responses consumed at each yield + the
session table. Resume = re-execute `call()` feeding the log (corda_tpu.flows
module docstring).

`FileCheckpointStorage` and `KvCheckpointStorage` add crash-durable atomic
persistence. A suspension writes a DELTA (`Checkpoint.log_from`): the durable
stores rewrite one small head per suspension and seal the log behind it into
segments that are written once, so a flow that suspends D times writes O(D)
bytes in all, not O(D^2).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field

from ..core.serialization import deserialize, serialize


@dataclass
class SessionSnapshot:
    """Persisted session state (statemachine session table row)."""

    peer_name: str
    our_session_id: int
    peer_session_id: int | None
    state: str
    received: list
    pending_out: list
    group: int = 0  # session group (sub-flow keying, statemachine)


@dataclass
class Checkpoint:
    run_id: str
    flow_class: str           # importable "module.QualName"
    flow_fields: dict         # flow __dict__ minus injected attrs
    response_log: list        # ordered responses consumed at yields
    sessions: list = field(default_factory=list)  # SessionSnapshot list
    #: what a suspension writes is a DELTA: ``response_log`` holds the
    #: entries from this index on, and what the storage holds below it
    #: stands. 0 (always, in what a storage hands back) is the whole log.
    log_from: int = 0

    @property
    def id(self) -> str:
        return self.run_id


class CheckpointStorage:
    """In-memory checkpoint store (reference CheckpointStorage SPI).

    ``add_checkpoint`` takes a delta (``Checkpoint.log_from``): the flow's
    fields and session table replace what is held, the log entries are
    appended, so a suspension costs what changed since the last one and not
    the flow's whole history. ``get_all_checkpoints`` hands back whole logs."""

    def __init__(self):
        self._checkpoints: dict[str, Checkpoint] = {}

    def add_checkpoint(self, cp: Checkpoint) -> None:
        if cp.log_from == 0:
            self._checkpoints[cp.id] = cp
            return
        held = self._checkpoints.get(cp.id)
        n_held = len(held.response_log) if held is not None else 0
        if cp.log_from > n_held:
            raise ValueError(
                f"checkpoint {cp.id} starts at log entry {cp.log_from}, the "
                f"storage holds {n_held}")
        del held.response_log[cp.log_from:]
        held.response_log.extend(cp.response_log)
        held.flow_fields, held.sessions = cp.flow_fields, cp.sessions

    def remove_checkpoint(self, cp_or_id) -> None:
        cp_id = cp_or_id if isinstance(cp_or_id, str) else cp_or_id.id
        self._checkpoints.pop(cp_id, None)

    def get_all_checkpoints(self) -> list[Checkpoint]:
        return list(self._checkpoints.values())


#: a durable checkpoint's unsealed log tail is sealed into a segment of its
#: own once it holds this many entries, or once the head blob (fields,
#: sessions, tail) passes this many bytes: every suspension rewrites the
#: head, so the tail it carries stays bounded
SEAL_ENTRIES = 16
SEAL_BYTES = 64 * 1024


class _BlobCheckpointStorage(CheckpointStorage):
    """Durable checkpoints as blobs under string keys: one HEAD per flow
    (``<run_id>``: fields, session table, the log's unsealed tail) and
    sealed log SEGMENTS (``<run_id>.<n>``) that are written once and never
    again. A suspension rewrites the head alone; segments are written
    before the head that counts them, so a crash between the two leaves
    the older head and an orphan segment that loading ignores. Subclasses
    store the blobs: ``_put``, ``_delete``, ``_load``."""

    def __init__(self):
        super().__init__()
        #: run_id -> (sealed segments, log entries they hold)
        self._sealed: dict[str, tuple[int, int]] = {}
        blobs = self._load()
        for key in [k for k in blobs if "." not in k]:
            head = deserialize(blobs[key])
            run_id, flow_class, fields, tail, sessions = head[:5]
            n_seg = head[5] if len(head) > 5 else 0
            log = []
            for i in range(n_seg):
                log.extend(deserialize(blobs.pop(f"{key}.{i}")))
            self._sealed[run_id] = (n_seg, len(log))
            self._checkpoints[run_id] = Checkpoint(
                run_id, flow_class, fields, log + list(tail),
                [SessionSnapshot(*s) for s in sessions])
            del blobs[key]
        for orphan in blobs:      # a segment no head counts
            self._delete(orphan)

    def add_checkpoint(self, cp: Checkpoint) -> None:
        super().add_checkpoint(cp)
        held = self._checkpoints[cp.id]
        n_seg, n_sealed = self._sealed.get(cp.id, (0, 0))
        if cp.log_from < n_sealed:      # no delta over what is sealed:
            self._drop(cp.id)           # start the flow's blobs over
            n_seg = n_sealed = 0
        tail = held.response_log[n_sealed:]
        blob = self._head_blob(held, tail, n_seg)
        if tail and (len(tail) >= SEAL_ENTRIES or len(blob) > SEAL_BYTES):
            self._put(f"{cp.id}.{n_seg}", serialize(tail))
            n_seg, n_sealed = n_seg + 1, n_sealed + len(tail)
            blob = self._head_blob(held, [], n_seg)
        self._put(cp.id, blob)
        self._sealed[cp.id] = (n_seg, n_sealed)

    @staticmethod
    def _head_blob(cp: Checkpoint, tail: list, n_seg: int) -> bytes:
        return serialize([
            cp.run_id, cp.flow_class, cp.flow_fields, tail,
            [[s.peer_name, s.our_session_id, s.peer_session_id, s.state,
              s.received, s.pending_out, s.group] for s in cp.sessions],
            n_seg])

    def remove_checkpoint(self, cp_or_id) -> None:
        cp_id = cp_or_id if isinstance(cp_or_id, str) else cp_or_id.id
        super().remove_checkpoint(cp_id)
        self._drop(cp_id)

    def _drop(self, cp_id: str) -> None:
        n_seg, _n = self._sealed.pop(cp_id, (0, 0))
        self._delete(cp_id)   # the head first: segments without it are orphans
        for i in range(n_seg):
            self._delete(f"{cp_id}.{i}")


class FileCheckpointStorage(_BlobCheckpointStorage):
    """Durable variant: canonical-codec blobs, one file per blob, atomic
    replace (the node_checkpoints table analog)."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        super().__init__()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, f"{key}.ckpt")

    def _load(self) -> dict:
        blobs = {}
        for name in os.listdir(self.directory):
            if name.endswith(".ckpt"):
                with open(os.path.join(self.directory, name), "rb") as f:
                    blobs[name[:-len(".ckpt")]] = f.read()
        return blobs

    def _put(self, key: str, blob: bytes) -> None:
        tmp = self._path(key) + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._path(key))

    def _delete(self, key: str) -> None:
        try:
            os.remove(self._path(key))
        except FileNotFoundError:
            pass


class KvCheckpointStorage(_BlobCheckpointStorage):
    """Checkpoints on the native kvlog engine (corda_tpu.storage): synced
    crc-framed appends with torn-tail recovery — the DBCheckpointStorage
    durability class without an embedded SQL database."""

    def __init__(self, path: str, use_native: bool | None = None):
        from ..storage import KvStore
        self._kv = KvStore(path, use_native=use_native)
        super().__init__()

    def _load(self) -> dict:
        return {key.decode(): blob for key, blob in self._kv.items()}

    def _put(self, key: str, blob: bytes) -> None:
        self._kv[key.encode()] = blob

    def _delete(self, key: str) -> None:
        if key.encode() in self._kv:
            del self._kv[key.encode()]

    def close(self) -> None:
        self._kv.close()
