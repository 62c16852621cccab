"""``prepare_batch_split``'s five phases as spans under the batcher's
``batcher.dispatch``, and its output held byte for byte to what the one-loop
version (PR 38's) gave: the prep is ONE native call over the rows' joined
bytes now (``prepare_words_split``; PR 40), pure Python where the library
is absent, with tracing on and off (one code path). No kernel is compiled
here: the prep is host code."""
import hashlib

import numpy as np
import pytest

from corda_tpu.core.crypto import ecmath
from corda_tpu.observability.tracing import Tracer, set_tracer, disable_tracing
from corda_tpu.ops import ed25519 as ed
from corda_tpu.ops import scalarprep as sp

PHASES = ["sig", "keys", "digest", "scalars", "handover"]
#: sha256 over dtype, shape and bytes of the five arrays that PR 38's
#: one-loop ``prepare_batch_split(items, device_tables=False)`` returned for
#: ``seeded_batch()`` (computed on that commit, native and Python scalars)
ONE_LOOP_DIGEST = \
    "46255f935118376206d428b6cbc3675260fdfe86b70fbbd9d9cf306ad876fb30"
REFUSED = {3, 5, 7, 11, 13}


def seeded_batch(n=24, seed=39):
    """24 rows of 5 signers; a key that is no point (3), a short signature
    (5), R's y >= p (7), s >= L (11), and y >= p under a key that is no
    point (13: such a row does not hash)."""
    rng = np.random.default_rng(seed)
    seeds = [rng.bytes(32) for _ in range(5)]
    pubs = [ecmath.ed25519_public_key(s) for s in seeds]
    items = []
    for i in range(n):
        k = i % 5
        msg = rng.bytes(32)
        items.append((pubs[k], ecmath.ed25519_sign(seeds[k], msg, pubs[k]),
                      msg))
    bad_key = next(bytes([b]) + bytes(31) for b in range(2, 255)
                   if ecmath.ed_point_decompress(bytes([b]) + bytes(31))
                   is None)
    items[3] = (bad_key,) + items[3][1:]
    items[5] = (items[5][0], items[5][1][:63], items[5][2])
    items[7] = (items[7][0],
                b"\xee" + b"\xff" * 30 + b"\x7f" + items[7][1][32:],
                items[7][2])
    items[11] = (items[11][0], items[11][1][:32] + b"\xff" * 32,
                 items[11][2])
    items[13] = (bad_key, items[7][1], items[13][2])
    return items


def digest(out) -> str:
    h = hashlib.sha256()
    for a in out:
        a = np.asarray(a)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.fixture
def tracer():
    t = Tracer()
    set_tracer(t)
    try:
        yield t
    finally:
        disable_tracing()


@pytest.fixture(scope="module")
def items():
    return seeded_batch()


def test_the_five_phases_in_order_disjoint_inside_the_parent(tracer, items):
    with tracer.span("batcher.dispatch", cpu=True) as parent:
        ed.prepare_batch_split(items, device_tables=False,
                               trace_parent=parent)
    spans = tracer.spans()
    assert [s["name"] for s in spans] \
        == [f"ed25519.prep.{p}" for p in PHASES] + ["batcher.dispatch"]
    *phases, top = spans
    end = top["start_s"] + top["duration_s"]
    at = top["start_s"]
    for s in phases:
        assert s["parent_id"] == top["span_id"]
        assert s["trace_id"] == top["trace_id"]
        assert s["tags"] == {"bucket": "ed25519", "rows": len(items)}
        assert s["cpu_s"] is not None and s["cpu_s"] >= 0.0
        # each begins where the one before it had ended, at the earliest
        assert s["start_s"] >= at - 1e-4
        at = s["start_s"] + s["duration_s"]
        assert at <= end + 1e-4
    assert sum(s["duration_s"] for s in phases) <= top["duration_s"] + 1e-4


@pytest.mark.parametrize(
    "mode", ["off", "on", "on_python_scalars", "off_no_library"])
def test_the_arrays_are_the_one_loop_versions_byte_for_byte(
        mode, items, monkeypatch):
    if mode == "on_python_scalars":
        monkeypatch.setattr(sp, "available", lambda: False)
    if mode == "off_no_library":
        monkeypatch.setattr(sp, "_LIB", None)
        assert not sp.available()
    if mode.startswith("off"):
        out = ed.prepare_batch_split(items, device_tables=False)
    else:
        t = Tracer()
        set_tracer(t)
        try:
            with t.span("batcher.dispatch") as parent:
                out = ed.prepare_batch_split(items, device_tables=False,
                                             trace_parent=parent)
        finally:
            disable_tracing()
        assert len(t.spans()) == 6
    assert digest(out) == ONE_LOOP_DIGEST
    precheck = np.asarray(out[-1])
    assert set(np.flatnonzero(~precheck).tolist()) == REFUSED


def test_without_a_parent_no_span_is_opened(tracer, items):
    """The mesh route and the tools call the prep with no parent: tracing
    on, and still not one orphan span a batch."""
    out = ed.prepare_batch_split(items, device_tables=False)
    assert tracer.spans() == []
    assert digest(out) == ONE_LOOP_DIGEST


@pytest.mark.parametrize("library", [True, False])
@pytest.mark.parametrize("capacity", [24, 32, 64])
def test_the_word_form_pads_as_the_list_of_items_did(capacity, library,
                                                     items, monkeypatch):
    """The rows as three lists and a capacity (what the batcher hands over)
    against the triples with the last one repeated (what it handed over
    before, and what the mesh route still does)."""
    if not library:
        monkeypatch.setattr(sp, "_LIB", None)
    padded = items + [items[-1]] * (capacity - len(items))
    want = ed.prepare_batch_split(padded, device_tables=False)
    keys, sigs, msgs = (list(col) for col in zip(*items))
    got = ed.prepare_words_split(keys, sigs, msgs, capacity,
                                 device_tables=False)
    assert digest(got) == digest(want)
    if capacity == len(items):
        assert digest(got) == ONE_LOOP_DIGEST
