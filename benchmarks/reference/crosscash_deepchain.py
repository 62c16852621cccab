"""Plain reference for the ``crosscash-deepchain`` deployment.

Imports nothing of the program. It is handed the RAW history, one record a
transaction (``raw`` below builds it from plain values), and computes with
hashlib, ``cryptography`` and dicts: every transaction's id from its
component leaves; the validity of EVERY signature of every transaction a
joiner recorded; the ancestry of a payment by a plain walk over input refs;
whether the order a joiner recorded in is topological; cash conservation
along the chain from the raw amounts; the consumed set (``crosscash_raft``'s).
The contract rules beyond conservation keep no independent copy, as in
``crosscash-raft`` (PERF.md section 4).
"""
from __future__ import annotations

from concurrent.futures import Future

from reference.crosscash_raft import (consumed_set,  # noqa: F401 (re-export)
                                      ed25519_valid, merkle_root)


def raw(tx_id: bytes, leaves, inputs, sigs, amounts) -> dict:
    """One transaction as the reference sees it: its id, the hashes of its
    components (the Merkle leaves), its input refs as (transaction id,
    output index), its (public key, signature) pairs, and the quantity of
    each output."""
    return {"id": bytes(tx_id), "leaves": [bytes(h) for h in leaves],
            "inputs": [(bytes(t), int(i)) for t, i in inputs],
            "sigs": [(bytes(p), bytes(s)) for p, s in sigs],
            "amounts": [int(a) for a in amounts]}


def ancestry(txs: dict, tip: bytes) -> set:
    """``tip`` and every transaction it descends from, as far as ``txs``
    holds them: a plain walk over input refs."""
    seen, todo = set(), [tip]
    while todo:
        tx_id = todo.pop()
        if tx_id in seen or tx_id not in txs:
            continue
        seen.add(tx_id)
        todo.extend(parent for parent, _i in txs[tx_id]["inputs"])
    return seen


def descendants(txs: dict, root: bytes) -> set:
    """``root`` and everything in ``txs`` that spends from it, however far
    down."""
    children: dict = {}
    for tx in txs.values():
        for parent, _i in tx["inputs"]:
            children.setdefault(parent, []).append(tx["id"])
    seen, todo = set(), [root]
    while todo:
        tx_id = todo.pop()
        if tx_id not in seen:
            seen.add(tx_id)
            todo.extend(children.get(tx_id, ()))
    return seen


def order_violations(txs: dict, recorded: list) -> int:
    """How many recorded transactions came before one they spend from (a
    parent the recorder never recorded counts too: it verified a spend of
    something it did not hold)."""
    position = {tx_id: k for k, tx_id in enumerate(recorded)}
    return sum(1 for tx_id in recorded
               for parent, _i in txs[tx_id]["inputs"]
               if position.get(parent, len(recorded)) > position[tx_id])


def bad_ids(txs: dict, ids) -> int:
    return sum(merkle_root(txs[t]["leaves"]) != t for t in ids)


def bad_signatures(txs: dict, ids) -> int:
    """Every signature of every named transaction, over the transaction's
    id (every key of this deployment is Ed25519)."""
    return sum(not ed25519_valid(pub, sig, t)
               for t in ids for pub, sig in txs[t]["sigs"])


def unbalanced(txs: dict, ids) -> int:
    """Transactions with inputs whose outputs do not add up to what their
    inputs held, or that spend an output ``txs`` does not hold (an issue
    has no inputs and creates what it holds)."""
    bad = 0
    for t in ids:
        tx = txs[t]
        if tx["inputs"]:
            try:
                held = sum(txs[parent]["amounts"][i]
                           for parent, i in tx["inputs"])
            except (KeyError, IndexError):
                held = None
            bad += held != sum(tx["amounts"])
    return bad


def judge_join(txs: dict, tip: bytes, recorded: list) -> dict:
    """One acknowledged join: the joiner's store in record order against the
    payment ``tip``. Every count has to be 0. (A recorded transaction the
    history does not know is ``extra`` and is judged no further.)"""
    want, got = ancestry(txs, tip), set(recorded)
    known = [t for t in recorded if t in txs]
    return {"missing": len(want - got), "extra": len(got - want),
            "recorded_twice": len(recorded) - len(got),
            "order_violations": order_violations(txs, known),
            "bad_ids": bad_ids(txs, set(known)),
            "bad_signatures": bad_signatures(txs, set(known)),
            "unbalanced": unbalanced(txs, set(known))}


def judge_refusal(txs: dict, bad_tx: bytes, recorded: list) -> int:
    """A refused join: how many transactions at or below ``bad_tx`` (itself
    and whatever descends from it) the joiner holds all the same."""
    return len(descendants(txs, bad_tx) & set(recorded))


class UncheckedVerifier:
    """CONTROL, never the reference: a verifier service in the joiners'
    place that checks the contract rules and waves every signature through,
    so a back chain with a bad signature is accepted."""

    def verify_signed(self, stx, services,
                      check_sufficient_signatures=True) -> Future:
        done: Future = Future()
        try:
            stx.to_ledger_transaction(services).verify()
            done.set_result(None)
        except Exception as e:
            done.set_exception(e)
        return done
