"""What an ECDSA kernel call has to move, from its shapes alone (the sibling
of ``kernel_cost.py``, which a PR that adds a deployment may not edit). Each
function returns ``{"bytes": ..., "ops": ... | None, "ops_peak": <key of
peaks.json> | None}`` for one call."""

#: limbs of one field element on the wire (16 x u16 = 256 bits)
NLIMB = 16
#: outer steps of the hybrid ladder: 128-bit GLV halves, 8 bits a step
K1_STEPS = 16


def secp256k1_hybrid(rows: int) -> dict:
    """One ``verify_core_hybrid_wide`` call over ``rows`` signatures (g_w = 8).

    Bytes per row that the call must read or write at least once: the four
    wire arrays, ``g_idx`` 16 x i32, ``q_bits`` 16 x 4 x u8, ``pts`` 4 x 16
    x u16, ``r_limbs`` 16 x u16; the verdict, 1 byte; and one row of the
    constant-G table gathered per outer step at the table's own dtypes: x
    and y 16 x u16 each and the u8 validity flag. The table itself (2**18
    entries, 17 MB) is resident and only the gathered rows count.

    No operation count is given, for ``ed25519_split``'s reason: the
    arithmetic is emulated 64-bit limb multiplication on the vector unit and
    the published peaks hold no integer vector figure. The roofline share
    built on this is the memory bound only."""
    per_row = (K1_STEPS * 4) + (K1_STEPS * 4) + (4 * NLIMB * 2) \
        + (NLIMB * 2) + 1 + K1_STEPS * (2 * NLIMB * 2 + 1)
    return {"bytes": rows * per_row, "ops": None, "ops_peak": None}
