"""What a kernel call has to move and compute, from its shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Each function returns ``{"bytes": ..., "ops": ... | None,
"ops_peak": <key of peaks.json> | None}`` for one call."""

#: limbs of one field element on the wire (16 x u16 = 256 bits)
NLIMB = 16


def ed25519_split(rows: int) -> dict:
    """One ``verify_core_split`` call over ``rows`` signatures (w = 16).

    Bytes per row that the call must read or write at least once:
    bb_idx 16 x i32, a_packed 8 x 8 x u8, the two (-A) rows 6 x 16 x u16, the
    wire R 16 x u16, the verdict 1 byte; and the gathers from the two
    constant-base Niels tables: 8 windows x 2 tables x 3 coordinates x 16
    limbs x u16. The tables themselves (2 x 3 x 2**16 x 16 x u16 = 12.6 MB)
    are resident and only the gathered rows count.

    No operation count is given: the arithmetic is emulated 64-bit limb
    multiplication on the vector unit, and the published peaks of a TPU v5e
    hold no integer vector figure to set it against (PERF.md, Open
    questions). The roofline share built on this is the memory bound only."""
    per_row = (16 * 4) + (8 * 8) + (6 * NLIMB * 2) + (NLIMB * 2) + 1 \
        + 8 * 2 * 3 * NLIMB * 2
    return {"bytes": rows * per_row, "ops": None, "ops_peak": None}
