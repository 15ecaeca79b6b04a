"""Bytes each device kernel of the program must move, from its shapes.

Kept with the benchmark so that no PR that claims a gain can change how
its kernel's roofline share is counted.
"""

from __future__ import annotations

#: the fingerprint kernel's layout: int32 rows of 128 lanes, tiles of at
#: most 1024 rows, two (8, 128) int32 accumulators written once
_LANES = 128
_TILE_ROWS = 1024
_ACC_BYTES = 2 * 8 * 128 * 4


def fingerprint_bytes(block_bytes: int) -> int:
    """HBM bytes one ``elbencho_fingerprint`` call must touch for a block:
    the block read once (rows padded with zeros to whole tiles, which the
    kernel also reads) and its two accumulators written once."""
    rows = -(-max(block_bytes // 4, 1) // _LANES)
    tile = min(_TILE_ROWS, 8 << max(-(-rows // 8) - 1, 0).bit_length())
    rows = -(-rows // tile) * tile
    return rows * _LANES * 4 + _ACC_BYTES
