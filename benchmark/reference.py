"""The plain reference: what every byte of the dataset is, from the seed.

It imports nothing of the program. The dataset is elbencho's verify
pattern: the 64-bit little-endian word at byte offset ``o`` holds
``(o + salt) mod 2**64``. The salt comes from ``--seed``, so two seeds
give different bytes and one seed always the same bytes.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

#: bytes generated per chunk when the dataset is written
CHUNK = 64 << 20


def salt_for_seed(seed: int) -> int:
    """A non-zero 62-bit salt from any whole-number seed (the program's
    ``--verify`` takes it as a positive int)."""
    digest = hashlib.blake2b(str(int(seed)).encode(), digest_size=8).digest()
    return 1 + int.from_bytes(digest, "little") % ((1 << 62) - 1)


def pattern_u64(offset: int, n_bytes: int, salt: int) -> np.ndarray:
    """The dataset's words for ``[offset, offset + n_bytes)``; offset and
    length are multiples of 8."""
    if offset % 8 or n_bytes % 8:
        raise ValueError(f"unaligned pattern range {offset}+{n_bytes}")
    words = np.arange(n_bytes // 8, dtype=np.uint64) * np.uint64(8)
    with np.errstate(over="ignore"):
        words += np.uint64((offset + salt) % (1 << 64))
    return words


def write_dataset(fd: int, size: int, salt: int) -> None:
    """Write ``size`` bytes of the pattern to ``fd`` from offset 0."""
    n = min(CHUNK, size)
    base = pattern_u64(0, n, salt)
    buf = np.empty_like(base)
    for off in range(0, size, CHUNK):
        length = min(CHUNK, size - off)
        with np.errstate(over="ignore"):
            np.add(base, np.uint64(off), out=buf)
        view = memoryview(buf.view(np.uint8)[:length])
        while view:
            view = view[os.write(fd, view):]


def mismatched_words(got: np.ndarray, offset: int, salt: int) -> int:
    """64-bit words of ``got`` (any dtype, as landed) that differ from the
    dataset at ``offset``. A block of the wrong size counts whole."""
    got = np.ascontiguousarray(got).view(np.uint8).reshape(-1)
    want = pattern_u64(offset, got.size - got.size % 8, salt)
    if got.size % 8:
        return want.size + 1
    return int(np.count_nonzero(got.view(np.uint64) != want))


def fingerprint(offset: int, n_bytes: int, salt: int) -> "tuple[int, int]":
    """(sum mod 2**32, xor) of the uint32 words of the dataset range: what
    the program's ``elbencho_fingerprint`` kernel must return for it."""
    words = pattern_u64(offset, n_bytes, salt).view(np.uint32)
    total = int(words.sum(dtype=np.uint64)) & 0xFFFFFFFF
    return total, int(np.bitwise_xor.reduce(words)) if words.size else 0

