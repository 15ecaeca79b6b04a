"""The comparison that decides ``correct``.

Every number compared is a count that a sound run holds at 0, so every
limit is 0 (an exact comparison). The numbers:

- ``bad_words``: 64-bit words of the sampled HBM blocks that differ from
  what the call landing them was given: under a ``"dataset"`` check the
  reference at their dataset offset, under a ``"host"`` check the host
  bytes the call was given (tap.py); a sampled call that left no
  block, or a block of the wrong size, counts whole.
- ``bad_fingerprints``: sampled blocks whose ``elbencho_fingerprint``
  result differs from the reference's (sum, xor), or that have none,
  where the traffic verifies on the device.
- ``missed_samples``: samples the reservoir should hold but does not; a
  window that landed nothing misses one.
- ``program_errors``: 1 where a phase of the program failed or left no
  result record.
"""

from __future__ import annotations

import numpy as np

import reference

LIMITS = {"bad_words": 0, "bad_fingerprints": 0, "missed_samples": 0,
          "program_errors": 0}


def mismatched_host_words(got: np.ndarray, want: np.ndarray) -> int:
    """64-bit words of ``got`` (any dtype, as landed) that differ from the
    host bytes ``want``; a block of another size counts whole."""
    got = np.ascontiguousarray(got).view(np.uint8).reshape(-1)
    if got.size != want.size or got.size % 8:
        return want.size // 8 + 1
    return int(np.count_nonzero(got.view(np.uint64) != want.view(np.uint64)))


def compare(tap, record: "dict | None", salt: int, verify: bool,
            program_rc: int) -> "tuple[dict, int]":
    """({name: {"value", "limit"}}, failed samples) for one window."""
    bad_words = bad_fp = bad_blocks = 0
    samples = tap.block_samples()
    for offset, length, arr, fp, want in samples:
        if arr is None:
            n = length // 8 + 1
        elif want is not None:
            n = mismatched_host_words(np.asarray(arr), want)
        else:
            n = reference.mismatched_words(np.asarray(arr), offset, salt)
        bad_words += n
        bad_blocks += n > 0
        if verify and fp != reference.fingerprint(offset, length, salt):
            bad_fp += 1
    found = {"bad_words": bad_words,
             "missed_samples": min(tap.blocks.slots, max(tap.calls, 1))
             - len(samples),
             "program_errors": int(program_rc != 0 or record is None)}
    if verify:
        found["bad_fingerprints"] = bad_fp
    return {k: {"value": v, "limit": LIMITS[k]}
            for k, v in found.items()}, bad_blocks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
