"""On-device integrity verification (--tpuverify).

The host-side verify (LocalWorker::postReadIntegrityCheckVerifyBuf,
LocalWorker.cpp:2170) compares every 64-bit word against ``offset + salt``.
On TPU we verify blocks already resident in HBM without a device->host
round-trip: a Pallas kernel reduces the block to (sum, xor) fingerprints,
compared against expected values the host computes in closed form, with
integer arithmetic whose cost does not grow with the block.
Fingerprint math is mod 2^32 (TPU-native word size).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128     # TPU vector lane count
_SUBLANES = 8    # int32 sublanes per vreg: the (8, 128) accumulator tile
#: rows of (rows, 128) int32 streamed through VMEM per grid step (512 KiB);
#: VMEM use stays at two such tiles plus the accumulators for any block size
_TILE_ROWS = 1024


def _xor_upto(m: int) -> int:
    """xor of 0..m (0 for m < 0), by the period-4 identity."""
    return (m, 1, m + 1, 0)[m & 3] if m >= 0 else 0


def expected_fingerprint_host(file_offset: int, length: int,
                              salt: int) -> "tuple[int, int]":
    """(sum mod 2^32, xor) of the uint32-word view of the verify pattern
    for [file_offset, file_offset+length), in closed form.

    Word i is ``v = (file_offset + salt + 8*i) mod 2^64``, seen as its low
    and high uint32 halves. Between two wraps of the low half the high
    word is constant and the low words are ``8*(q+j) + r``: the sum is an
    arithmetic series plus ``k*hi``, the xor that of the run ``q..q+k-1``
    shifted left 3, with ``r`` and ``hi`` when ``k`` is odd. One segment
    per 2^32 wrap of the low half, so O(1 + length / 2^32). A wrap at
    2^64 is a wrap of the high word mod 2^32. Tail bytes past the last
    whole word are not part of the fingerprint."""
    n = length // 8
    v = (file_offset + salt) & 0xFFFFFFFFFFFFFFFF
    s = x = 0
    while n:
        lo, hi = v & 0xFFFFFFFF, (v >> 32) & 0xFFFFFFFF
        q, r = lo >> 3, lo & 7
        k = min(n, (1 << 29) - q)  # words before the low half wraps
        s += k * (lo + hi) + 4 * k * (k - 1)
        x ^= (_xor_upto(q + k - 1) ^ _xor_upto(q - 1)) << 3
        if k & 1:
            x ^= r ^ hi
        v += 8 * k
        n -= k
    return s & 0xFFFFFFFF, x


def _fingerprint_kernel(x_ref, sum_ref, xor_ref):
    """Fold one (tile_rows, 128) int32 tile into the (8, 128) sum and xor
    accumulators. The output blocks map to the same tile on every grid
    step, so they stay resident in VMEM across the reduction axis. int32
    wraps like uint32, so sum and xor keep the uint32 bits."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        xor_ref[...] = jnp.zeros_like(xor_ref)

    x = x_ref[...].reshape(-1, _SUBLANES, _LANES)
    sum_ref[...] += jnp.sum(x, axis=0)
    # Mosaic has no xor reduction: fold the (power-of-two) leading axis
    # in halves, a whole-vreg xor per step
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] ^ x[half:]
    xor_ref[...] ^= x[0]


@functools.lru_cache(maxsize=None)
def fingerprint_fn(num_words: int, interpret: bool):
    """Jitted uint32[num_words] -> int32[2] (sum, xor) through the Pallas
    kernel: one array, so reading a block's result back is one host
    read. The block is zero-padded to whole tiles where needed: zeros
    change neither the sum nor the xor. ``interpret`` runs the kernel in
    the Pallas interpreter (CPU backends only)."""
    rows = -(-max(num_words, 1) // _LANES)
    # a power of two of vregs per tile, for the kernel's halving xor fold
    tile_rows = min(_TILE_ROWS,
                    _SUBLANES << max(-(-rows // _SUBLANES) - 1, 0).bit_length())
    rows = -(-rows // tile_rows) * tile_rows
    acc = jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.int32)
    acc_spec = pl.BlockSpec((_SUBLANES, _LANES), lambda i: (0, 0))
    call = pl.pallas_call(
        _fingerprint_kernel,
        out_shape=(acc, acc),
        grid=(rows // tile_rows,),
        in_specs=[pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0))],
        out_specs=(acc_spec, acc_spec),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="elbencho_fingerprint",
    )

    @jax.jit
    def run(block_u32):
        x = jax.lax.bitcast_convert_type(block_u32.reshape(-1), jnp.int32)
        pad = rows * _LANES - x.shape[0]
        if pad:
            x = jnp.pad(x, (0, pad))
        s, xr = call(x.reshape(rows, _LANES))
        return jnp.stack([jnp.sum(s),
                          jax.lax.reduce(xr, jnp.int32(0),
                                         jax.lax.bitwise_xor, (0, 1))])

    return run


class PendingFingerprint:
    """A block's fingerprint whose kernel was dispatched and not read back
    yet. ``result()`` reads it (one device->host read) and keeps the
    (sum mod 2^32, xor) pair; ``==`` / ``!=`` against a pair resolve it."""

    __slots__ = ("array", "_value")

    def __init__(self, array):
        self.array = array  # int32[2] on the block's device
        self._value = None

    def result(self) -> "tuple[int, int]":
        if self._value is None:
            s, x = np.asarray(self.array).tolist()
            self._value = (s & 0xFFFFFFFF, x & 0xFFFFFFFF)
            self.array = None
        return self._value

    def __eq__(self, other):
        return self.result() == other

    def __ne__(self, other):
        return self.result() != other

    __hash__ = None


def fingerprint_block_pallas(block_u32) -> PendingFingerprint:
    """The fingerprint of an HBM-resident uint32 block via the Pallas
    kernel, dispatched without a host sync: the caller reads it with
    ``result()`` when it needs the pair. Interpret mode is chosen from
    the block's platform (CPU only); on a TPU a lowering error
    propagates here, a runtime error from ``result()``."""
    platform = next(iter(block_u32.devices())).platform
    return PendingFingerprint(
        fingerprint_fn(int(block_u32.size), platform == "cpu")(block_u32))


def fingerprint_host(words_u32: np.ndarray) -> "tuple[int, int]":
    """(sum mod 2^32, xor) of a host uint32 array: the kernel's result for
    the same words, for a block whose device is gone (chip failover)."""
    return (int(words_u32.sum(dtype=np.uint64)) & 0xFFFFFFFF,
            int(np.bitwise_xor.reduce(words_u32)))


def check_fingerprint(got: "tuple[int, int]", want: "tuple[int, int]",
                      file_offset: int) -> None:
    """Raise ValueError when a block's on-device (sum, xor) fingerprint is
    not the one the verify pattern gives for its file offset."""
    if got != want:
        raise ValueError(
            f"on-device integrity check failed for block at offset "
            f"{file_offset}: fingerprint (sum={got[0]:#x}, xor={got[1]:#x})"
            f" != expected (sum={want[0]:#x}, xor={want[1]:#x})")


def verify_block_on_device(block_u32, file_offset: int, length: int,
                           salt: int) -> "tuple[int, int]":
    """The block's (sum, xor) fingerprint; raises ValueError if the
    HBM-resident block does not match the verify pattern for its file
    offset."""
    got = fingerprint_block_pallas(block_u32).result()
    check_fingerprint(got, expected_fingerprint_host(file_offset, length,
                                                     salt), file_offset)
    return got
