"""What the timed path produced, read where the program produces it.

The tap wraps two of the program's calls while a window is armed, and
changes nothing they do:

- ``TpuWorkerContext.host_to_device``: the call that lands one block in
  HBM. After each call the block the context holds as landed
  (``_last_ingested``) must be the one the call was given; the tap keeps a
  seeded reservoir sample of (offset, length, block) as device-side copies
  (the program recycles its HBM slots by donation, so a bare reference
  could be deleted under it). A call that landed nothing leaves the block
  before it there, which the check then reads as wrong words.
- ``ops.verify.fingerprint_block_pallas``: the ``elbencho_fingerprint``
  kernel's (sum, xor) for each sampled block.

The counts and the reservoir's slots are taken under one lock; the copy
runs outside it, so threads wait on each other only for a few counters.
The samples hold at most ``SAMPLE_BYTES`` of device memory, inside the
run's ``memory_peak_bytes``; the copies run as the program
``jit_bench_tap_copy``, which the trace reduction keeps apart from the
program's own work. Sampling decisions come from ``random.Random(seed)``,
so a seed always draws the same positions in the stream of calls.
"""

from __future__ import annotations

import random
import threading

#: device bytes the reservoir may hold, and the most samples it keeps
SAMPLE_BYTES = 256 << 20
SAMPLE_MAX = 512


def sample_slots(unit_bytes: int) -> int:
    return max(4, min(SAMPLE_MAX, SAMPLE_BYTES // max(unit_bytes, 1)))


def bench_tap_copy(x):
    """The sample's device-side copy; its program is ``jit_bench_tap_copy``
    in a trace, so the trace reduction tells the tap's work from the
    program's."""
    import jax.numpy as jnp
    return jnp.copy(x)


class _Reservoir:
    """Algorithm R over the stream of calls; each draw is a ticket, and a
    slot keeps the sample of its latest ticket only."""

    def __init__(self, rng: random.Random, slots: int):
        self.rng = rng
        self.slots = slots
        self.seen = 0
        self.items: list = []
        self.ticket: list = []

    def draw(self) -> "int | None":
        """The slot for the next call, or None; under the tap's lock."""
        self.seen += 1
        if len(self.items) < self.slots:
            self.items.append(None)
            self.ticket.append(self.seen)
            return len(self.items) - 1
        j = self.rng.randrange(self.seen)
        if j >= self.slots:
            return None
        self.ticket[j] = self.seen
        return j


class Tap:
    def __init__(self, seed: int, block_bytes: int):
        import jax
        self._copy = jax.jit(bench_tap_copy)
        self.lock = threading.Lock()
        self.local = threading.local()
        self._slots = sample_slots(block_bytes)
        self.armed = False
        self._undo: list = []
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """Zero the count and draw the samples afresh from ``seed``."""
        self.blocks = _Reservoir(random.Random(seed), self._slots)
        self.calls = 0            # host_to_device calls while armed

    def drop_samples(self) -> None:
        """Free the device memory the samples hold."""
        self.blocks.items = []

    # -- the wrapped calls ----------------------------------------------

    def _host_to_device(self, orig):
        tap = self

        def host_to_device(ctx, buf, length, verify_salt=0, file_offset=0):
            tap.local.fingerprint = None
            orig(ctx, buf, length, verify_salt=verify_salt,
                 file_offset=file_offset)
            if not tap.armed:
                return
            with tap.lock:
                tap.calls += 1
                slot = tap.blocks.draw()
                if slot is None:
                    return
                ticket = tap.blocks.ticket[slot]
            arr = ctx._last_ingested
            sample = (file_offset, length,
                      None if arr is None else tap._copy(arr),
                      tap.local.fingerprint)
            with tap.lock:
                if slot < len(tap.blocks.items) \
                        and tap.blocks.ticket[slot] == ticket:
                    tap.blocks.items[slot] = sample
        return host_to_device

    def _fingerprint(self, orig):
        tap = self

        def fingerprint_block_pallas(block_u32):
            result = orig(block_u32)
            tap.local.fingerprint = result
            return result
        return fingerprint_block_pallas

    # -- install / remove -----------------------------------------------

    def _patch(self, owner, name, wrap) -> None:
        orig = getattr(owner, name)
        setattr(owner, name, wrap(orig))
        self._undo.append((owner, name, orig))

    def install(self) -> None:
        from elbencho_tpu.ops import verify
        from elbencho_tpu.tpu.device import TpuWorkerContext
        self._patch(TpuWorkerContext, "host_to_device", self._host_to_device)
        self._patch(verify, "fingerprint_block_pallas", self._fingerprint)

    def remove(self) -> None:
        while self._undo:
            owner, name, orig = self._undo.pop()
            setattr(owner, name, orig)

    def block_samples(self) -> list:
        return [s for s in self.blocks.items if s is not None]
