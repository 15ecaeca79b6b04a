"""What the timed path produced, read where the program produces it.

The tap wraps the program's calls that a traffic file names under
``"tap"`` (``DEFAULT_TAP`` where it names none) while a window is armed,
and changes nothing they do, except as a ``"host"`` check asks (below).

A landing call (``"role": "land"``, the default) lands one host view in
device memory. Its entry names, by the names of the call's parameters:

- ``"call"``: ``"<module>:<attribute path>"``, a function or a method of
  a class, e.g. ``"elbencho_tpu.tpu.device:TpuWorkerContext.host_to_device"``;
- ``"view"``: the host buffer the call lands (any object with the buffer
  protocol: a memoryview, a numpy array);
- ``"length"`` (optional): the bytes of the view that it lands, from its
  start; without it, the whole view;
- ``"offset"`` (optional): the view's byte offset in the dataset, which
  a ``"dataset"`` check compares at; without it only a ``"host"`` check
  can read the call;
- ``"landed"``: where the landed array is read once the call has
  returned: ``"return"`` for the call's return value, else the name of
  an attribute of the call's first argument (``self`` of a method). It
  holds the block as landed, the whole of it and nothing else; a call
  that landed nothing must leave it as it was, which the check then
  reads as wrong words.

A fingerprint call (``"role": "fingerprint"``) returns the on-device
``(sum, xor)`` of the block its thread lands next; the tap keeps it with
that block's sample.

A landing call made inside another landing call of the same thread is
not tapped again: the outer call's landing is the one read.

Each call while armed draws a ticket from a seeded reservoir (Algorithm
R) before the call; a drawn call's landed array is kept as a device-side
copy (the program recycles its HBM slots by donation, so a bare
reference could be deleted under it). Under a ``"host"`` check the tap
first writes a value that is new for each call into ``MARKERS`` 64-bit
words of the view, spread over both halves, and a drawn call also keeps
a host copy of the bytes it is given, which the check compares its
landed array with word for word: the words make each block differ from
the one before it, so a stale, torn or half-landed block shows. A
``"dataset"`` check never writes into a buffer.

The counts and the reservoir's slots are taken under one lock; the
copies run outside it, so threads wait on each other only for a few
counters. The samples hold at most ``SAMPLE_BYTES`` of device memory,
inside the run's ``memory_peak_bytes``; the copies run as the program
``jit_bench_tap_copy``, which the trace reduction keeps apart from the
program's own work. Sampling decisions and marker values come from the
seed, so a seed always draws the same positions in the stream of calls.
"""

from __future__ import annotations

import importlib
import inspect
import random
import threading
import time

import numpy as np

#: device bytes the reservoir may hold, and the most samples it keeps
SAMPLE_BYTES = 256 << 20
SAMPLE_MAX = 512
#: 64-bit words a "host" check writes into each landed view
MARKERS = 16
#: host buffers whose views the tap keeps at once
VIEWS_KEPT = 256

#: today's calls: the block loops' landing call and the verify kernel
DEFAULT_TAP = [
    {"call": "elbencho_tpu.tpu.device:TpuWorkerContext.host_to_device",
     "view": "buf", "length": "length", "offset": "file_offset",
     "landed": "_last_ingested"},
    {"call": "elbencho_tpu.ops.verify:fingerprint_block_pallas",
     "role": "fingerprint"},
]


def sample_slots(unit_bytes: int) -> int:
    return max(4, min(SAMPLE_MAX, SAMPLE_BYTES // max(unit_bytes, 1)))


def bench_tap_copy(x):
    """The sample's device-side copy; its program is ``jit_bench_tap_copy``
    in a trace, so the trace reduction tells the tap's work from the
    program's."""
    import jax.numpy as jnp
    return jnp.copy(x)


def marker_words(n_words: int) -> np.ndarray:
    """Indices of the marked 64-bit words of an ``n_words`` view: the
    centres of ``MARKERS`` equal stretches, half of them in each half."""
    k = min(MARKERS, n_words)
    return (2 * np.arange(k) + 1) * n_words // (2 * k)


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


class _View:
    """One host buffer as a landing call lands it: its bytes, and its
    marked 64-bit words as a memoryview with their indices."""

    __slots__ = ("obj", "bytes", "words", "marked")

    def __init__(self, obj, length: "int | None"):
        self.obj = obj  # held, so that its id names it while cached
        self.bytes = np.frombuffer(obj, np.uint8)[:length]
        n = self.bytes.size // 8
        self.words = memoryview(self.bytes[:n * 8]).cast("Q")
        self.marked = marker_words(n).tolist()


class _Param:
    """Reads one named parameter of a call from its arguments."""

    def __init__(self, sig: inspect.Signature, name: "str | None"):
        self.name = name
        self.index = self.default = None
        if name is None:
            return
        if name not in sig.parameters:
            raise ValueError(f"the call has no parameter {name!r}: {sig}")
        self.index = list(sig.parameters).index(name)
        self.default = sig.parameters[name].default

    def get(self, args: tuple, kwargs: dict):
        if self.index is None:
            return None
        if self.index < len(args):
            return args[self.index]
        return kwargs.get(self.name, self.default)


def resolve(call: str) -> "tuple[object, str]":
    """(owner, attribute name) of ``"<module>:<attribute path>"``."""
    module, _, path = call.partition(":")
    owner = importlib.import_module(module)
    *outer, name = path.split(".")
    for part in outer:
        owner = getattr(owner, part)
    return owner, name


class Tap:
    def __init__(self, seed: int, block_bytes: int, check: str = "dataset",
                 specs: "list | None" = None):
        import jax
        if check not in ("dataset", "host"):
            raise ValueError(f"no check {check!r}: dataset or host")
        self.check = check
        self.specs = DEFAULT_TAP if specs is None else specs
        self._copy = jax.jit(bench_tap_copy)
        self.lock = threading.Lock()
        self.local = threading.local()
        self._slots = sample_slots(block_bytes)
        self.armed = False
        self._undo: list = []
        self._seq = 0             # calls marked, never reset: new values
        self._mark_base = random.Random(seed).getrandbits(64)
        self._views: dict = {}    # (id, length) -> _View, see _view
        self._spent: list = []    # each thread's ns of tap work
        self._spare: list = []    # host copies no sample holds, for reuse
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """Zero the counts and draw the samples afresh from ``seed``."""
        self.blocks = _Reservoir(random.Random(seed), self._slots)
        self.calls = 0            # landing calls while armed
        for spent in self._spent:
            spent[0] = 0
        self._views.clear()

    def drop_samples(self) -> None:
        """Free the memory the samples hold."""
        self.blocks.items = []
        self._spare = []
        self._views.clear()

    def _view(self, obj, length: "int | None") -> "_View":
        """The bytes of ``obj`` a landing call lands, cached per object:
        a call is as a rule given the same few staging buffers again and
        again, and after a 16 MiB transfer each numpy operation runs on
        cold caches (~10 us on a chip's host), so a call only writes its
        marks through the cached memoryview, as plain integers."""
        key = (id(obj), length)
        view = self._views.get(key)
        if view is None:
            if len(self._views) >= VIEWS_KEPT:
                self._views.clear()
            view = self._views[key] = _View(obj, length)
        return view

    def _host_copy(self, view: np.ndarray) -> np.ndarray:
        """A copy of view, into a spare buffer of its size where there is
        one: a fresh 16 MiB buffer costs its page faults on top."""
        with self.lock:
            for i, spare in enumerate(self._spare):
                if spare.size == view.size:
                    want = self._spare.pop(i)
                    break
            else:
                want = np.empty_like(view)
        np.copyto(want, view)
        return want

    @property
    def spent_s(self) -> float:
        """Seconds the tap's own work took, summed over threads, since
        the last reset: marks, host and device copies."""
        return sum(spent[0] for spent in self._spent) / 1e9

    def _thread_spent(self) -> list:
        spent = getattr(self.local, "spent", None)
        if spent is None:
            spent = self.local.spent = [0]
            with self.lock:
                self._spent.append(spent)
        return spent

    # -- the wrapped calls ----------------------------------------------

    def _landing(self, orig, spec: dict):
        tap = self
        sig = inspect.signature(orig)
        view_p = _Param(sig, spec["view"])
        length_p = _Param(sig, spec.get("length"))
        offset_p = _Param(sig, spec.get("offset"))
        landed = spec["landed"]
        if offset_p.name is None and self.check == "dataset":
            raise ValueError(f"{spec['call']} names no dataset offset: "
                             f"only a host check can read it")

        def landing(*args, **kwargs):
            local = tap.local
            if not tap.armed or getattr(local, "inside", False):
                return orig(*args, **kwargs)
            t0 = time.perf_counter_ns()
            local.fingerprint = None
            length = length_p.get(args, kwargs)
            with tap.lock:
                tap.calls += 1
                tap._seq += 1
                seq = tap._seq
                slot = tap.blocks.draw()
                ticket = None if slot is None else tap.blocks.ticket[slot]
            want = None
            if tap.check == "host":
                view = tap._view(view_p.get(args, kwargs), length)
                length = view.bytes.size
                value = tap._mark_base + seq * MARKERS
                words = view.words
                for i in view.marked:
                    words[i] = value & 0xFFFFFFFFFFFFFFFF
                    value += 1
                if slot is not None:
                    want = tap._host_copy(view.bytes)
            elif length is None:
                length = memoryview(view_p.get(args, kwargs)).nbytes
            spent = tap._thread_spent()
            spent[0] += time.perf_counter_ns() - t0
            local.inside = True
            try:
                result = orig(*args, **kwargs)
            finally:
                local.inside = False
            if slot is None:
                return result
            t0 = time.perf_counter_ns()
            arr = result if landed == "return" else getattr(args[0], landed)
            sample = (offset_p.get(args, kwargs), length,
                      None if arr is None else tap._copy(arr),
                      local.fingerprint, want)
            with tap.lock:
                if slot < len(tap.blocks.items) \
                        and tap.blocks.ticket[slot] == ticket:
                    sample, tap.blocks.items[slot] = \
                        tap.blocks.items[slot], sample
                if sample is not None and sample[4] is not None:
                    tap._spare.append(sample[4])
            spent[0] += time.perf_counter_ns() - t0
            return result
        return landing

    def _fingerprint(self, orig, spec: dict):
        tap = self

        def fingerprint(*args, **kwargs):
            result = orig(*args, **kwargs)
            tap.local.fingerprint = result
            return result
        return fingerprint

    # -- install / remove -----------------------------------------------

    def install(self) -> None:
        wraps = {"land": self._landing, "fingerprint": self._fingerprint}
        for spec in self.specs:
            role = spec.get("role", "land")
            if role not in wraps:
                raise ValueError(f"tap entry {spec}: no role {role!r}")
            owner, name = resolve(spec["call"])
            orig = inspect.getattr_static(owner, name)
            setattr(owner, name, wraps[role](getattr(owner, name), spec))
            self._undo.append((owner, name, orig))

    def remove(self) -> None:
        while self._undo:
            owner, name, orig = self._undo.pop()
            setattr(owner, name, orig)

    def block_samples(self) -> list:
        """(offset, length, landed copy, fingerprint, host bytes given)
        of each sampled call; offset or host bytes None where the call
        or the check has none."""
        return [s for s in self.blocks.items if s is not None]
