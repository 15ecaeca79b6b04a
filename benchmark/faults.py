"""Faults planted beneath the tap, for the control and the fault tests.

Each fault breaks the timed path in the running process only; the
benchmark's own runs never plant one (``run.py --fault`` is for
``control.py`` and ``tests/``, and plants it after the warm-up, under
the window). The control is ``torn``: each landed block keeps the second
half of the block landed before it on that worker, as when a host buffer
is rewritten before its transfer drained, the step that would tempt a
later PR to drop a wait.

Each patches ``TpuWorkerContext._transfer_h2d``, through which every
unbatched block of a context lands, on the group commit's path too; the
patch changes only the view the call is given and passes the rest of
the call on as it came.
"""

from __future__ import annotations

import numpy as np


def _h2d_fault(transform):
    """Patch TpuWorkerContext._transfer_h2d; transform(ctx, np_view)
    returns the view to land, or None to land nothing."""
    from elbencho_tpu.tpu.device import TpuWorkerContext
    orig = TpuWorkerContext._transfer_h2d

    def _transfer_h2d(ctx, np_view, *args, **kwargs):
        view = transform(ctx, np_view)
        if view is not None:
            orig(ctx, view, *args, **kwargs)
    TpuWorkerContext._transfer_h2d = _transfer_h2d


def _torn(ctx, view):
    half = len(view) // 2
    prev = getattr(ctx, "_fault_prev_tail", None)
    out = np.array(view)
    ctx._fault_prev_tail = out[half:].copy()
    if prev is not None and len(prev) == len(view) - half:
        out[half:] = prev
    return out


def _stale(ctx, view):
    # the step returns its state unchanged: the block already in HBM stays
    return view if ctx._last_ingested is None else None


def _half(ctx, view):
    ctx._fault_calls = getattr(ctx, "_fault_calls", 0) + 1
    return view if ctx._fault_calls % 2 else None


def _altered(ctx, view):
    out = np.array(view)
    out[len(out) // 2] ^= np.uint32(1)
    return out


FAULTS = {"torn": _torn, "stale": _stale, "half": _half, "altered": _altered}


def plant(name: str) -> None:
    if name not in FAULTS:
        raise ValueError(f"no fault {name!r}: {', '.join(FAULTS)}")
    _h2d_fault(FAULTS[name])
