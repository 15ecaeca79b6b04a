import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import per_op  # noqa: E402


def read(run):
    """Microseconds the fused ring waited, per held-back slot that went
    back to the storage engine, in release drains: drains it made only to
    complete the transfer that reads a held slot, beyond the transfer
    ring's own (SlotReleaseWaitUSec / SlotReleaseWaitOps)."""
    return per_op(run, "SlotReleaseWaitUSec", "SlotReleaseWaitOps")
