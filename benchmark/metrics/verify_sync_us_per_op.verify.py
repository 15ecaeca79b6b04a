import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import per_op  # noqa: E402


def read(run):
    """Microseconds per block from the call of the jitted fingerprint step
    until its host sync returned: the transfer and the kernel it waits on
    (TpuVerifySyncUSec / TpuVerifyOps)."""
    return per_op(run, "TpuVerifySyncUSec", "TpuVerifyOps")
