import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import per_op  # noqa: E402


def read(run):
    """Microseconds the host blocked per drain of the transfer ring
    (TpuReadyWaitUSec / TpuReadyWaitOps)."""
    return per_op(run, "TpuReadyWaitUSec", "TpuReadyWaitOps")
