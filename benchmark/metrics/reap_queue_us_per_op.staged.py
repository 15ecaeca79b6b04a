import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import per_op  # noqa: E402


def read(run):
    """Microseconds a reaped op waited from its reap until its H2D
    dispatch started (ReapQueueUSec / ReapQueueOps): the earlier ops of
    its reap batch, their H2D and verify work."""
    return per_op(run, "ReapQueueUSec", "ReapQueueOps")
