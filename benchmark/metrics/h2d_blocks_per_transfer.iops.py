import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import per_op  # noqa: E402


def read(run):
    """Blocks landed per list put of the chip's group commit of depth-1
    staged transfers (TpuH2dCoalescedBlocks / TpuH2dCoalescedPuts)."""
    return per_op(run, "TpuH2dCoalescedBlocks", "TpuH2dCoalescedPuts")
