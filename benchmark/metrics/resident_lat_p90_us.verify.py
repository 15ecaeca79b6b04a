import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import resident_percentile  # noqa: E402


def read(run):
    """90th percentile of the op-resident latency in seqread-16m.verify:
    about 137 verified blocks in a 5 s traced window, so at least 100 for
    ten beyond it."""
    return resident_percentile(run, 90)
