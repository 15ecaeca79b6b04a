import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import resident_percentile  # noqa: E402


def read(run):
    """99th percentile of the op-resident latency in randread-4k.ingest:
    about 8,800 ops in a 5 s traced window, so at least 1,000 for ten
    beyond it."""
    return resident_percentile(run, 99)
