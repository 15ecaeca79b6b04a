import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stage import resident_percentile  # noqa: E402


def read(run):
    """95th percentile of the op-resident latency in
    seqread-16m-staged.ingest: thousands of 16 MiB ops in a 5 s traced
    window, so at least 200 for ten beyond it."""
    return resident_percentile(run, 95)
