"""Shared by the metrics that read the program's per-op stage counters
from the window's record (the program's docs/telemetry.md, "Stage
spans"): a percentile of its op-resident histogram, and one stage's
microseconds per op. Every number comes from the one record
``run.record``, so a sum and its op count belong together; a program
without these keys reads None, never raises."""

#: samples a percentile must leave beyond it to be read at all
TAIL_SAMPLES = 10


def resident_percentile(run, pct: float):
    """``pct`` percentile (us) of ``OpResidentLatHisto``: each op from its
    storage submit until the program sees its block resident in HBM. As
    the program's own LatencyHistogram.percentile: the lower edge of the
    log2 quarter bucket that holds it. None while fewer than
    TAIL_SAMPLES samples lie beyond it."""
    histo = (run.record or {}).get("OpResidentLatHisto") or {}
    n = int(histo.get("LatNumValues", 0))
    if n * (100.0 - pct) / 100.0 < TAIL_SAMPLES:
        return None
    target = n * pct / 100.0
    running = 0
    buckets = sorted((int(k), int(v))
                     for k, v in histo.get("LatHistoList", {}).items())
    for idx, count in buckets:
        running += count
        if running >= target:
            return 2.0 ** (idx / 4)
    return float(histo.get("LatMaxMicroSec", 0))


def per_op(run, usec_key: str, ops_key: str):
    """The record's ``usec_key`` over its ``ops_key``; None without ops."""
    rec = run.record or {}
    ops = rec.get(ops_key, 0)
    return rec.get(usec_key, 0) / ops if ops else None
