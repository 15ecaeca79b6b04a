"""The stage metrics (metrics/*.py over the program's stage counters),
fed a record recorded from a CPU run of the program: each reads the
value the record gives, and a percentile reads None while fewer than
ten samples lie beyond it."""

import copy
import json
import os
import sys

import pytest

from .helpers import BENCH, REPO

sys.path.insert(0, BENCH)

import run  # noqa: E402

with open(os.path.join(BENCH, "tests", "data", "stage_record.json")) as f:
    RECORD = json.load(f)

#: percentile metric -> (pct, the fewest samples it reads at)
PERCENTILES = {"resident_lat_p95_us.bw": (95, 200),
               "resident_lat_p90_us.verify": (90, 100),
               "resident_lat_p99_us.iops": (99, 1000)}
#: per-op metric -> (sum key, op key)
PER_OP = {
    **{f"reap_queue_us_per_op.{k}": ("ReapQueueUSec", "ReapQueueOps")
       for k in ("bw", "verify", "iops")},
    **{f"h2d_wait_us_per_op.{k}": ("TpuReadyWaitUSec", "TpuReadyWaitOps")
       for k in ("bw", "verify", "iops")},
    "verify_sync_us_per_op.verify": ("TpuVerifySyncUSec", "TpuVerifyOps"),
}


def _read(name, record):
    return run.load_reader(name)(run.Run(block_size=4096, record=record))


def test_every_new_metric_has_a_file_and_an_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(PERCENTILES) | set(PER_OP) <= declared
    for name in set(PERCENTILES) | set(PER_OP):
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.py"))


@pytest.mark.parametrize("name", sorted(PERCENTILES))
def test_resident_percentile_reads_the_histogram(name):
    pct, fewest = PERCENTILES[name]
    sys.path.insert(0, REPO)
    from elbencho_tpu.stats.latency_histogram import LatencyHistogram
    want = LatencyHistogram.from_dict(
        RECORD["OpResidentLatHisto"]).percentile(pct)
    assert _read(name, RECORD) == pytest.approx(want)
    # n samples, all in one bucket: read at `fewest`, None one below
    for n, reads in ((fewest, True), (fewest - 1, False)):
        rec = copy.deepcopy(RECORD)
        rec["OpResidentLatHisto"].update(LatNumValues=n,
                                         LatHistoList={"40": n})
        got = _read(name, rec)
        assert (got == 2 ** 10) if reads else got is None


@pytest.mark.parametrize("name", sorted(PER_OP))
def test_per_op_metric_divides_within_the_record(name):
    usec, ops = PER_OP[name]
    assert _read(name, RECORD) == pytest.approx(RECORD[usec] / RECORD[ops])
    assert _read(name, {**RECORD, ops: 0}) is None


@pytest.mark.parametrize("name", sorted(set(PERCENTILES) | set(PER_OP)))
def test_a_program_without_the_counters_reads_none(name):
    """The parent program writes none of these keys: nothing to read, no
    error."""
    assert _read(name, {"Phase": "READ", "TpuHbmBytes": 1 << 20}) is None
    assert _read(name, None) is None
