"""The cell seqread-16m-staged.ingest: found by name with its metrics,
each metric read from a record of a staged fused read recorded from a CPU
run of the program, the copy's roofline from a small trace summary, and
one run of the cell at CPU size."""

import json
import os
import sys

import pytest

from .helpers import BENCH, REPO, run_cell, tiny_checkout

sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace_reduce  # noqa: E402

CELL = "seqread-16m-staged.ingest"
PER_LAYER = {"storage_lat_p95_us.staged", "h2d_dispatch_us_per_op.staged",
             "device_idle_pct.staged", "h2d_wait_us_per_op.staged",
             "reap_queue_us_per_op.staged", "resident_lat_p95_us.staged",
             "slot_release_us_per_op.staged", "staged_copy_roofline"}
#: per-op metric -> (sum key, op key)
PER_OP = {
    "h2d_wait_us_per_op.staged": ("TpuReadyWaitUSec", "TpuReadyWaitOps"),
    "reap_queue_us_per_op.staged": ("ReapQueueUSec", "ReapQueueOps"),
    "slot_release_us_per_op.staged": ("SlotReleaseWaitUSec",
                                      "SlotReleaseWaitOps"),
}
BLOCK = 16 << 20

with open(os.path.join(BENCH, "tests", "data", "staged_record.json")) as f:
    RECORD = json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _read(name, **kw):
    return run.load_reader(name)(run.Run(block_size=BLOCK, **kw))


def test_the_cell_runs_the_readme_invocation_on_the_staged_path():
    entry, config, traffic = run.find_cell(manifest(), CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("seqread-16m-staged", "ingest", 1)
    assert config["h2d_path"] == "staged" and config["reduced"] == \
        ["file_size"]
    assert (config["block_size"], config["threads"], config["iodepth"]) \
        == (BLOCK, 1, 8)
    assert "--tpudirect" not in traffic["flags"] + config["flags"]
    assert not traffic["verify"]


def test_the_cell_reports_its_metrics():
    m = manifest()
    assert {x["name"] for x in run.metrics_for(m, CELL, False)} == \
        {"hbm_iops", "setup_s"}
    traced = run.metrics_for(m, CELL, True)
    assert {x["name"] for x in traced} == PER_LAYER
    for metric in traced:
        assert CELL in metric["workloads"]
        assert metric["moves"] == "hbm_iops"
        assert callable(run.load_reader(metric["name"]))


def test_the_recorded_record_is_a_staged_fused_read():
    assert RECORD["TpuStreamFusedOps"] == RECORD["TpuH2dStagedOps"] > 0
    assert RECORD["TpuH2dDirectOps"] == 0
    # every held slot but the depth-1 still held at the phase's end
    assert RECORD["SlotReleaseWaitOps"] == RECORD["TpuH2dStagedOps"] - 7


@pytest.mark.parametrize("name", sorted(PER_OP))
def test_per_op_metric_reads_the_record(name):
    usec, ops = PER_OP[name]
    assert _read(name, record=RECORD) == \
        pytest.approx(RECORD[usec] / RECORD[ops])
    waited = {**RECORD, usec: 3 * RECORD[ops]}
    assert _read(name, record=waited) == pytest.approx(3.0)
    assert _read(name, record={**RECORD, ops: 0}) is None


def test_dispatch_and_resident_tail_read_the_record():
    assert _read("h2d_dispatch_us_per_op.staged", record=RECORD) == \
        pytest.approx(RECORD["TpuDispatchUSec"] / RECORD["TpuH2dStagedOps"])
    sys.path.insert(0, REPO)
    from elbencho_tpu.stats.latency_histogram import LatencyHistogram
    want = LatencyHistogram.from_dict(
        RECORD["OpResidentLatHisto"]).percentile(95)
    assert _read("resident_lat_p95_us.staged", record=RECORD) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", sorted(set(PER_OP) | {
    "resident_lat_p95_us.staged", "h2d_dispatch_us_per_op.staged"}))
def test_a_program_without_the_counters_reads_none(name):
    assert _read(name, record={"Phase": "READ", "TpuHbmBytes": 1}) is None
    assert _read(name, record=None) is None


def _summary(programs):
    return trace_reduce.Summary(window_s=1.0, n_devices=1, busy_s=0.1,
                                programs=programs)


PEAKS = {"hbm_bytes_per_s": 819e9}


def test_copy_roofline_is_its_least_time_over_its_device_time():
    seconds = 4 * 50e-6
    trace = _summary([("jit_staged_slot_copy", 50e-6, ("copy.3",))] * 4
                     + [("jit_other", 1.0, ("copy.1",))])
    got = _read("staged_copy_roofline", trace=trace, peaks=PEAKS)
    assert got == pytest.approx(100 * 4 * 2 * BLOCK / 819e9 / seconds)
    assert 0 < got <= 100


def test_copy_roofline_reads_none_without_the_named_program():
    """A program whose copy is an unnamed lambda (``jit__lambda``):
    nothing to read, no error."""
    trace = _summary([("jit__lambda", 50e-6, ("copy.3",))])
    assert _read("staged_copy_roofline", trace=trace, peaks=PEAKS) is None
    assert _read("staged_copy_roofline", trace=None, peaks=PEAKS) is None
    assert _read("staged_copy_roofline", trace=trace, peaks=None) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_at_cpu_size(tmp_path, trace):
    co = tiny_checkout(str(tmp_path))
    path = os.path.join(co, "benchmark", "configs", "seqread-16m-staged.json")
    with open(path) as f:
        config = json.load(f)
    config["file_size"], config["warmup_flags"] = 256 << 20, \
        ["-s", str(48 << 20)]
    with open(path, "w") as f:
        json.dump(config, f)
    rc, result, err = run_cell(co, CELL, trace=trace)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    metrics = set(result["metrics"])
    if trace:
        # the CPU trace has no TPU device: the device metrics read nothing
        assert metrics == PER_LAYER - {"device_idle_pct.staged",
                                       "staged_copy_roofline"}
    else:
        assert metrics == {"hbm_iops", "setup_s"}
