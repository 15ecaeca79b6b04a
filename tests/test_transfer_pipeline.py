"""TransferPipeline tests: in-flight window discipline, split
dispatch-vs-DMA accounting, --tpubudget enforcement, and the wire-protocol
round trip of the new counters — all on the virtual CPU mesh (conftest
forces JAX_PLATFORMS=cpu)."""

import json
import mmap

import numpy as np
import pytest

from elbencho_tpu.cli import main
from elbencho_tpu.tpu.device import (PATH_AUDIT_COUNTERS,
                                     PATH_AUDIT_MAX_KEYS, TransferPipeline,
                                     TpuWorkerContext,
                                     sum_path_audit_counters)


class _FakeArray:
    """Device-array stand-in that records when it was waited on, so ring
    ordering is testable without a device. ``ready`` mimics jax.Array's
    is_ready(): a drain of a not-yet-ready entry is a real stall."""

    def __init__(self, idx, log, ready=False):
        self.idx = idx
        self.log = log
        self.ready = ready

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.ready = True
        self.log.append(self.idx)


def test_pipeline_depth_n_inflight_ordering():
    """Submits beyond depth-1 drain the OLDEST entry first (FIFO ring:
    the host buffer reused next is the one guaranteed drained), and the
    high-water mark records the deepest in-flight window."""
    drained = []
    pipe = TransferPipeline(depth=4)
    arrs = [_FakeArray(i, drained) for i in range(10)]
    for a in arrs:
        pipe.submit(lambda a=a: a)
    # ring holds at most depth-1 = 3 after each submit's drain pass
    assert len(pipe._ring) == 3
    assert drained == list(range(7))  # FIFO: 0..6 drained in order
    assert pipe.inflight_hwm == 4     # deepest window == depth
    assert pipe.full_stalls == 7      # every full-ring drain had to wait
    pipe.flush()
    assert not pipe._ring
    assert drained == list(range(10))
    assert pipe.ops == 10


def test_pipeline_already_ready_drains_are_not_stalls():
    """A healthy fully-overlapped pipeline — every DMA done before the
    ring fills — must read ZERO stalls, not ~100%: full_stalls means the
    drain actually had to wait, so an A/B over depths can tell
    capacity-bound from fully-hidden."""
    pipe = TransferPipeline(depth=2)
    for i in range(5):
        pipe.submit(lambda i=i: _FakeArray(i, [], ready=True))
    assert pipe.full_stalls == 0
    assert pipe.inflight_hwm == 2
    # arrays without is_ready count conservatively as stalled
    pipe.submit(lambda: object.__new__(_NoIsReady))
    pipe.submit(lambda: object.__new__(_NoIsReady))
    assert pipe.full_stalls >= 1


class _NoIsReady:
    """Foreign device-array type: block_until_ready only."""

    def block_until_ready(self):
        pass


def test_pipeline_depth_one_is_synchronous():
    """depth 1 == sync mode: every submit waits (per-block latency honest),
    so nothing is ever left in flight."""
    drained = []
    pipe = TransferPipeline(depth=1)
    for i in range(3):
        pipe.submit(lambda i=i: _FakeArray(i, drained))
        assert not pipe._ring
    assert drained == [0, 1, 2]
    assert pipe.inflight_hwm == 1


def test_pipeline_flush_drains_all_and_budget_breach_is_clean():
    """flush() drains every in-flight transfer, then enforces --tpubudget:
    a breach raises one RuntimeError naming the measured overhead."""
    drained = []
    pipe = TransferPipeline(depth=8, budget_usec=1)
    for i in range(4):
        pipe.submit(lambda i=i: _FakeArray(i, drained))
    pipe.dispatch_usec = 4000  # 1000 usec/op >> 1 usec budget
    with pytest.raises(RuntimeError, match="tpubudget exceeded"):
        pipe.flush()
    assert drained == [0, 1, 2, 3]  # drained BEFORE the budget verdict
    # teardown-style flush must not re-raise (check_budget=False)
    pipe.flush(check_budget=False)


def test_pipeline_budget_within_limit_passes():
    pipe = TransferPipeline(depth=2, budget_usec=10 ** 9)
    pipe.submit(lambda: _FakeArray(0, []))
    pipe.flush()  # no raise


def test_context_interrupt_mid_window_resets_clean():
    """reset_path_counters mid-window (worker interrupt path) must drain
    the ring without a budget verdict and zero the per-phase split."""
    ctx = TpuWorkerContext(chip_id=0, block_size=4096, pipeline_depth=4,
                           dispatch_budget_usec=1)
    m = mmap.mmap(-1, 4096)
    mv = memoryview(m)
    for _ in range(3):
        ctx.host_to_device(mv, 4096)
    assert ctx._inflight  # window is live
    # interrupt: no RuntimeError even though the 1-usec budget is breached
    ctx.reset_path_counters()
    assert not ctx._inflight
    assert ctx.dispatch_usec == 0
    assert ctx.transfer_usec == 0
    assert ctx.pipe_full_stalls == 0
    assert ctx.pipe_inflight_hwm == 0
    ctx.close()


def test_context_split_accounting_both_directions():
    """H2D and D2H both contribute to the dispatch side of the split (the
    budget covers every host-side submit on the hot path)."""
    ctx = TpuWorkerContext(chip_id=0, block_size=4096, pipeline_depth=2)
    m = mmap.mmap(-1, 4096)
    mv = memoryview(m)
    ctx.host_to_device(mv, 4096)
    h2d_ops = ctx._pipeline.ops
    ctx.device_to_host(mv, 4096)
    assert ctx._pipeline.ops == h2d_ops + 1
    ctx.flush()
    assert ctx.dispatch_usec >= 0
    assert ctx.transfer_usec >= 0
    ctx.close()


def test_staged_path_reuses_staging_slots():
    """Donation-based slot recycling: steady-state staged ingest reuses
    HBM staging buffers instead of allocating per block (when the backend
    supports donation; either way the data path stays correct)."""
    ctx = TpuWorkerContext(chip_id=0, block_size=4096, pipeline_depth=2)
    ctx.warmup_transfer()
    m = mmap.mmap(-1, 4096)
    mv = memoryview(m)
    mv[:4] = b"\xaa\xbb\xcc\xdd"
    for _ in range(6):
        ctx.host_to_device(mv, 4096)
    ctx.flush()
    if ctx._donate_ok:
        assert ctx.staging_reuses >= 4
    assert bytes(np.asarray(ctx._last_ingested).view(np.uint8)[:4]) \
        == b"\xaa\xbb\xcc\xdd"
    ctx.close()


def test_staged_slot_rotation_ignores_d2h_ops():
    """Regression: slot rotation used to key on pipeline.ops, which D2H
    note_dispatch also increments — a mixed H2D/D2H phase then reused
    (and donated) a staging slot whose array was still in the in-flight
    ring. The rotation counter must advance only on staged H2D submits."""
    ctx = TpuWorkerContext(chip_id=0, block_size=4096, pipeline_depth=4)
    m = mmap.mmap(-1, 4096)
    mv = memoryview(m)
    for _ in range(3):
        ctx.host_to_device(mv, 4096)
        ctx.device_to_host(mv, 4096)  # advances pipeline.ops, not slots
    assert ctx._staged_submits == 3
    assert ctx._pipeline.ops == 6
    ctx.flush()
    ctx.close()


def test_tpubatch_non_word_aligned_block_size():
    """Round-5 advisor: -b 6 --tpubatch 3 used to ValueError out of
    np.frombuffer (mmap size not a uint32 multiple). The aggregation ring
    must round its mmap up and keep working."""
    ctx = TpuWorkerContext(chip_id=0, block_size=6, batch_blocks=3)
    m = mmap.mmap(-1, 8)
    mv = memoryview(m)[:6]
    for _ in range(4):
        ctx.host_to_device(mv, 6)
    ctx.flush()
    ctx.close()


def test_tpubench_pipelined_keeps_transfers_in_flight(tmp_path):
    """Acceptance: --tpubench h2d with --iodepth > 1 keeps >= 2 transfers
    in flight (high-water-mark counter) and reports dispatch vs DMA time
    as separate JSON fields."""
    jsonfile = tmp_path / "out.json"
    rc = main(["--tpubench", "-s", "2M", "-b", "128K", "--iodepth", "4",
               "--nolive", "--jsonfile", str(jsonfile)])
    assert rc == 0
    rec = json.loads(jsonfile.read_text().splitlines()[0])
    assert rec["TpuPipeInflightHwm"] >= 2
    # stalls only count drains that actually waited — 0 on a fast
    # backend is healthy, the key just has to round-trip
    assert rec["TpuPipeFullStalls"] >= 0
    # the split is reported as separate fields, dispatch strictly
    # host-side (> 0 on any real run), DMA wall time >= 0
    assert rec["TpuDispatchUSec"] > 0
    assert rec["TpuTransferUSec"] >= 0
    assert rec["TpuHbmBytes"] == 2 << 20


def test_tpubench_sync_depth_has_hwm_one(tmp_path):
    """--tpudepth 1 forces sync mode even with --iodepth > 1: one transfer
    in flight at a time."""
    jsonfile = tmp_path / "out.json"
    rc = main(["--tpubench", "-s", "1M", "-b", "128K", "--iodepth", "4",
               "--tpudepth", "1", "--nolive", "--jsonfile", str(jsonfile)])
    assert rc == 0
    rec = json.loads(jsonfile.read_text().splitlines()[0])
    assert rec["TpuPipeInflightHwm"] == 1


def test_tpubudget_breach_fails_run_loudly(tmp_path, capsys):
    """An unmeetable --tpubudget (0.001 usec/op is below any Python
    dispatch) must fail the run with the budget message, not ship a
    degraded number."""
    rc = main(["--tpubench", "-s", "512K", "-b", "64K", "--iodepth", "2",
               "--tpubudget", "1", "--nolive"])
    # dispatch on the CPU backend costs way over 1 usec/op
    assert rc != 0
    err = capsys.readouterr().err
    assert "tpubudget exceeded" in err


def test_tpubudget_generous_budget_passes(tmp_path):
    rc = main(["--tpubench", "-s", "512K", "-b", "64K", "--iodepth", "2",
               "--tpubudget", str(10 ** 9), "--nolive"])
    assert rc == 0


def test_tpudepth_requires_tpu_path():
    """--tpudepth/--tpubudget without a TPU data path is a config error,
    not a silently ignored flag."""
    rc = main(["-w", "-t", "1", "-s", "4K", "-b", "4K", "--tpudepth", "4",
               "--nolive", "/tmp/nonexistent-elbencho-x"])
    assert rc != 0


def test_dispatch_counters_roundtrip_service_wire():
    """The dispatch/transfer split and pipeline counters survive the
    service wire protocol: a master-side RemoteWorker ingests the keys a
    service-side Statistics.build_result_record emits."""
    from elbencho_tpu.service.remote_worker import RemoteWorker

    ingested = RemoteWorker.__new__(RemoteWorker)
    result = {
        "TpuHbmBytes": 1 << 20,
        "TpuHbmUSec": 777,
        "TpuHbmDispatchUSec": 55,
        "TpuPipeFullStalls": 3,
        "TpuPipeInflightHwm": 4,
        "TpuH2dStagedOps": 8,
    }
    ingested.tpu_transfer_bytes = result.get("TpuHbmBytes", 0)
    ingested.tpu_transfer_usec = result.get("TpuHbmUSec", 0)
    ingested.tpu_dispatch_usec = result.get("TpuHbmDispatchUSec", 0)
    for _attr, key, ingest_attr in PATH_AUDIT_COUNTERS:
        setattr(ingested, ingest_attr, result.get(key, 0))
    ingested._tpu = None

    assert ingested.tpu_dispatch_usec == 55
    assert ingested.tpu_pipe_full_stalls == 3
    assert ingested.tpu_pipe_inflight_hwm == 4

    # the master-side merge sums ops but MAXes the high-water mark: two
    # services at hwm 4 did not make any ring 8 deep
    totals = sum_path_audit_counters([ingested, ingested])
    assert totals["TpuH2dStagedOps"] == 16
    assert totals["TpuPipeFullStalls"] == 6
    assert totals["TpuPipeInflightHwm"] == 4
    assert "TpuPipeInflightHwm" in PATH_AUDIT_MAX_KEYS


def test_statistics_reports_dispatch_vs_dma_rows(tmp_path, capsys):
    """The human-readable result table shows the split as its own rows
    when TPU ops ran (acceptance: 'separate rows in results')."""
    rc = main(["--tpubench", "-s", "1M", "-b", "256K", "--iodepth", "4",
               "--nolive"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "HBM dispatch us/op" in out
    assert "HBM DMA us/op" in out
