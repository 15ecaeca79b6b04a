"""Worker base class: per-worker stats container + phase wait loop.

Reference: source/workers/Worker.{h,cpp} — atomic LiveOps (entries/bytes/
iops) x2 (normal + rwmix-read), stonewall snapshots for first-done results
(Worker.h:203), 4 latency histograms (iops/entries x normal/rwmix),
per-phase elapsed time, interruption flag (Worker.h:48-60,167-219).

In CPython the GIL makes single-value counter updates effectively atomic,
so LiveOps are plain ints written by the owning worker thread and read by
the statistics thread; the C++ ioengine writes its counters into a shared
memoryview that the worker syncs from.
"""

from __future__ import annotations

import time

from ..stats.latency_histogram import LatencyHistogram
from .shared import WorkerInterruptedException, WorkersSharedData

INTERRUPT_CHECK_INTERVAL = 128  # ops between interruption checks
                                # (reference: LocalWorker.cpp:70)


class LiveOps:
    """entries/bytes/iops counter triple (reference: LiveOps, Worker.h)."""

    __slots__ = ("num_entries_done", "num_bytes_done", "num_iops_done")

    def __init__(self):
        self.num_entries_done = 0
        self.num_bytes_done = 0
        self.num_iops_done = 0

    def snapshot(self) -> "LiveOps":
        s = LiveOps()
        s.num_entries_done = self.num_entries_done
        s.num_bytes_done = self.num_bytes_done
        s.num_iops_done = self.num_iops_done
        return s

    def add(self, other: "LiveOps") -> None:
        self.num_entries_done += other.num_entries_done
        self.num_bytes_done += other.num_bytes_done
        self.num_iops_done += other.num_iops_done

    def reset(self) -> None:
        self.num_entries_done = 0
        self.num_bytes_done = 0
        self.num_iops_done = 0

    def as_dict(self) -> dict:
        return {"NumEntriesDone": self.num_entries_done,
                "NumBytesDone": self.num_bytes_done,
                "NumIOPSDone": self.num_iops_done}


class Worker:
    def __init__(self, shared: WorkersSharedData, rank: int):
        self.shared = shared
        self.rank = rank
        self.live_ops = LiveOps()
        self.live_ops_rwmix_read = LiveOps()
        self.stonewall_ops = LiveOps()
        self.stonewall_ops_rwmix_read = LiveOps()
        self.stonewall_taken = False
        self.iops_latency_histo = LatencyHistogram()
        self.entries_latency_histo = LatencyHistogram()
        self.iops_latency_histo_rwmix = LatencyHistogram()
        self.entries_latency_histo_rwmix = LatencyHistogram()
        # elapsed usec of finished workers; RemoteWorker appends one entry
        # per remote thread (reference: Worker elapsedUSecVec)
        self.elapsed_usec_vec: "list[int]" = []
        self.stonewall_elapsed_usec = 0
        self.got_phase_work = True
        self.is_interrupted = False
        self.phase_finished = False
        self._ops_since_check = 0
        # --tracefile span recorder; None keeps every instrumentation
        # point a single attribute test (telemetry/tracer.py contract)
        self._tracer = getattr(shared, "tracer", None)
        self.tpu_transfer_bytes = 0   # HBM ingest accounting (TPU data path)
        self.tpu_transfer_usec = 0    # DMA wall time (submit -> ready)
        self.tpu_dispatch_usec = 0    # host-side submit cost (the overhead
                                      # --tpubudget bounds)
        # platform/kind/count of the devices the TPU path ran on
        # (tpu.device.device_stamp; empty while no phase touched them)
        self.tpu_device_stamp: dict = {}
        # on-device data fingerprint of the phase (tpu.device
        # fold_fingerprint; --tpuslice stripes fold here)
        self.tpu_fp_sum = self.tpu_fp_xor = self.tpu_fp_blocks = 0
        # data-plane fault-tolerance audit (--ioretries/--iotimeout;
        # worker-owned entries of PATH_AUDIT_COUNTERS — see
        # tpu.device.PATH_AUDIT_WORKER_ATTRS)
        self.io_retries = 0       # per-op transient-error retries
        self.io_retry_usec = 0    # total backoff slept for those retries
        self.io_timeouts = 0      # ops cancelled by the --iotimeout deadline
        # unified staging-pool audit (utils/staging_pool.py): local
        # workers serve these from _staging_pool via PATH_AUDIT_POOL_ATTRS;
        # the attributes exist so RemoteWorker ingest and pool-less
        # workers read as zero
        self.pool_buf_reuses = 0
        self.pool_occupancy_hwm = 0
        self.pool_registered_ops = 0
        self.pool_sqpoll_ops = 0
        # pod-slice phase audit (--tpuslice; PATH_AUDIT_WORKER_ATTRS):
        # per-worker shard-ingest MiB plus the driver worker's ICI
        # redistribution counters (workers/tpuslice.py keeps the raw byte
        # totals in _shard_ingest_bytes/_ici_redist_bytes and mirrors the
        # MiB floor here so the wire stays integer-MiB)
        self.shard_ingest_mib = 0
        self.ici_redist_mib = 0
        self.ici_redist_usec = 0
        self.ici_gbps_hwm = 0
        self._shard_ingest_bytes = 0
        self._ici_redist_bytes = 0
        # slow-op forensics audit (--slowops/--opsample;
        # PATH_AUDIT_WORKER_ATTRS): plain ints so RemoteWorker ingest
        # and recorder-less workers read as zero
        self.slow_ops_recorded = 0
        self.op_samples_dropped = 0
        self.tail_p999_usec_hwm = 0
        # per-op stage counters of the storage->HBM path (worker-owned
        # PATH_AUDIT_COUNTERS entries): a reaped op's wait behind the
        # earlier ops of its reap batch, and the ops that waited; the
        # fused ring's wait in release drains, and the held-back slots
        # it let go
        self.reap_queue_usec = 0
        self.reap_queue_ops = 0
        self.slot_release_wait_usec = 0
        self.slot_release_wait_ops = 0
        # storage submit -> block resident in HBM, per H2D op of the
        # block loops (JSON OpResidentLatHisto)
        self.op_resident_histo = LatencyHistogram()
        # --slowops per-worker recorder; None keeps every instrumentation
        # point a single attribute test (telemetry/slowops.py contract)
        from ..telemetry.slowops import make_recorder
        self._slowops = make_recorder(self)

    def oplog(self, op_name: str, entry_name: str = "", offset: int = 0,
              length: int = 0):
        """Per-op trace context (pre+post records incl. error flag);
        no-op without --opslog (reference: OPLOG macros, OpsLogger.h:19-36)."""
        from ..toolkits.ops_logger import null_logged_op
        ops_log = getattr(self, "_ops_log", None)
        if ops_log is None:
            return null_logged_op()
        return ops_log.logged_op(op_name, entry_name, offset, length)

    # -- stats management ---------------------------------------------------

    def reset_stats(self) -> None:
        # per-phase interrupts (e.g. --timelimit expiry) must not leak into
        # the next phase; a user Ctrl-C persists via shared.interrupt_requested
        self.is_interrupted = False
        self.live_ops.reset()
        self.live_ops_rwmix_read.reset()
        self.stonewall_ops.reset()
        self.stonewall_ops_rwmix_read.reset()
        self.stonewall_taken = False
        self.iops_latency_histo.reset()
        self.entries_latency_histo.reset()
        self.iops_latency_histo_rwmix.reset()
        self.entries_latency_histo_rwmix.reset()
        self.elapsed_usec_vec = []
        self.stonewall_elapsed_usec = 0
        self.got_phase_work = True
        self.phase_finished = False
        self._ops_since_check = 0
        self.tpu_transfer_bytes = 0
        self.tpu_transfer_usec = 0
        self.tpu_dispatch_usec = 0
        self.tpu_fp_sum = self.tpu_fp_xor = self.tpu_fp_blocks = 0
        self.io_retries = 0
        self.io_retry_usec = 0
        self.io_timeouts = 0
        self.pool_buf_reuses = 0
        self.pool_occupancy_hwm = 0
        self.pool_registered_ops = 0
        self.pool_sqpoll_ops = 0
        self.shard_ingest_mib = 0
        self.ici_redist_mib = 0
        self.ici_redist_usec = 0
        self.ici_gbps_hwm = 0
        self._shard_ingest_bytes = 0
        self._ici_redist_bytes = 0
        self.slow_ops_recorded = 0
        self.op_samples_dropped = 0
        self.tail_p999_usec_hwm = 0
        self.reap_queue_usec = 0
        self.reap_queue_ops = 0
        self.slot_release_wait_usec = 0
        self.slot_release_wait_ops = 0
        self.op_resident_histo.reset()
        if self._slowops is not None:
            self._slowops.reset_phase()

    def create_stonewall_stats_if_triggered(self) -> None:
        """Snapshot current counters when the first worker finished
        (reference: createStoneWallStats, Worker.h:203)."""
        if self.stonewall_taken or not self.shared.stonewall_triggered:
            return
        self.stonewall_ops = self.live_ops.snapshot()
        self.stonewall_ops_rwmix_read = self.live_ops_rwmix_read.snapshot()
        self.stonewall_elapsed_usec = self.phase_elapsed_usec()
        self.stonewall_taken = True

    def finish_phase_stats(self) -> None:
        """Called by the worker when its phase work is complete."""
        if self._slowops is not None:
            # final TailP999UsecHwm BEFORE anything sums the counters
            # (the service's /benchresult, the master's phase results)
            self._slowops.refresh_hwm()
        if not self.stonewall_taken:
            # first finisher: stonewall stats == final stats
            self.stonewall_ops = self.live_ops.snapshot()
            self.stonewall_ops_rwmix_read = self.live_ops_rwmix_read.snapshot()
            self.stonewall_elapsed_usec = self.phase_elapsed_usec()
            self.stonewall_taken = True
        self.elapsed_usec_vec.append(self.phase_elapsed_usec())
        self.phase_finished = True

    def phase_elapsed_usec(self) -> int:
        return int((time.monotonic()
                    - self.shared.phase_start_monotonic) * 1_000_000)

    # -- interruption -------------------------------------------------------

    def interrupt_execution(self) -> None:
        self.is_interrupted = True

    def check_interruption_request(self, force: bool = False) -> None:
        """Cheap periodic check in hot loops; also the stonewall snapshot
        point (reference: checkInterruptionRequest + stonewall polling).
        Worker-thread only (counter snapshot + _ops_since_check are not
        thread-safe) — helper threads use check_interruption_flag_only."""
        self._ops_since_check += 1
        if not force and self._ops_since_check < INTERRUPT_CHECK_INTERVAL:
            return
        self._ops_since_check = 0
        self.create_stonewall_stats_if_triggered()
        self.check_interruption_flag_only()

    def check_interruption_flag_only(self) -> None:
        """Thread-safe interruption test (no stonewall snapshot, no
        counters) for request threads of the S3 pipeline."""
        if (self.is_interrupted or self.shared.interrupt_requested
                or self.shared.phase_time_expired):
            raise WorkerInterruptedException("worker interruption requested")

    # -- thread entry -------------------------------------------------------

    def run(self) -> None:
        raise NotImplementedError

    def thread_start(self) -> None:
        try:
            self.run()
        except Exception as err:  # noqa: BLE001 - worker errors are reported
            from ..toolkits import logger
            logger.log_error(f"Worker {self.rank} terminated on error: "
                             f"{type(err).__name__}: {err}")
            self.shared.inc_num_workers_done_with_error(err)
