"""TPU HBM data path: per-worker device buffers + host<->HBM transfers.

This is the TPU-native replacement for the reference's CUDA staging
(SURVEY.md section 2.5 "GPU staging" — the north-star port target):

  cudaSetDevice / workerRank % gpuIDs  ->  worker rank % tpu_ids chip pick
                                           (reference LocalWorker.cpp:1444)
  cudaMalloc per iodepth               ->  jax device_put-allocated HBM
                                           staging arrays on the chosen chip
  cudaMemcpy H2D after reads           ->  jax.device_put onto the chip +
                                           block_until_ready (completion wait
                                           keeps per-block latency honest)
  cudaMemcpy D2H before writes         ->  np.asarray(device_array) D2H; the
                                           write-source data originates in
                                           HBM via on-device PRNG (curand
                                           analogue, ops/fill.py)
  cuFileRead (GPUDirect)               ->  --tpudirect: zero-bounce path
                                           using jax dlpack-view of the
                                           page-aligned I/O buffer
  CuFileHandleData register/deregister ->  TpuWorkerContext lifecycle

Per-chip ingest bandwidth is accounted by the worker (tpu_transfer_bytes /
tpu_transfer_usec) and reported by Statistics as "HBM ingest" rows.
"""

from __future__ import annotations

import _thread
import os
import threading
import time
from array import array

import numpy as np

_jax_lock = threading.Lock()
_jax_mod = None

#: H2D/D2H path-audit counter map, the single schema for how counters
#: flow from TpuWorkerContext to result records: (context attribute,
#: wire/JSON key, RemoteWorker ingest attribute). Statistics sums by it,
#: the service payload serializes by it, RemoteWorker ingests by it —
#: adding a counter here plumbs it end to end.
PATH_AUDIT_COUNTERS = (
    ("h2d_direct_ops", "TpuH2dDirectOps", "tpu_h2d_direct_ops"),
    ("h2d_staged_ops", "TpuH2dStagedOps", "tpu_h2d_staged_ops"),
    ("h2d_direct_fallbacks", "TpuH2dDirectFallbacks",
     "tpu_h2d_direct_fallbacks"),
    ("d2h_direct_ops", "TpuD2hDirectOps", "tpu_d2h_direct_ops"),
    ("d2h_staged_ops", "TpuD2hStagedOps", "tpu_d2h_staged_ops"),
    ("d2h_direct_fallbacks", "TpuD2hDirectFallbacks",
     "tpu_d2h_direct_fallbacks"),
    ("d2h_prefetch_hits", "TpuD2hPrefetchHits", "tpu_d2h_prefetch_hits"),
    ("d2h_prefetch_misses", "TpuD2hPrefetchMisses",
     "tpu_d2h_prefetch_misses"),
    ("pipe_full_stalls", "TpuPipeFullStalls", "tpu_pipe_full_stalls"),
    ("pipe_inflight_hwm", "TpuPipeInflightHwm", "tpu_pipe_inflight_hwm"),
    # ops completed by the fused native-stream loop (--tpustream): disk
    # I/O reaped from the engine's ring and handed straight to the
    # transfer pipeline — zero means the phase ran the Python loop
    ("stream_fused_ops", "TpuStreamFusedOps", "tpu_stream_fused_ops"),
    # data-plane fault tolerance (--ioretries/--iotimeout/--tpufallback):
    # per-op retry/timeout accounting lives on the WORKER (the retries
    # happen in storage loops that exist with or without a TPU context —
    # see PATH_AUDIT_WORKER_ATTRS); chip failover lives on the context
    ("io_retries", "IoRetries", "io_retries"),
    ("io_retry_usec", "IoRetryUsec", "io_retry_usec"),
    ("io_timeouts", "IoTimeouts", "io_timeouts"),
    ("chip_failovers", "TpuChipFailovers", "tpu_chip_failovers"),
    # unified staging pool (utils/staging_pool.py): slot-reuse /
    # occupancy / fixed-buffer-registration / SQPOLL audit — the proof
    # that the shared allocator (and its one-time io_uring registration)
    # actually served the phase's I/O (see PATH_AUDIT_POOL_ATTRS)
    ("pool_buf_reuses", "PoolBufReuses", "pool_buf_reuses"),
    ("pool_occupancy_hwm", "PoolOccupancyHwm", "pool_occupancy_hwm"),
    ("pool_registered_ops", "PoolRegisteredOps", "pool_registered_ops"),
    ("pool_sqpoll_ops", "PoolSqpollOps", "pool_sqpoll_ops"),
    # pod-slice phase (--tpuslice; workers/tpuslice.py): striped storage
    # ingest across every chip of the mesh + ICI redistribution. All four
    # live on the WORKER (the slice phase runs with or without a
    # per-worker TpuWorkerContext): ShardIngestMiB counts each worker's
    # shard bytes fed onto the mesh, the Ici trio is recorded by the
    # driver worker that runs the SPMD redistribution step. IciGbpsHwm is
    # a high-water mark (best single-stripe redistribution rate) and
    # MAX-merges like the other hwm counters.
    ("shard_ingest_mib", "ShardIngestMiB", "shard_ingest_mib"),
    ("ici_redist_mib", "IciRedistMiB", "ici_redist_mib"),
    ("ici_redist_usec", "IciRedistUSec", "ici_redist_usec"),
    ("ici_gbps_hwm", "IciGbpsHwm", "ici_gbps_hwm"),
    # slow-op forensics (--slowops/--opsample; telemetry/slowops.py):
    # records captured into the per-worker K-slowest heaps, sample
    # points the density reservoirs dropped on compaction, and the
    # running p99.9 high-water mark of per-op latency (MAX merge — a
    # sum of percentiles means nothing). All worker-owned: the capture
    # happens in storage loops that exist with or without a TPU context.
    ("slow_ops_recorded", "SlowOpsRecorded", "slow_ops_recorded"),
    ("op_samples_dropped", "OpSamplesDropped", "op_samples_dropped"),
    ("tail_p999_usec_hwm", "TailP999UsecHwm", "tail_p999_usec_hwm"),
    # per-op stage counters of the storage->HBM path (docs/telemetry.md
    # "Stage spans"), each sum with its own op count so a reader divides
    # within one record: the time the host blocked in the transfer
    # ring's completion wait; the on-device verify's hold on the block
    # loop (kernel dispatch + read of the fingerprint it resolves) and
    # the host's expected fingerprint; and, worker-owned,
    # the wait of a reaped op behind the earlier ops of its reap batch,
    # and the fused ring's wait in release drains (drains made only to
    # free a held-back slot) over the held slots it let go
    ("pipe_ready_wait_usec", "TpuReadyWaitUSec", "tpu_ready_wait_usec"),
    ("pipe_ready_wait_ops", "TpuReadyWaitOps", "tpu_ready_wait_ops"),
    ("verify_sync_usec", "TpuVerifySyncUSec", "tpu_verify_sync_usec"),
    ("verify_ops", "TpuVerifyOps", "tpu_verify_ops"),
    ("verify_expect_usec", "TpuVerifyExpectUSec", "tpu_verify_expect_usec"),
    ("reap_queue_usec", "ReapQueueUSec", "reap_queue_usec"),
    ("reap_queue_ops", "ReapQueueOps", "reap_queue_ops"),
    ("slot_release_wait_usec", "SlotReleaseWaitUSec",
     "slot_release_wait_usec"),
    ("slot_release_wait_ops", "SlotReleaseWaitOps", "slot_release_wait_ops"),
    # the most on-device verifies pending at once (dispatched, not yet
    # checked) when a host_to_device call returned: depth-1 when the
    # verify overlaps the transfers, 0 at depth 1 (a hwm, MAX merge)
    ("verify_pending_hwm", "TpuVerifyPendingHwm", "tpu_verify_pending_hwm"),
    # the chip's group commit of depth-1 staged transfers (H2dGroupCommit):
    # the blocks it landed, and the list puts that landed them
    ("h2d_coalesced_blocks", "TpuH2dCoalescedBlocks",
     "tpu_h2d_coalesced_blocks"),
    ("h2d_coalesced_puts", "TpuH2dCoalescedPuts", "tpu_h2d_coalesced_puts"),
)

#: counters owned by the Worker object itself rather than the
#: TpuWorkerContext: the merge reads them from the worker even when a
#: TPU context is attached, and the context's per-phase counter reset
#: must not shadow them with zeros on the context
PATH_AUDIT_WORKER_ATTRS = frozenset({
    "io_retries", "io_retry_usec", "io_timeouts",
    "pool_buf_reuses", "pool_occupancy_hwm", "pool_registered_ops",
    "pool_sqpoll_ops", "shard_ingest_mib", "ici_redist_mib",
    "ici_redist_usec", "ici_gbps_hwm", "slow_ops_recorded",
    "op_samples_dropped", "tail_p999_usec_hwm", "reap_queue_usec",
    "reap_queue_ops", "slot_release_wait_usec", "slot_release_wait_ops"})

#: counters owned by the worker's StagingPool: the merge reads them
#: from worker._staging_pool when one is attached (local workers), and
#: from the ingested worker attribute otherwise (RemoteWorkers)
PATH_AUDIT_POOL_ATTRS = frozenset({
    "pool_buf_reuses", "pool_occupancy_hwm", "pool_registered_ops",
    "pool_sqpoll_ops"})

#: counters that merge across workers as MAX, not sum: a high-water mark
#: summed over workers would report an in-flight depth no single ring
#: ever reached. TpuChipFailovers is a hwm too: every worker sharing a
#: lost chip records its own failover, so a sum would multiply one chip
#: loss by the worker count — MAX reports the deepest failover chain any
#: single worker ran (~ chips lost along the worst path).
PATH_AUDIT_MAX_KEYS = frozenset({"TpuPipeInflightHwm", "TpuChipFailovers",
                                 "PoolOccupancyHwm", "IciGbpsHwm",
                                 "TailP999UsecHwm", "TpuVerifyPendingHwm"})


def sum_path_audit_counters(workers) -> dict:
    """Total the path-audit counters over a worker list, reading local
    workers' TpuWorkerContext (or StagingPool, for PATH_AUDIT_POOL_ATTRS
    entries) directly — worker-owned entries always come from the
    worker — and RemoteWorkers' ingested attributes (keyed by wire/JSON
    name, ready to merge into records). PATH_AUDIT_MAX_KEYS entries
    merge as max instead of sum."""
    totals = {key: 0 for _, key, _ in PATH_AUDIT_COUNTERS}
    for w in workers:
        ctx = getattr(w, "_tpu", None)
        pool = getattr(w, "_staging_pool", None)
        for attr, key, ingest_attr in PATH_AUDIT_COUNTERS:
            if attr in PATH_AUDIT_POOL_ATTRS:
                val = getattr(pool, attr) if pool is not None \
                    else getattr(w, ingest_attr, 0)
            elif ctx is not None and attr not in PATH_AUDIT_WORKER_ATTRS:
                val = getattr(ctx, attr)
            else:
                val = getattr(w, ingest_attr, 0)
            if key in PATH_AUDIT_MAX_KEYS:
                totals[key] = max(totals[key], val)
            else:
                totals[key] += val
    return totals


#: conservative message markers for device-loss classification —
#: deliberately narrow so an unrelated RuntimeError (e.g. the --tpubudget
#: breach, whose message mentions DMA) can never be eaten by failover
_DEVICE_LOSS_MARKERS = (
    "device lost", "data loss", "data_loss", "failed to enqueue",
    "device is in an error state", "device unavailable",
    "chip is unavailable", "hardware failure", "device halted",
)

#: exception type names that identify an XLA runtime / device failure
#: (matched by name: jaxlib's XlaRuntimeError moves between modules
#: across versions, and tests raise a shape-compatible fake)
_DEVICE_LOSS_TYPE_NAMES = ("XlaRuntimeError", "DeviceLostError",
                           "TpuDeviceLostError")


def is_device_loss_error(err: BaseException) -> bool:
    """Classify an exception raised on the TPU transfer path: True for
    XLA-runtime/device-loss failures (the chip-failover trigger of
    --tpufallback), False for everything else — a logic error or a
    --tpubudget breach must abort, never failover."""
    for cls in type(err).__mro__:
        if cls.__name__ in _DEVICE_LOSS_TYPE_NAMES:
            return True
    msg = str(err).lower()
    return any(marker in msg for marker in _DEVICE_LOSS_MARKERS)


class TpuDeviceLostError(RuntimeError):
    """Raised when --tpufallback abort (the default) sees a device loss:
    carries the chip id so the phase error names the failed chip."""

    def __init__(self, chip_id: int, cause: BaseException):
        self.chip_id = chip_id
        super().__init__(
            f"TPU chip {chip_id} lost mid-phase "
            f"({type(cause).__name__}: {cause}); rerun with --tpufallback "
            f"chip|host to survive single-chip loss")


#: persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset: a
#: fixed path in the checkout, since the path is part of the cache key and
#: TPU jit compiles outlast the short-lived benchmark processes
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _get_jax():
    """Lazy jax import so CPU-only workloads never pay for it. Where
    JAX_COMPILATION_CACHE_DIR is set, jax reads it itself."""
    global _jax_mod
    if _jax_mod is None:
        with _jax_lock:
            if _jax_mod is None:
                import jax
                if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                    jax.config.update("jax_compilation_cache_dir",
                                      JAX_CACHE_DIR)
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0.5)
                _jax_mod = jax
    return _jax_mod


def tpu_devices() -> list:
    """The devices of the TPU path. Raises unless they are TPUs, or
    JAX_PLATFORMS explicitly names cpu (tests and CPU rehearsals): a TPU
    run must never quietly measure another backend."""
    devices = list(_get_jax().devices())
    platform = devices[0].platform
    explicit = {p.strip() for p in
                os.environ.get("JAX_PLATFORMS", "").lower().split(",")}
    if platform != "tpu" and platform not in explicit:
        raise RuntimeError(
            f"the TPU path found no TPU: jax's default backend is "
            f"{platform!r} ({len(devices)} device(s)); set "
            f"JAX_PLATFORMS={platform} to run it there on purpose")
    return devices


def check_chip_ids(chip_ids, num_devices: int) -> None:
    """A --tpuids entry names a device; past the device count it is a
    config error, never a wrap onto another chip."""
    bad = [c for c in chip_ids if not 0 <= c < num_devices]
    if bad:
        from ..config.args import ConfigError
        raise ConfigError(
            f"--tpuids {','.join(map(str, bad))}: this host has "
            f"{num_devices} device(s) (ids 0-{num_devices - 1})")


def device_stamp(devices: list, device=None) -> dict:
    """Result-record stamp of the device a phase ran on: platform and
    kind as jax reports them, and the process's device count."""
    device = devices[0] if device is None else device
    return {"TpuPlatform": device.platform,
            "TpuDeviceKind": device.device_kind,
            "TpuDeviceCount": len(devices)}


class TransferPipeline:
    """Ring of up to ``depth`` in-flight device transfers with split
    dispatch-vs-DMA accounting (the io_uring-style submission/completion
    window of the reference's cuFile iodepth semantics, re-done on JAX's
    async dispatch: submit block k+1 while block k's DMA is in flight,
    wait only when the ring is full or at flush).

    Counters (all per-phase, reset via reset_counters):

    - ``dispatch_usec``  host-side submit cost: time spent issuing
      transfers (device_put / dlpack import / jitted copy dispatch) —
      the per-block overhead the VERDICT's budget targets.
    - ``transfer_usec``  DMA wall time: submission -> block_until_ready
      per transfer, measured when the ring entry is drained. In-flight
      windows overlap, so this is per-block transfer latency, not a
      divisor for aggregate bandwidth (use phase wall time for that).
    - ``full_stalls``    full-ring drains that actually had to WAIT for
      the oldest transfer (it was not yet ready) — zero on a healthy
      fully-overlapped pipeline, ~ops when the ring is capacity-bound.
    - ``inflight_hwm``   in-flight high-water mark — proof the pipeline
      actually overlapped transfers (>= 2 under any real pipelining).
    - ``ready_wait_usec`` / ``ready_wait_ops``  the time the host actually
      blocked in a drain's completion wait, and the drains: unlike
      ``transfer_usec`` these do not overlap, so their sum is host time.
    - ``resident``       op-resident latencies (usec) not yet booked: from
      the storage submit of an op carried by a ring entry (``op_ns``) to
      the moment its drain returns; the worker moves them into its
      histogram in bulk (``TpuWorkerContext.take_resident_usec``).

    ``budget_usec`` (--tpubudget): maximum average host-side dispatch
    cost per submitted op; check_budget() fails the run LOUDLY when the
    measured overhead exceeds it.
    """

    def __init__(self, depth: int, budget_usec: int = 0):
        from collections import deque
        self.depth = max(depth, 1)
        self.budget_usec = max(budget_usec, 0)
        # (device array, submit-done perf_counter_ns, the op's storage
        # submit perf_counter_ns or 0, the op's span id or None)
        self._ring = deque()
        self.dispatch_usec = 0
        self.transfer_usec = 0
        self.full_stalls = 0
        self.inflight_hwm = 0
        self.ops = 0
        self.ready_wait_usec = 0
        self.ready_wait_ops = 0
        self.resident = array("Q")
        # per-phase stage-span handle (telemetry/tracer.py StageSpans);
        # None keeps the hot path a single attribute test per transfer
        self.stages = None

    def submit(self, submit_fn, op_ns: int = 0, op=None, then=None):
        """Issue one transfer (submit_fn() -> device array) into the ring,
        then drain to at most depth-1 in flight: with io_depth rotating
        host buffers, the buffer reused next is then guaranteed drained
        (depth == 1 -> fully synchronous, per-block latency honest).
        ``op_ns`` is the storage-submit stamp of the op whose block this
        is, when its drain is where the block becomes resident (0: not
        booked here); ``op`` the op's span id. ``then(arr)``, when
        given, dispatches device work that reads the block and returns
        its output, which the ring entry waits on instead: the entry's
        drain then also ends that work's reads of the block (and of the
        host buffer an aliasing import still reads)."""
        st = self.stages
        token = st.begin("tpu_dispatch") if st is not None else None
        t0 = time.perf_counter_ns()
        arr = submit_fn()
        t1 = time.perf_counter_ns()
        self.dispatch_usec += (t1 - t0) // 1000
        self.ops += 1
        if st is not None:
            st.end(token, "tpu_dispatch", "tpu", t0, t1, op)
        # an op whose residency is booked elsewhere (its on-device verify
        # resolves it) keeps its id off the entry: the entry's later
        # drain is then no stage of that op
        self._ring.append((arr if then is None else then(arr), t1, op_ns,
                           op if op_ns else None))
        if len(self._ring) > self.inflight_hwm:
            self.inflight_hwm = len(self._ring)
        while len(self._ring) >= self.depth:
            self._drain_one(count_stall=True)
        return arr

    def land_grouped(self, group: "H2dGroupCommit", view, op_ns: int = 0,
                     op=None) -> "_Landing":
        """Land one block through its chip's group commit and book it as
        a depth-1 submit and its drain would: its share of the list
        put's host time as dispatch, the time its thread blocked until
        the block was resident as one ready wait (queueing behind the
        put in flight included), put-to-ready as transfer time, and
        storage submit (``op_ns``) to ready as its resident latency."""
        landing = group.land(view, self.stages, op)
        self.dispatch_usec += landing.put_usec
        self.ops += 1
        self.transfer_usec += (landing.ready_ns - landing.put_ns) // 1000
        self.ready_wait_usec += landing.wait_ns // 1000
        self.ready_wait_ops += 1
        self.inflight_hwm = max(self.inflight_hwm, 1)
        st = self.stages
        if st is not None:
            st.record("tpu_dma", "tpu", landing.put_ns, landing.ready_ns, op)
        if op_ns:
            self.resident.append((landing.ready_ns - op_ns) // 1000)
            if st is not None and op is not None:
                st.record("op", "op", op_ns, landing.ready_ns, op)
        return landing

    def wait_newest_on(self, arr) -> None:
        """Make the newest in-flight entry wait on ``arr`` instead: work
        dispatched after that entry's, which in-order device execution
        completes only after everything dispatched on the entry's block
        in between (no-op on an empty ring)."""
        if self._ring:
            self._ring[-1] = (arr,) + self._ring[-1][1:]

    def note_dispatch(self, usec: int) -> None:
        """Account host-side submit cost of a transfer issued outside the
        ring (D2H exports, speculative prefetch issues) so --tpubudget
        covers both directions."""
        self.dispatch_usec += usec
        self.ops += 1
        if self.stages is not None:
            now = time.perf_counter_ns()
            self.stages.record("tpu_dispatch", "tpu", now - usec * 1000, now)

    def note_transfer(self, usec: int) -> None:
        """Account DMA wall time of a transfer completed outside the ring
        (blocking D2H export waits)."""
        self.transfer_usec += usec
        if self.stages is not None:
            now = time.perf_counter_ns()
            self.stages.record("tpu_dma", "tpu", now - usec * 1000, now)

    def _drain_one(self, count_stall: bool = False) -> None:
        """Complete the oldest in-flight transfer. A full-ring drain
        (count_stall) only counts as a stall when the transfer had NOT
        finished yet — a healthy fully-overlapped pipeline drains
        already-ready entries and must read as zero stalls, not ~100%.
        Arrays without is_ready (foreign device types) count
        conservatively as stalled."""
        arr, t_submit, op_ns, op = self._ring.popleft()
        if count_stall:
            is_ready = getattr(arr, "is_ready", None)
            if is_ready is None or not is_ready():
                self.full_stalls += 1
        st = self.stages
        token = st.begin("h2d_wait") if st is not None else None
        t_wait = time.perf_counter_ns()
        arr.block_until_ready()
        done_ns = time.perf_counter_ns()
        self.transfer_usec += (done_ns - t_submit) // 1000
        self.ready_wait_usec += (done_ns - t_wait) // 1000
        self.ready_wait_ops += 1
        if op_ns:
            self.resident.append((done_ns - op_ns) // 1000)
        if st is not None:
            st.end(token, "h2d_wait", "tpu", t_wait, done_ns, op)
            st.record("tpu_dma", "tpu", t_submit, done_ns, op)
            if op is not None:
                st.record("op", "op", op_ns, done_ns, op)

    def flush(self, check_budget: bool = True) -> None:
        """Drain every in-flight transfer (phase-end completion wait); by
        default also enforce --tpubudget — teardown paths pass
        check_budget=False so a breach can't fire during cleanup."""
        while self._ring:
            self._drain_one()
        if check_budget:
            self.check_budget()

    def check_budget(self) -> None:
        """--tpubudget: fail LOUDLY when the measured per-op host dispatch
        overhead exceeds the budget (the VERDICT's 'measured per-block
        overhead budget' — a silent regression of the dispatch hot path
        must abort the run, not ship a degraded number)."""
        if not self.budget_usec or not self.ops:
            return
        avg = self.dispatch_usec / self.ops
        if avg > self.budget_usec:
            raise RuntimeError(
                f"--tpubudget exceeded: measured per-op dispatch overhead "
                f"{avg:.1f} usec > budget {self.budget_usec} usec over "
                f"{self.ops} ops ({self.dispatch_usec} usec host-side "
                f"dispatch total; DMA wall {self.transfer_usec} usec)")

    def drain_to(self, max_inflight: int) -> None:
        """Drain the ring until at most max_inflight transfers are in
        flight — the dlpack-stability helper for host-buffer reuse: a
        caller about to rewrite a buffer submitted k transfers ago
        drains to k-1 first, making the alias provably released."""
        while len(self._ring) > max(max_inflight, 0):
            self._drain_one()

    def poison(self) -> None:
        """Drop every in-flight entry WITHOUT completion waits: the chip
        failover path — block_until_ready on a lost chip would hang or
        re-raise, and the data of in-flight transfers is gone either
        way. Timing counters keep what they accumulated."""
        self._ring.clear()

    def reset_counters(self) -> None:
        self.dispatch_usec = 0
        self.transfer_usec = 0
        self.full_stalls = 0
        self.inflight_hwm = 0
        self.ops = 0
        self.ready_wait_usec = 0
        self.ready_wait_ops = 0
        self.resident = array("Q")


class _Landing:
    """One block in a chip's group commit, and what its put left for the
    thread that lands it."""

    __slots__ = ("view", "gate", "done", "arr", "err", "put_usec", "put_ns",
                 "ready_ns", "wait_ns", "led")

    def __init__(self, view):
        self.view = view
        # released when the block is resolved, or to hand its thread the
        # next put: a bare lock, the cheapest wait that drops the GIL
        self.gate = _thread.allocate_lock()
        self.gate.acquire()
        self.done = False
        self.arr = self.err = None
        self.put_usec = 0     # this block's share of its put's host time
        self.put_ns = self.ready_ns = 0  # its put returned / was resident
        self.wait_ns = 0      # its thread's time blocked until then
        self.led = False      # its thread made the put


class H2dGroupCommit:
    """Group commit of the synchronous (depth-1) staged transfers of one
    chip, shared by this process's TpuWorkerContexts on it.

    A thread that lands a block queues it. When no put is in flight on
    the chip, that thread leads: it takes every queued block, its own
    among them, puts them with ONE list ``jax.device_put`` and waits
    once for all of them. Threads that queue meanwhile wait, with the
    GIL released, until their own block is resident. The leader never
    waits for more blocks to come: a put carries what queued during the
    one before it (one block when its thread is alone on the chip), so
    there is no timer and no size. When its put is done, or has raised,
    the leader hands the next put to the oldest queued block's thread;
    an error of a put is raised in the thread of each of its blocks."""

    def __init__(self, device):
        self.device = device
        # a host-backed put aliases the host buffer, which the caller may
        # rewrite as soon as its block is resident: put a copy there
        self._host_backed = device.platform == "cpu"
        self._lock = threading.Lock()
        self._queue: list = []
        self._busy = False

    def land(self, view, stages=None, op=None) -> _Landing:
        """Land one block's host view on the chip and return its landing
        once the block is resident (``arr``); raises its put's error."""
        entry = _Landing(view)
        with self._lock:
            self._queue.append(entry)
            lead = not self._busy
            self._busy = True
        if not lead:
            _timed_wait(entry, stages, op, entry.gate.acquire)
            lead = not entry.done
        if lead:
            self._commit(entry, stages, op)
        if entry.err is not None:
            raise entry.err
        return entry

    def _commit(self, own: _Landing, stages, op) -> None:
        """Put every queued block, as the leader whose block is ``own``."""
        jax = _get_jax()
        with self._lock:
            batch, self._queue = self._queue, []
        try:
            views = [e.view for e in batch]
            if self._host_backed:
                views = [np.array(v) for v in views]
            token = stages.begin("tpu_dispatch") if stages is not None \
                else None
            t0 = time.perf_counter_ns()
            arrs = jax.device_put(views, self.device)
            t1 = time.perf_counter_ns()
            if stages is not None:
                stages.end(token, "tpu_dispatch", "tpu", t0, t1, op,
                           blocks=len(batch))
            ready = _timed_wait(own, stages, op, jax.block_until_ready, arrs)
            share, rest = divmod((t1 - t0) // 1000, len(batch))
            for e, arr in zip(batch, arrs):
                e.arr = arr
                e.put_usec = share
                e.put_ns = t1
                e.ready_ns = ready
            own.put_usec += rest
            own.led = True
        except BaseException as err:  # noqa: BLE001 - each member raises it
            for e in batch:
                e.err = err
        finally:
            with self._lock:
                nxt = self._queue[0] if self._queue else None
                self._busy = nxt is not None
            for e in batch:
                e.done = True
                if e is not own:
                    e.gate.release()
            if nxt is not None:
                nxt.gate.release()


def _timed_wait(entry: _Landing, stages, op, wait, *args) -> int:
    """wait(*args) as an ``h2d_wait`` stage of entry's thread, booked in
    its wait_ns; returns the perf_counter_ns at which it returned."""
    token = stages.begin("h2d_wait") if stages is not None else None
    t0 = time.perf_counter_ns()
    wait(*args)
    t1 = time.perf_counter_ns()
    entry.wait_ns += t1 - t0
    if stages is not None:
        stages.end(token, "h2d_wait", "tpu", t0, t1, op)
    return t1


_group_commits: dict = {}
_group_commits_lock = threading.Lock()


def h2d_group_commit(device) -> H2dGroupCommit:
    """The process's group commit for ``device``, made on first use."""
    key = (device.platform, device.id)
    with _group_commits_lock:
        group = _group_commits.get(key)
        if group is None:
            group = _group_commits[key] = H2dGroupCommit(device)
        return group


class TpuWorkerContext:
    """Per-worker handle to one TPU chip's HBM (CuFileHandleData analogue,
    reference source/CuFileHandleData.h:18-73)."""

    #: device-resident pre-filled source blocks (curand-at-alloc parity)
    _FILL_POOL_BLOCKS = 4

    def __init__(self, chip_id: int, block_size: int, direct: bool = False,
                 verify_on_device: bool = False, pipeline_depth: int = 1,
                 hbm_limit_pct: int = 90, batch_blocks: int = 1,
                 dispatch_budget_usec: int = 0, staging_pool=None):
        jax = _get_jax()
        devices = tpu_devices()
        check_chip_ids([chip_id], len(devices))
        self.chip_id = chip_id
        self.device = devices[chip_id]
        self.block_size = block_size
        self.direct = direct
        self.verify_on_device = verify_on_device
        self.pipeline_depth = max(pipeline_depth, 1)
        # --tpuhbmpct budget enforcement: resident HBM is the fill pool +
        # the in-flight transfer ring + the last-ingested sink block. The
        # pool shrinks and the pipeline depth is clamped to fit the budget;
        # below the 3-block floor (1 pool/in-flight + 1 sink + 1 headroom)
        # the block size is rejected outright.
        self.hbm_budget_bytes = hbm_bytes_limit(self.device, hbm_limit_pct)
        budget_blocks = self.hbm_budget_bytes // max(block_size, 1)
        if budget_blocks < 3:
            raise RuntimeError(
                f"block size {block_size} exceeds the HBM staging budget "
                f"of chip {chip_id} ({self.hbm_budget_bytes} bytes at "
                f"--tpuhbmpct {hbm_limit_pct} fits fewer than 3 blocks)")
        # --tpubatch: coalesce N blocks into one DMA, amortizing the
        # per-transfer dispatch overhead at the price of one extra
        # host-side copy per block. Disabled under on-device verify,
        # which needs per-block arrays.
        self.batch_blocks = max(batch_blocks, 1)
        if verify_on_device and self.batch_blocks > 1:
            from ..toolkits.logger import LOG_NORMAL, log
            log(LOG_NORMAL, "NOTE: --tpubatch is ignored with "
                            "--tpuverify (per-block on-device checks)")
            self.batch_blocks = 1
        self._pool_blocks = min(self._FILL_POOL_BLOCKS,
                                max(budget_blocks - 2, 1))
        # a single aggregated span must itself fit the budget (alongside
        # the sink block and the D2H ring's share): clamp batch_blocks
        # BEFORE it sizes the ring math below, or one --tpubatch DMA
        # could exceed --tpuhbmpct outright
        spare_blocks = max(budget_blocks - self._pool_blocks - 1, 2)
        if self.batch_blocks > spare_blocks // 2:
            clamped = max(spare_blocks // 2, 1)
            from ..toolkits.logger import LOG_NORMAL, log
            log(LOG_NORMAL,
                f"NOTE: --tpubatch {self.batch_blocks} exceeds the HBM "
                f"staging budget; clamped to {clamped}")
            self.batch_blocks = clamped
        # both rings can be live on ONE context in the same phase (rwmix
        # interleaves reads -> H2D in-flight ring with writes -> D2H
        # speculative ring), so the depth clamp budgets for two rings of
        # pipeline_depth slots each — and with batching every H2D slot
        # holds batch_blocks blocks of HBM
        max_depth = max((budget_blocks - self._pool_blocks - 1)
                        // (2 * self.batch_blocks), 1)
        self.pipeline_depth = min(self.pipeline_depth, max_depth)
        self._h2d_agg = None
        self._h2d_agg_fill = 0  # words staged in the active agg buffer
        self._h2d_agg_op = (0, None)  # (op_ns, op) of the span's oldest op
        self._own_pool = None   # private allocator when no worker pool
        if self.batch_blocks > 1:
            # page-aligned host aggregation buffers (64B-aligned for the
            # dlpack export of the --tpudirect path). One buffer per
            # ring slot: a buffer stays aliased by its in-flight direct
            # import until the ring drains it, so the next batch must
            # stage into a different buffer (same rotation discipline
            # as the worker's iodepth I/O buffers). The byte size is
            # rounded up to a uint32 multiple so non-word-aligned block
            # sizes (e.g. -b 6 --tpubatch 3) still view cleanly.
            # Allocation comes from the worker's unified staging pool
            # (same hugepage/NUMA policy, one teardown owner); contexts
            # without a pool (tpubench probes, tests) fall back to a
            # private pool-less slab via a throwaway allocator.
            agg_bytes = self.batch_blocks * max(block_size, 1)
            agg_bytes += (-agg_bytes) % 4
            agg_bytes = max(agg_bytes, 4)
            if staging_pool is None:
                from ..utils.staging_pool import StagingPool
                staging_pool = self._own_pool = StagingPool(
                    1, 4096, register=False, log_rank=None)
            self._h2d_agg_views = staging_pool.alloc_aux(
                max(self.pipeline_depth, 1), agg_bytes)
            self._h2d_agg_ring = [np.frombuffer(mv, dtype=np.uint32)
                                  for mv in self._h2d_agg_views]
            self._h2d_agg_idx = 0
            self._h2d_agg = self._h2d_agg_ring[0]
        self._key = jax.random.PRNGKey(chip_id)
        self._num_words = max(block_size // 4, 1)
        # write-source pool: filled ONCE on first use, like the reference's
        # curandGenerate at allocGPUIOBuffer time (LocalWorker.cpp:1427);
        # device_to_host then only pays the D2H DMA, not per-block RNG.
        # Lazy so read-only workloads never compile the fill kernel.
        self._fill_pool: list = []
        self._fill_idx = 0
        # in-flight H2D transfers (pipelined up to --iodepth / --tpudepth;
        # the completion wait happens when the ring is full or at flush()),
        # with split dispatch-vs-DMA accounting and --tpubudget enforcement
        self._pipeline = TransferPipeline(self.pipeline_depth,
                                          budget_usec=dispatch_budget_usec)
        # donation-based staging-slot reuse (staged path): one HBM block
        # per ring slot, recycled by a donating jitted device-copy step so
        # steady-state ingest re-uses buffers instead of allocating one
        # per block. Latches off on backends without buffer donation.
        self._slot_prev: "list" = [None] * self.pipeline_depth
        self._staged_submits = 0
        self._copy_step = None
        self._donate_ok = True
        self._donate_probed = False
        self.staging_reuses = 0
        self._last_ingested = None
        # --tpudirect path accounting (auditable: a user A/B-ing direct vs
        # staged must be able to see which path actually executed)
        self.h2d_direct_ops = 0
        self.h2d_staged_ops = 0
        self.h2d_direct_fallbacks = 0
        self._direct_warned = False
        # the H2D import and D2H export are INDEPENDENT capabilities of
        # --tpudirect (e.g. on the virtual mesh the export works on every
        # device while the import only aliases onto device 0), so each
        # has its own works/failed latch; self.direct stays the user's
        # intent and is never mutated
        self._h2d_direct_ok = True
        # symmetric D2H audit (write path / --tpubench d2h): direct =
        # zero-copy dlpack export of the device block, staged = np.asarray
        # D2H; prefetch = async D2H issued ahead of consumption
        self.d2h_direct_ops = 0
        self.d2h_staged_ops = 0
        self.d2h_direct_fallbacks = 0
        self.d2h_prefetch_hits = 0
        self.d2h_prefetch_misses = 0
        # the zero-copy D2H export needs host-addressable device memory:
        # TPU HBM is not, so there --tpudirect's D2H leg is the staged
        # copy by design (counted as staged ops, never as a fallback)
        self._d2h_direct_ok = self.device.platform == "cpu"
        self._d2h_warned = False
        # speculative verify-pattern pipeline: (offset, length, salt) ->
        # device block with its D2H already issued. Bounded by
        # pipeline_depth; sequential write streams hit, random streams
        # miss and speculation self-disables after a miss streak.
        self._d2h_spec: dict = {}
        self._d2h_spec_miss_streak = 0
        # fused native-stream loop audit (--tpustream; schema entry in
        # PATH_AUDIT_COUNTERS): ops whose storage I/O ran in the engine's
        # submission/completion ring
        self.stream_fused_ops = 0
        # on-device verify stage counters (PATH_AUDIT_COUNTERS)
        self.verify_sync_usec = 0
        self.verify_expect_usec = 0
        self.verify_ops = 0
        self.verify_pending_hwm = 0
        # on-device verifies dispatched and not yet checked, oldest first:
        # (PendingFingerprint, file_offset, length, salt, op_ns, op, the
        # block's host view). Resolved down to depth-1 with the transfer
        # ring, so a check lags its block by at most depth-1 blocks
        from collections import deque
        self._verify_pending = deque()
        # the op the next host_to_device lands: its storage-submit
        # perf_counter_ns (0: no resident sample) and its span id; set by
        # the worker's block loops, a plain attribute so host_to_device
        # keeps its signature
        self.op_submit_ns = 0
        self.op_id = None
        # per-phase stage-span handle (telemetry/tracer.py StageSpans)
        self.stages = None
        # --tpufallback: chip-failover audit + host-staging degraded mode.
        # chip_failovers is per-phase (PATH_AUDIT_COUNTERS); the
        # host-staging latch persists for the run — a lost chip stays
        # lost (workers/local_worker.py drives the failover decisions)
        self.chip_failovers = 0
        self._host_staging = False
        self._host_sink = None       # host staging: H2D sink buffer
        self._host_fill_pool: list = []  # host staging: write-source pool
        reset_fingerprint(self)
        # the chip's group commit, which lands this context's transfers
        # when they are synchronous staged single blocks: depth 1, no
        # --tpudirect, --tpubatch or --tpuverify (PATH_AUDIT_COUNTERS:
        # its blocks and puts)
        self.h2d_coalesced_blocks = 0
        self.h2d_coalesced_puts = 0
        self._group = h2d_group_commit(self.device) \
            if self.pipeline_depth == 1 and not self.direct \
            and self.batch_blocks == 1 and not self.verify_on_device \
            else None

    # -- chip failover (--tpufallback; the data-plane analogue of
    # --svctolerant: survive single-chip loss instead of aborting) -------

    @property
    def host_staging(self) -> bool:
        """True when the context degraded to host-memory staging after a
        chip loss (--tpufallback host, or chip mode with no survivor)."""
        return self._host_staging

    def _poison_device_state(self) -> None:
        """Drop every reference to device-resident state without touching
        the (possibly dead) chip: in-flight ring entries, staging slots,
        fill pool, speculative D2H blocks, the jitted copy step. No
        block_until_ready anywhere — the chip may never answer again.
        The pending verifies are then checked on the host, from the host
        buffers their blocks were read into (still held: the caller
        rewrites a buffer only after its check resolved)."""
        self._pipeline.poison()
        self._slot_prev = [None] * self.pipeline_depth
        self._staged_submits = 0
        self._copy_step = None
        self._donate_ok = True
        self._donate_probed = False
        self._fill_pool = []
        self._fill_idx = 0
        self._d2h_spec = {}
        self._d2h_spec_miss_streak = 0
        self._last_ingested = None
        self._h2d_agg_fill = 0
        self._verify_pending_on_host()

    def failover_to_chip(self, new_chip_id: int) -> None:
        """Drain-and-poison the failed chip's state, then redirect this
        context to a surviving chip. The caller (LocalWorker) picks the
        survivor and registers the failed chip in the shared poison set
        so sibling workers stop submitting to it."""
        from ..toolkits.logger import log_error
        self._poison_device_state()
        jax = _get_jax()
        old = self.chip_id
        self.chip_id = new_chip_id
        self.device = tpu_devices()[new_chip_id]
        self._key = jax.random.PRNGKey(new_chip_id)
        if self._group is not None:
            self._group = h2d_group_commit(self.device)
        self.chip_failovers += 1
        log_error(f"TPU chip {old} lost; worker failed over to chip "
                  f"{new_chip_id} (--tpufallback chip)")

    def failover_to_host(self) -> None:
        """Degrade to host-memory staging: transfers become host memcpys
        (the accounting keeps flowing so phase results stay complete and
        the TpuChipFailovers counter marks them DEGRADED-TPU). On-device
        verify falls back to the host-side check."""
        from ..toolkits.logger import log_error
        self._poison_device_state()
        self._host_staging = True
        self.verify_on_device = False  # host memcmp takes over
        self.chip_failovers += 1
        log_error(f"TPU chip {self.chip_id} lost; worker degraded to "
                  f"host-memory staging (--tpufallback host)")

    def _host_staged_h2d(self, np_view: np.ndarray) -> None:
        """Host-staging H2D: the staging copy without a device. Counted
        as a staged op so op-count parity checks keep holding."""
        import time
        t0 = time.perf_counter_ns()
        if self._host_sink is None or len(self._host_sink) < len(np_view):
            self._host_sink = np.empty(max(len(np_view), self._num_words),
                                       dtype=np.uint32)
        self._host_sink[:len(np_view)] = np_view
        self.h2d_staged_ops += 1
        self._pipeline.note_dispatch(
            (time.perf_counter_ns() - t0) // 1000)

    def _host_staged_d2h(self, buf: memoryview, length: int,
                         verify_salt: int, file_offset: int) -> None:
        """Host-staging D2H: produce the exact bytes the device path
        would have produced — the verify pattern for --verify phases, a
        deterministic PRNG pool otherwise — so a failed-over write phase
        still writes verifiable content."""
        import time
        t0 = time.perf_counter_ns()
        dst = np.frombuffer(buf, dtype=np.uint8, count=length)
        if verify_salt:
            n_words = length // 8
            arr = np.frombuffer(buf[:n_words * 8], dtype=np.uint64)
            with np.errstate(over="ignore"):
                arr[:] = (np.arange(n_words, dtype=np.uint64)
                          * np.uint64(8) + np.uint64(file_offset)
                          + np.uint64(verify_salt))
            if length % 8:
                dst[n_words * 8:] = 0
        else:
            if not self._host_fill_pool:
                from ..toolkits.random_algos import create_rand_algo
                fill = create_rand_algo("fast", seed=self.chip_id + 1)
                blk = max(self._num_words * 4, 4)
                self._host_fill_pool = [
                    np.frombuffer(fill.fill_buffer(blk), dtype=np.uint8)
                    for _ in range(self._FILL_POOL_BLOCKS)]
            self._fill_idx = (self._fill_idx + 1) \
                % len(self._host_fill_pool)
            src = self._host_fill_pool[self._fill_idx]
            dst[:length] = src[:length]
        self.d2h_staged_ops += 1
        self._pipeline.note_dispatch(
            (time.perf_counter_ns() - t0) // 1000)

    # -- read path: host buffer -> HBM --------------------------------------

    def host_to_device(self, buf: memoryview, length: int,
                       verify_salt: int = 0, file_offset: int = 0) -> None:
        """DMA the freshly-read block into HBM (replaces cudaMemcpyAsync H2D,
        LocalWorker.cpp:2437-2490). With pipeline_depth == 1 (default) the
        call waits for completion so per-block latency stays honest; deeper
        pipelines overlap up to --iodepth transfers and only wait when the
        ring is full (documented pipelined mode, SURVEY.md section 7 "TPU
        transfer overlap"). With --tpuverify, the on-device fingerprint
        check replaces the host-side memcmp; it is deferred like the
        transfer's wait, so a bad block raises (naming its offset) up to
        depth-1 calls later, or at the drain that ends the phase.

        Two transfer paths (the cuFileRead-vs-cudaMemcpy split of the
        reference, LocalWorker.cpp:2633-2749):

        - staged (default): ``jax.device_put`` of the buffer view. The
          call returns before the transfer has read the buffer: on a TPU
          the runtime reads the host buffer until the transfer completes
          (a 16 MiB read at --iodepth 8 landed wrong bytes in HBM when
          the buffer was rewritten at once). Only the transfer's
          completion releases the buffer.
        - direct (--tpudirect): the page-aligned I/O buffer (mmap-backed,
          64B-aligned for O_DIRECT) is exported via dlpack straight into
          the device transfer; on host-backed devices (virtual CPU mesh)
          the import is true zero-copy, and the array aliases the buffer
          until its transfer drains.

        So on both paths a host buffer must not be rewritten before its
        transfer completed (CuFileHandleData register-once discipline,
        reference CuFileHandleData.h:18-73): the drain-to-depth-1 ring
        below provides that to callers that rotate depth buffers, and
        the fused stream loop holds slots back (holdback_depth). A
        pending verify's block stays in its buffer by the same rule, for
        the host check a chip failover makes.
        """
        n_words = length // 4
        np_view = np.frombuffer(buf[:n_words * 4], dtype=np.uint32)
        if self._host_staging:  # degraded after chip loss (--tpufallback)
            self._host_staged_h2d(np_view)
            return
        if self.batch_blocks > 1:
            # --tpubatch: stage into the aggregation buffer; the DMA
            # fires once per batch_blocks blocks (or at flush), so the
            # per-transfer dispatch cost is paid once per batch. The
            # copy releases the caller's I/O buffer immediately, which
            # also means the dlpack stability contract moves to the
            # aggregation buffer (drained before reuse via the ring).
            start = self._h2d_agg_fill
            if not start:
                # the span's residency is booked for its oldest op
                self._h2d_agg_op = (self.op_submit_ns, self.op_id)
            self._h2d_agg[start:start + n_words] = np_view
            self._h2d_agg_fill = start + n_words
            if self._h2d_agg_fill + self._num_words > len(self._h2d_agg):
                self._flush_h2d_batch()
            return
        if verify_salt and self.verify_on_device:
            self._verified_h2d(np_view, length, verify_salt, file_offset)
        else:
            self._transfer_h2d(np_view, self.op_submit_ns, self.op_id)

    def _verified_h2d(self, np_view: np.ndarray, length: int, salt: int,
                      file_offset: int) -> None:
        """Land a block and dispatch its fingerprint kernel with no host
        sync; the block's check waits in the pending FIFO and is resolved
        once more than depth-1 verifies are pending (at depth 1 within
        this call), or by a drain. The ring entry waits on the kernel, so
        the ring's drain guarantee covers the kernel's reads too. On a
        host-backed device the landed block is the host buffer itself,
        and a copy of it taken after the call returned reads the buffer
        too: the previous block's entry then waits on this block's
        kernel, which in-order execution runs after that copy. The
        check's resolve is the op's resident moment."""
        from ..ops import verify
        op = self.op_id
        queued = []

        def dispatch(arr):
            st = self.stages
            token = st.begin("verify_sync") if st is not None else None
            t0 = time.perf_counter_ns()
            fp = verify.fingerprint_block_pallas(arr)
            _d2h_async(fp.array)  # the read-back streams behind the kernel
            if self.device.platform == "cpu":
                self._pipeline.wait_newest_on(fp.array)
            t1 = time.perf_counter_ns()
            self.verify_sync_usec += (t1 - t0) // 1000
            if st is not None:
                st.end(token, "verify_sync", "tpu", t0, t1, op)
            queued.append((fp, file_offset, length, salt, self.op_submit_ns,
                           op, np_view))
            return fp.array

        self._transfer_h2d(np_view, 0, op, then=dispatch)
        # queued only once landed: a device loss before this point makes
        # the guarded caller land the block again
        entry = queued[0]
        pending = self._verify_pending
        pending.append(entry)
        try:
            self._resolve_verifies(self.pipeline_depth - 1)
        except Exception:
            if pending and pending[-1] is entry:
                pending.pop()  # not checked: the retry lands it again
            raise
        if len(pending) > self.verify_pending_hwm:
            self.verify_pending_hwm = len(pending)

    def _resolve_verifies(self, max_pending: int) -> None:
        """Check the oldest pending verifies until at most max_pending
        remain, each timed in two stages: the read of its fingerprint
        (one host read), then the host's expected value. A bad block
        raises ValueError naming its own offset."""
        from ..ops import verify
        pending = self._verify_pending
        st = self.stages
        while len(pending) > max(max_pending, 0):
            fp, file_offset, length, salt, op_ns, op, _view = pending[0]
            token = st.begin("verify_sync") if st is not None else None
            t0 = time.perf_counter_ns()
            got = fp.result()
            t1 = time.perf_counter_ns()
            pending.popleft()  # after the read: a lost chip leaves it queued
            if st is not None:
                st.end(token, "verify_sync", "tpu", t0, t1, op)
                token = st.begin("verify_expect")
            want = verify.expected_fingerprint_host(file_offset, length, salt)
            t2 = time.perf_counter_ns()
            self.verify_sync_usec += (t1 - t0) // 1000
            self.verify_expect_usec += (t2 - t1) // 1000
            self.verify_ops += 1
            if op_ns:
                self._pipeline.resident.append((t1 - op_ns) // 1000)
            if st is not None:
                st.end(token, "verify_expect", "tpu", t1, t2, op)
                if op is not None:
                    st.record("op", "op", op_ns, t2, op)
            verify.check_fingerprint(got, want, file_offset)
            fold_fingerprint(self, *got)

    def _verify_pending_on_host(self) -> None:
        """Check every pending verify on the host, from its block's host
        buffer: the chip that holds the block and its kernel's result may
        never answer again (chip failover). Same check, same fold."""
        from ..ops import verify
        pending = self._verify_pending
        if pending:
            from ..toolkits.logger import LOG_NORMAL, log
            log(LOG_NORMAL, f"NOTE: chip {self.chip_id} lost with "
                            f"{len(pending)} on-device verify(s) pending; "
                            f"checking them on the host")
        while pending:
            _fp, file_offset, length, salt, _op_ns, _op, view = \
                pending.popleft()
            got = verify.fingerprint_host(view)
            verify.check_fingerprint(
                got, verify.expected_fingerprint_host(file_offset, length,
                                                      salt), file_offset)
            fold_fingerprint(self, *got)

    #: read access to the pipeline's ring for tests/diagnostics (the ring
    #: discipline itself lives in TransferPipeline)
    @property
    def _inflight(self):
        return self._pipeline._ring

    @property
    def pipe_full_stalls(self) -> int:
        return self._pipeline.full_stalls

    @property
    def pipe_inflight_hwm(self) -> int:
        return self._pipeline.inflight_hwm

    @property
    def pipe_ready_wait_usec(self) -> int:
        return self._pipeline.ready_wait_usec

    @property
    def pipe_ready_wait_ops(self) -> int:
        return self._pipeline.ready_wait_ops

    def take_resident_usec(self) -> array:
        """The op-resident latencies (usec) booked since the last call;
        the pipeline starts a fresh buffer."""
        vals, self._pipeline.resident = self._pipeline.resident, array("Q")
        return vals

    @property
    def dispatch_usec(self) -> int:
        """Host-side submit cost this phase (both directions)."""
        return self._pipeline.dispatch_usec

    @property
    def transfer_usec(self) -> int:
        """DMA wall time this phase (both directions)."""
        return self._pipeline.transfer_usec

    def _transfer_h2d(self, np_view: np.ndarray, op_ns: int = 0,
                      op=None, then=None) -> None:
        """One DMA into the in-flight pipeline (a block, or a --tpubatch
        aggregation span). The staged path recycles per-slot HBM staging
        buffers through a donating jitted copy (see _staged_submit); the
        direct path imports the host buffer as-is (zero-bounce). A
        depth-1 staged block lands through the chip's group commit
        instead (H2dGroupCommit), as its own array of one list put, with
        no slot copy. op_ns, op and then: see TransferPipeline.submit."""
        group = self._group
        if self.direct and self._h2d_direct_ok:
            arr = self._pipeline.submit(
                lambda: self._direct_import(np_view), op_ns, op, then)
        elif group is not None:
            landing = self._pipeline.land_grouped(group, np_view, op_ns, op)
            arr = landing.arr
            self.h2d_staged_ops += 1
            self.h2d_coalesced_blocks += 1
            self.h2d_coalesced_puts += landing.led
        else:
            arr = self._pipeline.submit(
                lambda: self._staged_submit(np_view), op_ns, op, then)
        self._last_ingested = arr  # keep resident (benchmark sink)

    def _staged_submit(self, np_view: np.ndarray):
        """device_put of the block, then — when a drained staging slot of
        matching shape exists — a donation-based jitted device copy into
        it (``staged_slot_copy``), so the slot's HBM buffer is reused
        instead of re-allocated per block (the allocGPUIOBuffer-once
        discipline of the reference, LocalWorker.cpp:1427: buffers live
        for the worker's lifetime).

        The device_put is asynchronous: it may still read ``np_view``
        after this returns. The array returned (the copy's output, which
        waits on the transfer) is ready only once the host buffer has
        been read, so the caller may rewrite the buffer after the ring
        drained this entry, not before.

        The slot rotation mirrors the ring: a slot is reused exactly
        depth staged SUBMITS later (a dedicated counter — pipeline.ops
        also counts D2H note_dispatch entries, so keying on it would
        reuse, and donate, a slot whose array is still in the in-flight
        ring on mixed H2D/D2H phases), by which point the ring — at most
        depth-1 deep after every drain — has drained it."""
        jax = _get_jax()
        placed = jax.device_put(np_view, self.device)
        self.h2d_staged_ops += 1
        if not self._donate_ok:
            return placed
        slot = self._staged_submits % self.pipeline_depth
        self._staged_submits += 1
        prev = self._slot_prev[slot]
        arr = placed
        if prev is not None and prev.shape == placed.shape \
                and prev.dtype == placed.dtype:
            try:
                arr = self._donated_copy(prev, placed)
                self.staging_reuses += 1
            except Exception:  # noqa: BLE001 - donation unsupported
                self._donate_ok = False
                arr = placed
        self._slot_prev[slot] = arr
        return arr

    def _donated_copy(self, dst, src):
        """dst <- src on device, donating dst so XLA reuses its buffer for
        the output (jax's canonical in-place update pattern). Probed once:
        a backend that ignores donation warns instead of reusing — latch
        the copy step off there rather than paying a copy for nothing."""
        jax = _get_jax()
        if self._copy_step is None:
            self._copy_step = jax.jit(staged_slot_copy, donate_argnums=(0,))
        if not self._donate_probed:
            self._donate_probed = True
            import warnings
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                out = self._copy_step(dst, src)
            if any("donat" in str(w.message).lower() for w in caught):
                self._donate_ok = False
                raise RuntimeError("buffer donation unsupported")
            return out
        return self._copy_step(dst, src)

    def _flush_h2d_batch(self) -> None:
        if self._h2d_agg_fill:
            self._transfer_h2d(self._h2d_agg[:self._h2d_agg_fill],
                               *self._h2d_agg_op)
            # rotate to the next aggregation buffer: the one just
            # transferred may stay aliased by a direct import until the
            # ring drains it (by then the rotation has cycled past it)
            self._h2d_agg_idx = (self._h2d_agg_idx + 1) \
                % len(self._h2d_agg_ring)
            self._h2d_agg = self._h2d_agg_ring[self._h2d_agg_idx]
            self._h2d_agg_fill = 0

    def _direct_import(self, np_view: np.ndarray):
        """Zero-bounce dlpack import of the I/O buffer (--tpudirect).
        On a host-backed device (virtual CPU mesh) copy=False demands a
        true zero-copy alias — a buffer that would need a hidden copy
        (e.g. sub-64B-aligned) falls back LOUDLY instead of silently
        degrading. On a real TPU the host->HBM copy is inherent (there is
        no storage->HBM DMA engine); what direct skips is the framework's
        defensive staging/dispatch layer: the registered page-aligned
        buffer goes straight into the PjRt import. One note + counted
        fallback to the staged path on any export failure."""
        jax = _get_jax()
        try:
            from jax import dlpack as jax_dlpack
            copy_mode = False if self.device.platform == "cpu" else None
            arr = jax_dlpack.from_dlpack(np_view, device=self.device,
                                         copy=copy_mode)
            self.h2d_direct_ops += 1
            return arr
        except Exception as err:  # noqa: BLE001 - any export failure
            if not self._direct_warned:
                self._direct_warned = True
                from ..toolkits.logger import log, LOG_NORMAL
                log(LOG_NORMAL,
                    f"NOTE: --tpudirect dlpack export failed for chip "
                    f"{self.chip_id} ({err}); falling back to the staged "
                    f"transfer path for this run")
            # the I/O buffers are fixed for the worker's lifetime, so one
            # failed import means they all fail: latch the H2D side off
            # so the hot loop doesn't pay a raise per block (the D2H
            # export is an independent capability and keeps its own latch)
            self._h2d_direct_ok = False
            self.h2d_direct_fallbacks += 1
            self.h2d_staged_ops += 1
            return jax.device_put(np_view, self.device)

    def holdback_depth(self) -> int:
        """How many freshly-ingested staging slots the fused stream loop
        must keep OUT of the engine's ring after their host_to_device.
        An unbatched transfer reads the host buffer until it completes,
        on both paths (host_to_device): a --tpudirect import aliases it,
        and a staged device_put reads it after the call returned. The
        pipeline holds at most depth-1 transfers after every submit, so
        holding the last depth-1 ingested slots is exactly the drain
        guarantee both need; 0 at depth 1, where every submit drains.
        The --tpubatch path copies each block into its own aggregation
        ring at host_to_device and needs no holdback."""
        if self.batch_blocks == 1:
            return max(self.pipeline_depth - 1, 0)
        return 0

    def set_stages(self, stages) -> None:
        """Arm (or, with None, disarm) the phase's stage spans on this
        context and its transfer pipeline (telemetry/tracer.py
        StageSpans)."""
        self.stages = stages
        self._pipeline.stages = stages

    def drain_to(self, max_inflight: int) -> None:
        """Drain the in-flight transfer ring to at most max_inflight
        entries (see TransferPipeline.drain_to): the explicit form of
        the buffer-rotation guarantee for callers that reuse host
        buffers on their own schedule — the fused stream loop calls it
        to release a held-back staging slot without waiting for more
        storage completions. The pending verifies resolve down to the
        same bound, so a released slot's block was checked too."""
        self._pipeline.drain_to(max_inflight)
        self._resolve_verifies(max_inflight)
        if max_inflight <= 0:
            self._settle_host_buffers()

    def reset_path_counters(self) -> None:
        """Zero the H2D/D2H path-audit counters (called from the worker's
        per-phase reset_stats so each phase record reports its own ops,
        consistent with the phase-reset TpuHbmBytes). Speculation state
        resets with them: a random-offset phase must not leave prefetch
        disabled for a later sequential phase, and stale speculated
        blocks must not charge a miss to the next phase's record."""
        for attr, _key, _ingest in PATH_AUDIT_COUNTERS:
            # pipeline-owned counters reset below; worker-owned counters
            # (io_retries & co) reset in Worker.reset_stats — creating
            # zeros for them HERE would shadow the worker's real counts
            # in sum_path_audit_counters
            if not attr.startswith("pipe_") \
                    and attr not in PATH_AUDIT_WORKER_ATTRS:
                setattr(self, attr, 0)
        # dispatch/transfer timing and the ring audit are per-phase like
        # the rest; an interrupted phase must also drain its in-flight
        # window so the next phase starts with an empty ring
        self._pipeline.flush(check_budget=False)
        self._pipeline.reset_counters()
        # a failed phase's unchecked verifies are dropped, never folded
        self._verify_pending.clear()
        self._d2h_spec.clear()
        self._d2h_spec_miss_streak = 0
        reset_fingerprint(self)
        # a phase that ended without reaching flush() (worker error /
        # interrupt) must not leak its staged-but-untransferred batch
        # blocks into the next phase's first span
        self._h2d_agg_fill = 0

    def flush(self) -> None:
        """Drain all pipelined transfers (phase-end completion wait),
        including a partially-filled --tpubatch aggregation span, check
        every pending verify, then enforce --tpubudget against the
        measured dispatch overhead."""
        if self._h2d_agg_fill:
            self._flush_h2d_batch()
        self._pipeline.flush(check_budget=False)
        self._resolve_verifies(0)
        self._settle_host_buffers()
        self._pipeline.check_budget()

    def _settle_host_buffers(self) -> None:
        """After a drain to 0, before the caller reuses every buffer: on a
        host-backed device the landed block is its host buffer, and a
        copy of it taken after the call that landed it reads the buffer
        too. Wait for all work dispatched so far, through one more op on
        the device, which in-order execution runs after them."""
        if self._last_ingested is not None \
                and self.device.platform == "cpu":
            _get_jax().numpy.zeros((), np.uint32, device=self.device) \
                .block_until_ready()

    def warmup_transfer(self) -> None:
        """Run one staged ingest outside any timed loop so first-use costs
        (the donating copy step's jit compile, transfer-path setup) never
        land inside a measured phase or charge against --tpubudget; the
        counters are reset afterwards (call from worker prepare when the
        workload ingests into HBM)."""
        probe = np.zeros(self._num_words, dtype=np.uint32)
        if self._group is not None:
            self._group.land(probe)  # a list put: no program to compile
        else:
            # depth+1 submits so the first slot is REUSED once: that
            # reuse is what compiles (and donation-probes) the copy step
            for _ in range(self.pipeline_depth + 1):
                self._pipeline.submit(lambda: self._staged_submit(probe))
        self._pipeline.flush(check_budget=False)
        self._pipeline.reset_counters()
        self.h2d_staged_ops = 0
        self.staging_reuses = 0
        self._last_ingested = None

    def _ensure_fill_pool(self) -> None:
        if not self._fill_pool:
            jax = _get_jax()
            from ..ops.fill import random_block_u32
            for i in range(self._pool_blocks):
                key = jax.random.fold_in(self._key, i)
                arr = random_block_u32(key, self._num_words)
                _d2h_async(arr)  # host copies stream while later blocks fill
                self._fill_pool.append(arr)

    def warmup_fill(self) -> None:
        """Build the HBM fill pool ahead of the first measured phase so the
        jit compile never lands inside a timed loop (call from worker
        prepare when the workload includes device-originated writes)."""
        self._ensure_fill_pool()
        _get_jax().block_until_ready(self._fill_pool[-1])

    # -- write path: HBM -> host buffer --------------------------------------

    #: consecutive speculation misses before the verify-pattern prefetch
    #: pipeline concludes the offset stream is not sequential and stops
    #: wasting device compute + HBM on mispredicted blocks
    _D2H_SPEC_MISS_LIMIT = 8

    def device_to_host(self, buf: memoryview, length: int,
                       verify_salt: int = 0, file_offset: int = 0) -> None:
        """Write-source block originates in HBM (on-device PRNG fill, or the
        on-device verify pattern when --verify is active) and is DMA'd to
        the host I/O buffer (replaces curandGenerate + cudaMemcpy D2H,
        LocalWorker.cpp:1427-1537; the reference's GPU path is symmetric,
        cudaMemcpyAsync D2H :2437-2490 — this is the symmetric TPU leg).

        Pipelined like the H2D ring, with the roles flipped:

        - pool path (plain writes): every pool block's host copy is
          issued asynchronously at fill time, so steady-state calls only
          pay the copy into the I/O buffer, never a blocking D2H.
        - verify path (--verify): block content depends on file_offset,
          so the ring speculates — after serving offset o it precomputes
          the patterns for o+len .. o+depth*len on device and issues
          their D2H transfers; a sequential write stream then always
          consumes an already-in-flight block (d2h_prefetch_hits), while
          a random stream misses (d2h_prefetch_misses) and speculation
          self-disables after a miss streak. Depth rides --iodepth
          (pipeline_depth), reusing the H2D ring's HBM budget allowance —
          a phase is either reading (H2D ring live) or writing (D2H
          ring live), never both on the same context.
        - the final hop into the caller's I/O buffer uses a zero-copy
          dlpack export of the device block when --tpudirect is active
          on a host-backed backend; on a TPU it is the staged
          np.asarray, whose async copy the ring already started.
        """
        import time
        if self._host_staging:  # degraded after chip loss (--tpufallback)
            self._host_staged_d2h(buf, length, verify_salt, file_offset)
            return
        n_words = max(length // 4, 1)
        t0 = time.perf_counter_ns()
        if verify_salt:
            arr = self._verify_block_pipelined(length, n_words,
                                               verify_salt, file_offset)
        else:
            # cycle the pre-filled HBM pool (curand-at-alloc parity)
            self._ensure_fill_pool()
            self._fill_idx = (self._fill_idx + 1) % len(self._fill_pool)
            arr = self._fill_pool[self._fill_idx]
            if n_words != self._num_words:
                arr = arr[:n_words]
        t1 = time.perf_counter_ns()
        # host-side submit cost (pattern/spec issue, pool rotation) vs the
        # blocking export wait: the D2H leg of the dispatch-vs-DMA split
        self._pipeline.note_dispatch((t1 - t0) // 1000)
        host = self._d2h_export(arr)
        self._pipeline.note_transfer((time.perf_counter_ns() - t1) // 1000)
        # single copy into the I/O buffer (tobytes() + slice-assign would
        # add two more full-block copies on this hot path)
        dst = np.frombuffer(buf, dtype=np.uint8, count=length)
        np.copyto(dst[:n_words * 4], host.view(np.uint8)[:length])
        if length % 4:  # trailing sub-word bytes the u32 view can't carry
            dst[n_words * 4:] = 0
        if verify_salt and length % 8:
            dst[(length // 8) * 8:] = 0

    def _verify_block_pipelined(self, length: int, n_words: int,
                                verify_salt: int, file_offset: int):
        """Serve the verify-pattern block for file_offset, preferably from
        the speculative ring, and re-arm speculation for the sequential
        continuation of the stream."""
        from ..ops.fill import verify_pattern_block_u32
        arr = self._d2h_spec.pop((file_offset, length, verify_salt), None)
        if arr is not None:
            self.d2h_prefetch_hits += 1
            self._d2h_spec_miss_streak = 0
        else:
            if self._d2h_spec:
                # mispredicted stream: the speculated blocks are stale
                # (their offsets will never be asked for in order)
                self.d2h_prefetch_misses += 1
                self._d2h_spec_miss_streak += 1
                self._d2h_spec.clear()
            arr = verify_pattern_block_u32(
                _split_u64_params(file_offset, verify_salt), n_words)
            _d2h_async(arr)
        # evaluated AFTER miss accounting so the ring cannot re-arm on
        # the very call whose miss reached the limit
        if self._d2h_spec_miss_streak < self._D2H_SPEC_MISS_LIMIT:
            # speculate the sequential continuation up to ring depth
            for k in range(1, self.pipeline_depth + 1):
                if len(self._d2h_spec) >= self.pipeline_depth:
                    break
                nxt = (file_offset + k * length, length, verify_salt)
                if nxt in self._d2h_spec:
                    continue
                spec_arr = verify_pattern_block_u32(
                    _split_u64_params(nxt[0], verify_salt), n_words)
                _d2h_async(spec_arr)
                self._d2h_spec[nxt] = spec_arr
        return arr

    def _d2h_export(self, arr) -> np.ndarray:
        """Host ndarray of a device block. Direct (--tpudirect on a
        host-backed device): zero-copy dlpack export — the device buffer
        IS the host memory, so the only copy left is the one into the
        I/O buffer (cudaMemcpy-D2H-into-registered-buffer analogue). A
        failed export falls back LOUDLY to the staged np.asarray path
        (whose transfer the async ring already started) and stays
        disabled so the hot loop doesn't pay a raise per block."""
        if self.direct and self._d2h_direct_ok:
            try:
                host = np.from_dlpack(arr)
                self.d2h_direct_ops += 1
                return host
            except Exception as err:  # noqa: BLE001 - any export failure
                self._d2h_direct_ok = False
                self.d2h_direct_fallbacks += 1
                if not self._d2h_warned:
                    self._d2h_warned = True
                    from ..toolkits.logger import log, LOG_NORMAL
                    log(LOG_NORMAL,
                        f"NOTE: --tpudirect D2H dlpack export failed for "
                        f"chip {self.chip_id} ({err}); falling back to "
                        f"the staged transfer path for this run")
        self.d2h_staged_ops += 1
        return np.asarray(arr)

    def close(self) -> None:
        # teardown drain: no --tpubudget check here — a breach surfaces at
        # the phase-end flush(), never as a secondary error mid-cleanup
        if self._h2d_agg_fill:
            self._flush_h2d_batch()
        self._pipeline.flush(check_budget=False)
        self._verify_pending.clear()
        self._last_ingested = None
        self._slot_prev = [None] * self.pipeline_depth
        self._fill_pool = []
        self._d2h_spec = {}
        if self._h2d_agg is not None:
            self._h2d_agg = None
            self._h2d_agg_ring = []
            self._h2d_agg_views = []
        if self._own_pool is not None:
            # contexts without a worker pool own their aggregation slab
            self._own_pool.close()
            self._own_pool = None


def reset_fingerprint(holder) -> None:
    """Zero the per-phase data fingerprint of a worker or context."""
    holder.tpu_fp_sum = holder.tpu_fp_xor = holder.tpu_fp_blocks = 0


def fold_fingerprint(holder, block_sum: int, block_xor: int) -> None:
    """Fold one on-device block fingerprint into the phase's (wrapping
    uint32 sum, xor): both are order-independent, so a whole dataset
    read through any layout of chips folds to the same pair."""
    holder.tpu_fp_sum = (holder.tpu_fp_sum + block_sum) & 0xFFFFFFFF
    holder.tpu_fp_xor ^= block_xor
    holder.tpu_fp_blocks += 1


def staged_slot_copy(dst, src):
    """The staged path's per-block device program: the block just put on
    the device copied into a recycled staging slot (dst, donated). Its
    program is ``jit_staged_slot_copy`` in a device trace."""
    return _get_jax().lax.dynamic_update_slice(dst, src, (0,))


def _d2h_async(arr) -> None:
    """Start the device->host copy of arr without blocking (jax caches
    the host copy on the array; a later np.asarray completes instantly
    once the DMA lands). Best-effort: backends without the method just
    stay synchronous."""
    try:
        arr.copy_to_host_async()
    except Exception:  # pragma: no cover - non-jax.Array or old backend
        pass


def _split_u64_params(file_offset: int, salt: int):
    """(base_lo, base_hi) uint32 halves of (offset + salt) mod 2^64 for the
    on-device pattern kernel."""
    base = (file_offset + salt) & ((1 << 64) - 1)
    return (np.uint32(base & 0xFFFFFFFF), np.uint32(base >> 32))


def hbm_bytes_limit(device, pct: int) -> int:
    """--tpuhbmpct: usable HBM staging budget for a chip. A CPU device's
    memory is the host's RAM; any other device that reports no limit is
    an error, not a guess."""
    stats = device.memory_stats() or {}
    total = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not total and device.platform == "cpu":
        total = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    if not total:
        raise RuntimeError(
            f"cannot size the HBM of {device} ({device.device_kind}): "
            f"memory_stats() reports no bytes_limit")
    return int(total) * pct // 100
